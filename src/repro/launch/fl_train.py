"""Federated training driver — the paper's experiment, end to end.

Runs FedAvg on a synthetic MNIST/CIFAR-like dataset (or a reduced LLM
workload) under a chosen selection policy and reports accuracy-vs-round
plus the load-metric statistics (Var[X], cohort sizes) against theory.
Driven through the unified engine API: any registered policy or
aggregator name works here without touching the round loop.

Examples:
  PYTHONPATH=src python -m repro.launch.fl_train --dataset mnist \
      --policy markov --rounds 60
  PYTHONPATH=src python -m repro.launch.fl_train --dataset mnist --noniid \
      --policy random --rounds 60
  PYTHONPATH=src python -m repro.launch.fl_train --policy markov_hetero \
      --rounds 40                        # per-client-rate Markov chains
  PYTHONPATH=src python -m repro.launch.fl_train --arch tinyllama-1.1b \
      --policy markov --rounds 20        # reduced-LLM federated workload
"""
from __future__ import annotations

import argparse

from repro.compile_cache import enable_compile_cache
from repro.core import load_metric
from repro.engine import RunConfig, SyncEngine, run_engine
from repro.fl.rounds import rounds_to_target
from repro.launch._fl_cli import (
    add_common_args,
    build_run_config,
    build_task,
    print_defense_stats,
    print_tier_stats,
    write_result,
)

DEFAULTS = {"rounds": 60, "clients": 100, "local_epochs": 5, "lr": 0.1}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    add_common_args(ap, DEFAULTS)
    ap.add_argument("--target-acc", type=float, default=None)
    return ap


def build_config(args: argparse.Namespace) -> RunConfig:
    return build_run_config(args, mode="sync", eval_div=30)


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    task = build_task(args)
    cfg = build_config(args)
    engine = SyncEngine(task, cfg)
    print(f"policy={cfg.policy} n={cfg.n_clients} k={cfg.k} m={cfg.m} "
          f"rounds={cfg.rounds} aggregator={cfg.resolved_aggregator()} "
          f"chunk={cfg.resolved_steps_per_chunk()}"
          + (f" cohort=sharded/x{engine.mesh_shards}"
             if cfg.shard_cohort else "")
          + (f" topology={cfg.topology_name()}" if cfg.topology else ""))
    res = run_engine(engine, progress=True)

    stats = res.load_stats
    print("\n== load metric X ==")
    print(f"empirical: E[X]={stats['mean_X']:.3f} Var[X]={stats['var_X']:.3f} "
          f"(samples {stats['num_samples']})")
    print(f"theory   : E[X]={cfg.n_clients / cfg.k:.3f} "
          f"Var random={load_metric.random_selection_var(cfg.n_clients, cfg.k):.3f} "
          f"Var markov*={load_metric.optimal_var(cfg.n_clients, cfg.k, cfg.m):.3f}")
    print(f"cohort   : mean={stats['mean_cohort']:.2f} std={stats['std_cohort']:.2f} "
          f"range [{stats['min_cohort']}, {stats['max_cohort']}]")
    injected = {k[len("fault_"):-len("_injected")]: v for k, v in stats.items()
                if k.startswith("fault_") and k.endswith("_injected")}
    if injected:
        print("faults injected: " + ", ".join(
            f"{nm}={int(v)}" for nm, v in injected.items()))
    agg_stats = {k[len("agg_"):]: v for k, v in stats.items()
                 if k.startswith("agg_")}
    if agg_stats:
        print("robust aggregation: " + ", ".join(
            f"{nm}={int(v)}" for nm, v in agg_stats.items()))
    print_defense_stats(res.load_stats)
    print_tier_stats(res.load_stats)
    if args.target_acc:
        r = rounds_to_target(res.history(), args.target_acc)
        print(f"rounds to {args.target_acc:.0%}: {r}")
    write_result(args.out, res, args)


if __name__ == "__main__":
    main()

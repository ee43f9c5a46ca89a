"""Asynchronous federated training driver — the paper's experiment under
wall-clock heterogeneity (stragglers, dropouts, availability windows).

Mirrors ``fl_train`` but runs the event-driven simulator through the same
unified engine API: clients that become available consult their selection
policy (admission control), train on the model version they pulled, and
the server aggregates a buffer of updates per step through the configured
aggregator (staleness-discounted ``fedbuff`` by default, ``fedprox`` for
proximal damping). Load-metric statistics are reported in *simulated
seconds* alongside the round-indexed theory.

Examples:
  PYTHONPATH=src python -m repro.launch.fl_async --policy markov \
      --rounds 40 --clients 200
  PYTHONPATH=src python -m repro.launch.fl_async --latency-profile mobile \
      --policy markov --buffer-size 10 --staleness-weight 0.5
  PYTHONPATH=src python -m repro.launch.fl_async --policy markov_hetero \
      --latency-profile mobile --rounds 30   # per-client-rate admission
  PYTHONPATH=src python -m repro.launch.fl_async --latency-profile uniform \
      --policy random --rounds 30     # degenerate: reduces to sync FedAvg
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.fl_async --mesh-shards 0 \
      --clients 200 --rounds 40       # fleet state sharded over 8 devices
  PYTHONPATH=src python -m repro.launch.fl_async --faults dropout,corrupt \
      --fault-rate 0.1 --robust-agg trimmed_mean \
      --redispatch-timeout 30         # chaos run with graceful degradation
"""
from __future__ import annotations

import argparse

from repro.compile_cache import enable_compile_cache
from repro.core import load_metric
from repro.engine import RunConfig, make_engine, run_engine
from repro.launch._fl_cli import (
    add_common_args,
    build_run_config,
    build_task,
    print_defense_stats,
    print_tier_stats,
    write_result,
)
from repro.sim import PROFILES

# async default: frequent small local updates (FedBuff-style) — with
# per-client shards this small, 5 epochs at lr 0.1 diverges (sync too)
DEFAULTS = {
    "rounds": 40, "clients": 200, "local_epochs": 2, "lr": 0.05,
    "rounds_help": "server steps (buffer flushes)",
}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    add_common_args(ap, DEFAULTS)
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="updates aggregated per server step (default k)")
    ap.add_argument("--latency-profile", default="lognormal",
                    choices=sorted(PROFILES))
    ap.add_argument("--staleness-weight", type=float, default=0.5,
                    help="polynomial discount exponent a in (1+s)^-a; 0 = constant")
    ap.add_argument("--max-versions", type=int, default=8)
    return ap


def build_config(args: argparse.Namespace) -> RunConfig:
    return build_run_config(
        args, mode="async", eval_div=20,
        aggregator_kwargs={
            "staleness_mode": "const" if args.staleness_weight == 0 else "poly",
            "staleness_exp": args.staleness_weight,
        } if (args.aggregator in (None, "fedbuff", "fedprox", "norm_clip")
              and args.robust_agg in (None, "norm_clip")) else {},
        buffer_size=args.buffer_size,
        max_versions=args.max_versions,
        profile=args.latency_profile,
    )


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    task = build_task(args)
    cfg = build_config(args)
    engine = make_engine(task, cfg)
    shards = getattr(engine, "mesh_shards", None)
    print(
        f"async policy={cfg.policy} profile={args.latency_profile} "
        f"n={cfg.n_clients} k={cfg.k} m={cfg.m} buffer={cfg.resolved_buffer_size()} "
        f"steps={cfg.rounds} aggregator={cfg.resolved_aggregator()} "
        f"staleness=(1+s)^-{args.staleness_weight} "
        f"chunk={cfg.resolved_steps_per_chunk()}"
        + (f" mesh_shards={shards}" if shards else "")
        + (" cohort=sharded" if cfg.shard_cohort else "")
        + (f" topology={cfg.topology_name()}" if cfg.topology else "")
    )
    res = run_engine(engine, progress=True)

    ws = res.wall_stats
    print("\n== load metric X (wall clock) ==")
    print(f"simulated time: {ws['sim_time']:.2f}s over {ws['aggregations']} aggregations "
          f"({ws['updates_applied']} client updates)")
    print(f"X_wall : E[X]={ws['mean_X_wall']:.3f}s Var[X]={ws['var_X_wall']:.3f} "
          f"(samples {ws['num_samples_wall']})")
    print(f"X_epoch: E[X]={ws['mean_X_epoch']:.3f} Var[X]={ws['var_X_epoch']:.3f} "
          f"(samples {ws['num_samples_epoch']})")
    print(f"theory (sync rounds): E[X]={cfg.n_clients / cfg.k:.3f} "
          f"Var random={load_metric.random_selection_var(cfg.n_clients, cfg.k):.3f} "
          f"Var markov*={load_metric.optimal_var(cfg.n_clients, cfg.k, cfg.m):.3f}")
    print(f"staleness: mean={ws['mean_staleness']:.2f} max={ws['max_staleness']}")
    if "hb_expired" in ws:
        print(f"heartbeat churn: {ws['hb_expired']} updates expired")
    ls = res.load_stats or {}
    injected = {k[len("fault_"):-len("_injected")]: v for k, v in ls.items()
                if k.startswith("fault_") and k.endswith("_injected")}
    if injected:
        print("faults injected: " + ", ".join(
            f"{nm}={int(v)}" for nm, v in injected.items()))
    if "redispatched" in ls:
        print(f"re-dispatch: {ls['redispatched']} re-sent, "
              f"{ls['rd_expired']} deadline hits")
    agg_stats = {k[len("agg_"):]: v for k, v in ls.items()
                 if k.startswith("agg_")}
    if agg_stats:
        print("robust aggregation: " + ", ".join(
            f"{nm}={int(v)}" for nm, v in agg_stats.items()))
    # load_stats now come from the device-resident accumulators whenever
    # the (rounds, n) history is not materialized — fleet scale included
    if res.load_stats:
        es = res.load_stats
        print(f"dispatch cohorts: mean={es['mean_cohort']:.2f} std={es['std_cohort']:.2f} "
              f"range [{es['min_cohort']}, {es['max_cohort']}]")
        print(f"X_round: E[X]={es['mean_X']:.3f} Var[X]={es['var_X']:.3f} "
              f"(samples {es['num_samples']}, "
              f"{'history' if res.selection is not None else 'accumulators'})")
    print_defense_stats(res.load_stats)
    print_tier_stats(res.load_stats)
    if res.records:
        last = res.records[-1]
        print(f"final: acc={last.accuracy:.4f} eval_loss={last.eval_loss:.4f} "
              f"(v{last.version} @ t={last.clock:.2f}s)")
    write_result(args.out, res, args)


if __name__ == "__main__":
    main()

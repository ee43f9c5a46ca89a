#!/usr/bin/env python3
"""Drives the federated training path on TPU chips and checks what comes out.

    python chip_smoke.py             # one chip: (a) sync paper run, (b) async fleet
    python chip_smoke.py --chips 4   # four chips: the fleet-sharded engine only

Everything runs in this one process, which holds the chips. Tasks and run
configs come from the drivers' own builders (``repro.launch.fl_train`` /
``fl_async``) and run through ``make_engine`` + ``run_engine``, exactly as
a user's run does. Each phase runs ``run_engine`` twice on one engine:
the first pass compiles, the second reuses the compiled chunks, so
"set-up" is the first pass minus the second and "run" is the second.

Each phase prints one line: its sizes, the checks it passed (and any
that failed), and its set-up and run seconds. The last line is one JSON
object naming the device. With no TPU, or on any failed check, the
script exits nonzero before that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# (a) the paper's experiment: CNN at its published widths on the
# synthetic MNIST (12,000 train / 2,000 test), n=100, k=15, m=10
SYNC_FLAGS = [
    "--dataset", "mnist", "--data-scale", "1.0", "--clients", "100",
    "--k", "15", "--m", "10", "--policy", "markov", "--aggregator", "fedavg",
    "--rounds", "60",
]
# (b) async at fleet scale: 4 event_topk tiles of 65,536 clients, k = 15%
# of n, a buffer that is not a multiple of 128, 2 examples (one local
# batch) per client. The buffer is bounded by the (buffer, params) cohort
# stack of the CNN's local training: 2,621 would need ~35 GB of HBM.
ASYNC_FLAGS = [
    "--dataset", "mnist", "--data-scale", "43.7", "--clients", "262144",
    "--k", "39321", "--buffer-size", "500", "--batch-size", "2",
    "--latency-profile", "lognormal", "--rounds", "8", "--no-history",
]
# shard_cohort changes the cohort reduction order, not the arithmetic
COHORT_EVAL_RTOL = 1e-3


class Checks:
    """Every check of a phase is evaluated and reported; the phase fails
    after its line is printed if any of them failed."""

    def __init__(self, phase: str):
        self.phase, self.passed, self.failed = phase, [], []

    def __call__(self, ok: bool, what: str) -> None:
        (self.passed if ok else self.failed).append(what)

    def __str__(self) -> str:
        text = "checks: " + "; ".join(self.passed)
        if self.failed:
            text += " | FAILED: " + "; ".join(self.failed)
        return text


def same(a, b) -> bool:
    """Bitwise-equal pytrees (NaN equals NaN)."""
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    try:
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    except AssertionError:
        return False
    return True


def timed_runs(engine):
    """``run_engine`` twice on one engine; returns the second result, the
    set-up seconds (first pass minus second), the run seconds (second
    pass) and the final engine state of the second pass."""
    from repro.engine import run_engine

    final = {}
    finalize = engine.finalize

    def capture(state, *rest):
        final["state"] = state
        return finalize(state, *rest)

    engine.finalize = capture
    t0 = time.perf_counter()
    run_engine(engine)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = run_engine(engine)
    warm = time.perf_counter() - t0
    return res, cold - warm, warm, final["state"]


def build(driver, flags):
    from repro.launch._fl_cli import build_task

    args = driver.build_parser().parse_args(flags)
    t0 = time.perf_counter()
    task = build_task(args)
    return task, driver.build_config(args), time.perf_counter() - t0


def phase_sync(flags=SYNC_FLAGS) -> tuple[str, Checks]:
    from repro.core import load_metric
    from repro.engine import make_engine
    from repro.launch import fl_train

    check = Checks("sync")
    task, cfg, data_s = build(fl_train, flags)
    res, setup_s, run_s, _ = timed_runs(make_engine(task, cfg))
    recs = res.records
    train = [r.train_loss for r in recs]
    evals = [r.eval_loss for r in recs]
    n, k = cfg.n_clients, cfg.k
    ls = res.load_stats
    var_rand = load_metric.random_selection_var(n, k)
    check(all(map(math.isfinite, train)), f"{len(train)} train losses finite")
    check(all(map(math.isfinite, evals)) and evals[-1] < evals[0],
          f"eval loss falls {evals[0]:.4f} -> {evals[-1]:.4f}")
    check(abs(ls["mean_X"] - n / k) <= 0.1 * n / k,
          f"E[X]={ls['mean_X']:.3f} within 10% of n/k={n / k:.3f}")
    check(ls["var_X"] < var_rand,
          f"Var[X]={ls['var_X']:.3f} < random {var_rand:.3f}")
    return (f"phase {check.phase}: {task.name} n={n} k={k} m={cfg.m} "
            f"policy={cfg.policy} aggregator={cfg.resolved_aggregator()} "
            f"rounds={cfg.rounds} chunk={cfg.resolved_steps_per_chunk()} "
            f"examples/client={task.examples_per_client} | {check}"
            f" | set-up {setup_s:.2f} s (compile: first pass minus "
            f"second), run {run_s:.2f} s, data {data_s:.2f} s"), check


def phase_async(flags=ASYNC_FLAGS) -> tuple[str, Checks]:
    import jax

    from repro.engine import make_engine
    from repro.kernels import ops
    from repro.launch import fl_async
    from repro.sim import events as ev_mod

    check = Checks("async-fleet")
    task, cfg, data_s = build(fl_async, flags)
    b = cfg.resolved_buffer_size()
    runs = {}
    for name, c in (("kernel", cfg),
                    ("reference", dataclasses.replace(cfg, use_kernel=False))):
        engine = make_engine(task, c)
        runs[name] = (engine,) + timed_runs(engine)
    (eng_k, res_k, setup_k, run_k, st_k) = runs["kernel"]
    (_, res_r, setup_r, run_r, st_r) = runs["reference"]
    step_hlo = eng_k._chunk.lower(eng_k.init(), 0, 1, False).as_text()
    check(not ops._interpret() and "tpu_custom_call" in step_hlo,
          "event_topk compiled in the step (tpu_custom_call, not "
          "interpreted)")
    check(same(st_k["ev"], st_r["ev"])
          and same(st_k["load_acc"], st_r["load_acc"]),
          f"event state and load accumulators equal, kernel vs lax.top_k, "
          f"after {cfg.rounds} steps")
    # one more pop on the final fleet state, both paths, slot by slot
    pops = [jax.jit(lambda ev, u=u: ev_mod.pop_events(ev, b, use_kernel=u))(
        st_k["ev"]) for u in (None, False)]
    n_valid = int(np.asarray(pops[1][2]).sum())
    check(same(pops[0], pops[1]) and n_valid > 0,
          f"pop of {b} on the final fleet state equal ({n_valid} valid)")
    losses = [r.train_loss for r in res_k.records + res_r.records]
    evals = [r.eval_loss for r in res_k.records + res_r.records]
    check(all(map(math.isfinite, losses + evals)),
          "train and eval losses finite")
    bitwise = same(st_k["params"], st_r["params"])
    return (f"phase {check.phase}: {task.name} n={cfg.n_clients} k={cfg.k} "
            f"buffer={b} profile={cfg.profile_name()} steps={cfg.rounds} "
            f"examples/client={task.examples_per_client} | {check}"
            f" | params bitwise kernel==reference: {bitwise}"
            f" | set-up s (compile: first pass minus second): kernel "
            f"{setup_k:.2f}, reference {setup_r:.2f} | run s: kernel "
            f"{run_k:.2f}, reference {run_r:.2f} | data {data_s:.2f} s"), check


def phase_sharded(chips: int, flags=ASYNC_FLAGS) -> tuple[str, Checks]:
    """(b)'s fleet on ``ShardedAsyncEngine`` across ``chips`` chips, with
    the cohort replicated ("fleet") and sharded ("cohort"), against
    ``AsyncEngine`` on one device ("single")."""
    import jax

    from repro.engine import make_engine
    from repro.launch import fl_async

    check = Checks(f"sharded-x{chips}")
    task, cfg, data_s = build(fl_async, flags)
    cfgs = {
        "single": cfg,
        "fleet": dataclasses.replace(cfg, mesh_shards=chips),
        "cohort": dataclasses.replace(cfg, mesh_shards=chips,
                                      shard_cohort=True),
    }
    want = set(jax.devices()[:chips])
    engines = {name: make_engine(task, c) for name, c in cfgs.items()}
    check(all(set(engines[name].mesh.devices.flat) == want
              for name in ("fleet", "cohort")),
          f"fleet mesh spans {chips} chips")
    out = {name: timed_runs(eng) for name, eng in engines.items()}
    for name in ("fleet", "cohort"):
        placed = jax.tree.leaves((out[name][3], engines[name].task.client_data))
        check(all(x.sharding.device_set == want for x in placed),
              f"{name}: every state and data array spans all {chips} chips")
    st = {name: r[3] for name, r in out.items()}
    for name in ("fleet", "cohort"):
        check(same(st[name]["ev"], st["single"]["ev"])
              and same(st[name]["load_acc"], st["single"]["load_acc"]),
              f"{name}: event state and load accumulators equal to one "
              f"device")
    ev_of = {name: np.array([r.eval_loss for r in res.records])
             for name, (res, *_) in out.items()}
    rel = float(np.max(np.abs(ev_of["cohort"] - ev_of["fleet"])
                       / np.abs(ev_of["fleet"])))
    check(np.isfinite(ev_of["cohort"]).all() and rel <= COHORT_EVAL_RTOL,
          f"shard_cohort eval loss within rtol {COHORT_EVAL_RTOL:g} of the "
          f"replicated cohort (max rel diff {rel:.2e})")
    diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(jax.tree.leaves(st["fleet"]["params"]),
                               jax.tree.leaves(st["single"]["params"])))
    bitwise = (same(st["fleet"]["params"], st["single"]["params"])
               and same(ev_of["fleet"], ev_of["single"]))
    losses = " | ".join(
        f"{what} losses: " + " / ".join(
            f"{name} " + ",".join(f"{getattr(r, attr):.4f}" for r in res.records)
            for name, (res, *_) in out.items())
        for what, attr in (("eval", "eval_loss"), ("train", "train_loss")))
    return (f"phase {check.phase}: {task.name} n={cfg.n_clients} k={cfg.k} "
            f"buffer={cfg.resolved_buffer_size()} steps={cfg.rounds} | "
            f"{check} | fleet vs one device bitwise (params, eval loss): "
            f"{bitwise}, max |param diff| {diff:.3e} | {losses}"
            " | set-up s (first pass minus second): "
            + ", ".join(f"{n} {r[1]:.2f}" for n, r in out.items())
            + " | run s: " + ", ".join(f"{n} {r[2]:.2f}" for n, r in out.items())
            + f" | data {data_s:.2f} s"), check


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the fleet-sharded engine across 4 chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX found {dev.platform} devices only")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} TPU device(s)")

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache: {cache} ({warm} entries at start)", flush=True)
    if args.chips == 1:
        phases = (phase_sync, phase_async)
    else:
        phases = (lambda: phase_sharded(args.chips),)
    for phase in phases:
        line, check = phase()
        print(line, flush=True)
        if check.failed:
            sys.exit(f"chip_smoke: {check.phase}: check failed: "
                     + "; ".join(check.failed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

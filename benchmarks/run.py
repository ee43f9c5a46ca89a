"""Benchmark harness — one module per paper table/figure plus the
framework deliverables. Prints a ``name,us_per_call,derived`` CSV at the
end (and human-readable tables along the way).

  PYTHONPATH=src python -m benchmarks.run                # all, CPU-budget scale
  PYTHONPATH=src python -m benchmarks.run --only variance,roofline
  PYTHONPATH=src python -m benchmarks.run --paper-scale  # full Figs 2-4 protocol
  PYTHONPATH=src python -m benchmarks.run --out bench.json   # strict-JSON dump
  PYTHONPATH=src python -m benchmarks.run --only async \
      --check benchmarks/baselines/cpu.json              # regression gate

``--check`` compares every timed row against a committed baseline (same
strict-JSON schema as ``--out``) by name and exits nonzero when a row is
slower than ``baseline * (1 + rtol)``. Refresh a stale baseline by
re-running with ``--out`` pointed at the baseline file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


# rows every committed baseline must carry, whatever --only subset is
# being checked: renaming or dropping one of these must fail the gate
# loudly instead of silently shrinking coverage. The hierarchical rows
# come from bench_async_fleet.run_topo on 8 fake devices; the serve row
# from bench_serve.run_serve (single device).
REQUIRED_BASELINE_ROWS = (
    "async_engine_step_n262144_hier64x8",
    "async_engine_step_n262144_hier64x8_sharded8",
    "serve_tick_tinyllama-1.1b_r2s4",
    # chaos stack: armed-fault step cost + the convergence-vs-corruption
    # evidence row (robust aggregation recovering what fedavg loses)
    "faults_step_n100_chaos",
    "faults_robust_recovers_replacement",
    # defense tier: armed-reputation step cost on a calm fleet + the
    # adaptive-vs-static-vs-fedavg recovery evidence row
    "defense_step_n100_armed",
    "defense_adaptive_recovers",
    # collusion-aware detection (norm-invisible sign-flip + coalition
    # recall/FPR gate) and the aggregator-family mtd recovery row
    "defense_collusion_recall",
    "defense_mtd_family_recovers",
)


def check_against_baseline(csv_rows, baseline_path: str, rtol: float) -> int:
    """Compare timed rows to a committed baseline; returns the number of
    regressions (rows slower than baseline * (1 + rtol))."""
    with open(baseline_path) as f:
        payload = json.load(f)
    base = {r["name"]: float(r["us_per_call"]) for r in payload["rows"]}
    absent = [name for name in REQUIRED_BASELINE_ROWS if name not in base]
    if absent:
        print(f"FAIL: baseline {baseline_path} is missing required row(s): "
              f"{', '.join(absent)} (refresh it with --out after running "
              f"the topo section on 8 fake devices)")
        return len(absent)
    regressions, faster, missing = [], [], []
    compared = 0
    print(f"\n== regression check vs {baseline_path} (rtol={rtol}) ==")
    for name, us, _ in csv_rows:
        if us <= 0:  # derived-only rows carry no timing
            continue
        if name not in base or base[name] <= 0:
            missing.append(name)
            continue
        compared += 1
        ratio = us / base[name]
        flag = ""
        if ratio > 1.0 + rtol:
            regressions.append(name)
            flag = "  <-- REGRESSION"
        elif ratio < 1.0 / (1.0 + rtol):
            faster.append(name)
            flag = "  (faster; consider refreshing the baseline)"
        print(f"  {name:40s} {us:12.1f}us vs {base[name]:12.1f}us "
              f"({ratio:5.2f}x){flag}")
    if missing:
        print(f"  [not in baseline: {', '.join(missing)}]")
    if regressions:
        print(f"FAIL: {len(regressions)} row(s) regressed: "
              f"{', '.join(regressions)}")
    elif compared == 0:
        # a gate that compared nothing must not read as green — either
        # the wrong --only subset was checked or every row was renamed
        print("FAIL: no timed row matched the baseline; nothing was "
              "actually checked (wrong --only subset, or rows renamed "
              "without refreshing the baseline?)")
        return 1
    else:
        print(f"OK: no regressions across {compared} compared rows"
              + (f" ({len(faster)} faster than baseline)" if faster else "")
              + (f"; {len(missing)} not in baseline" if missing else ""))
    return len(regressions)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: variance,scheduler,kernels,convergence,"
                         "roofline,async,sharded,topo,serve,faults,defense")
    ap.add_argument("--paper-scale", action="store_true")
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--out", default=None,
                    help="write the CSV rows as strict JSON (NaN-safe)")
    ap.add_argument("--check", default=None, metavar="BASELINE_JSON",
                    help="compare rows against a committed baseline "
                         "(benchmarks/baselines/cpu.json) and exit nonzero "
                         "on regression")
    ap.add_argument("--check-rtol", type=float, default=1.0,
                    help="relative tolerance for --check: a row regresses "
                         "when slower than baseline * (1 + rtol). The "
                         "default is deliberately loose — shared CI boxes "
                         "jitter ~2x; tighten locally for real perf work")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    want = set(args.only.split(",")) if args.only else None

    def on(name):
        return want is None or name in want

    csv_rows = []
    t0 = time.time()
    if on("variance"):
        from benchmarks import bench_variance

        bench_variance.run(csv_rows)
    if on("scheduler"):
        from benchmarks import bench_scheduler_scale

        bench_scheduler_scale.run(csv_rows)
    if on("kernels"):
        from benchmarks import bench_kernels

        bench_kernels.run(csv_rows)
    if on("convergence"):
        from benchmarks import bench_convergence

        bench_convergence.run(csv_rows, rounds=args.rounds,
                              paper_scale=args.paper_scale)
    if on("async"):
        from benchmarks import bench_async_fleet

        bench_async_fleet.run(csv_rows, rounds=args.rounds)
    if on("sharded"):
        from benchmarks import bench_async_fleet

        bench_async_fleet.run_sharded(csv_rows)
        bench_async_fleet.run_cohort(csv_rows)
    if on("topo"):
        from benchmarks import bench_async_fleet

        bench_async_fleet.run_topo(csv_rows)
    if on("serve"):
        from benchmarks import bench_serve

        bench_serve.run_serve(csv_rows)
    if on("faults"):
        from benchmarks import bench_faults

        bench_faults.run(csv_rows, rounds=args.rounds)
    if on("defense"):
        from benchmarks import bench_defense

        bench_defense.run(csv_rows, rounds=args.rounds)
    if on("roofline"):
        from benchmarks import bench_roofline

        bench_roofline.run(csv_rows)

    print(f"\n[{time.time() - t0:.1f}s total]")
    print("\nname,us_per_call,derived")
    for name, us, derived in csv_rows:
        print(f"{name},{us:.1f},{derived}")
    if args.out:
        from repro.engine import dump_json

        dump_json(args.out, {
            "rows": [
                {"name": name, "us_per_call": us, "derived": derived}
                for name, us, derived in csv_rows
            ],
            "total_s": time.time() - t0,
        })
        print("wrote", args.out)
    if args.check:
        if check_against_baseline(csv_rows, args.check, args.check_rtol):
            sys.exit(1)


if __name__ == "__main__":
    main()

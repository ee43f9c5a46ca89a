#!/usr/bin/env python3
"""Reads the numbers the correctness check compares, for a cell, over many
seeds in one process: the program's (the lower readings a limit is set
above), and the control's and a planted fault's (the upper readings it is
set below).

    python bench/control.py --workload <cell> --seeds 11,12,13 [--control-seeds 11,12,13]

The control is the reference put in the program's place and computed one
precision step below the configuration's float32: bfloat16 parameters,
data and arithmetic. The fault is the float32 reference put in the
program's place with half of every local batch left out (the loss is the
mean over the first half). Each seed prints one JSON line with the
readings and the reference's wall time. The benchmark's own runs never
run this.
"""
import argparse
import contextlib
import json
import sys
import time

import run as bench_run  # bench/run.py: puts the checkout on sys.path


@contextlib.contextmanager
def half_batch():
    """The reference's loss over the first half of each batch."""
    from bench import reference

    xent = reference.xent

    def first_half(p, x, y):
        k = max(y.shape[0] // 2, 1)
        return xent(p, x[:k], y[:k])

    reference.xent = first_half
    try:
        yield
    finally:
        reference.xent = xent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    import jax.numpy as jnp

    from bench import oracle
    from bench.cell import load_cell
    from bench.reference import Reference

    cell = load_cell(args.workload)
    bench_run.check_chips(cell)
    bench_run.use_cache()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        data, _, task, engine, run_seed = bench_run.build(cell, seed)
        cfg = engine.cfg
        got = bench_run.compared(engine)
        del engine, task
        steps, chunk = cfg.rounds, cfg.resolved_steps_per_chunk()

        def follow(dtype=jnp.float32):
            return Reference(cell.config, cell.traffic, data, run_seed,
                             dtype).follow(steps, chunk)

        t0 = time.perf_counter()
        ref = follow()
        line = {"seed": seed, "reference_s": time.perf_counter() - t0,
                "program": oracle.compare(got, ref)}
        if seed in control:
            line["control"] = oracle.compare(follow(jnp.bfloat16), ref)
            with half_batch():
                line["half_batch"] = oracle.compare(follow(), ref)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

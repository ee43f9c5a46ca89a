#!/usr/bin/env python3
"""Reads the numbers the correctness check compares, for a cell, over many
seeds in one process: the program's (the lower readings a limit is set
above) and, on the control seeds, the control's and two planted faults'
(the upper readings it is set below).

    python bench/control.py --workload <cell> --seeds 11,12,13 \
        [--control-seeds 11,12,13]

The control is the reference put in the program's place and computed one
precision step below the configuration's float32: bfloat16 parameters,
data and arithmetic. Each fault is the float32 reference put in the
program's place with one thing broken: ``half_batch`` leaves out half of
every local batch (the model's loss is the mean over the first half);
``other_clients`` trains each client on the next client's examples (the
cohort's data routed to the wrong clients). Each seed prints one JSON
line with the readings, the reference's wall time and the process's peak
device memory. The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import sys
import time

import run as bench_run  # bench/run.py: puts the checkout on sys.path


class HalfBatch:
    """A model module whose ``loss`` leaves out the second half of each
    batch; everything else is the module's."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def loss(self, p, x, y):
        k = max(y.shape[0] // 2, 1)
        return self._model.loss(p, x[:k], y[:k])


def other_clients(reference):
    """``reference``'s class with each selected client trained on the next
    client's examples (the last client's on the first's): the cohort's
    data routed to the wrong clients, with no copy of the data."""

    class OtherClients(reference):
        def _train(self, params_of, data, idx, keys, lrs, weights):
            return super()._train(params_of, data, (idx + 1) % self.n, keys,
                                  lrs, weights)

    return OtherClients


def read_seed(cell, seed: int, control: bool) -> dict:
    """One seed's readings: the program's, and on a control seed the
    control's and each fault's. Returns once the seed's arrays are freed."""
    import jax.numpy as jnp

    from bench import oracle
    from bench.reference import Reference

    data, model, task, engine, run_seed = bench_run.build(cell, seed)
    cfg = engine.cfg
    got = bench_run.compared(engine)
    del engine, task
    gc.collect()
    steps, chunk = cfg.rounds, cfg.resolved_steps_per_chunk()

    def follow(model=model, dtype=jnp.float32, ref_class=Reference):
        out = ref_class(model, cell.config, cell.traffic, data, run_seed,
                        dtype).follow(steps, chunk)
        gc.collect()  # the reference's device copy of the data goes with it
        return out

    t0 = time.perf_counter()
    ref = follow()
    line = {"seed": seed, "reference_s": time.perf_counter() - t0,
            "program": oracle.compare(got, ref)}
    if control:
        line["control"] = oracle.compare(follow(dtype=jnp.bfloat16), ref)
        line["half_batch"] = oracle.compare(follow(HalfBatch(model)), ref)
        line["other_clients"] = oracle.compare(
            follow(ref_class=other_clients(Reference)), ref)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from bench.cell import load_cell

    cell = load_cell(args.workload)
    chips = bench_run.check_chips(cell)
    bench_run.use_cache()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        line = read_seed(cell, seed, seed in control)
        gc.collect()
        line["peak_bytes"] = bench_run.peak_bytes(chips)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Runs one traced benchmark run and splits its device time by the engine's
own scopes and its idle gaps by the run loop's own spans.

    python bench/scopes.py --workload <cell> --seed <n> --seconds <s>

The run is the harness's ``--trace 1`` run (``bench/run.py``), and its
result line is printed first, as the harness prints it. The profiler's
file is then also read with ``bench/xplane.py``, which ``bench/trace.py``
does not do: each device op's name path gives the ``jax.named_scope`` of
the engine step it ran under (``admission``, ``dispatch``, ``pop``,
``local_train``, ``aggregate``, ``load_metric``). The last line is one
JSON object, for each program: self milliseconds a step under each scope,
and the ops that carry no scope with their paths; the readings
``sched_ms`` (``admission`` + ``dispatch`` + ``pop`` + ``load_metric``),
``pop_ms``, ``train_ms`` and ``agg_ms``, each a step; and the ten
longest idle gaps, each named by the ``run_engine.*`` span that is the
innermost for the longest part of it ("no span" where none is).
``--keep <dir>`` also copies the profiler's file there. The benchmark's
own runs never run this.
"""
import argparse
import json
import os
import shutil
import sys
import traceback

CHUNK = "jit_chunk"
SCHED = ("admission", "dispatch", "pop", "load_metric")


def readings(scoped, steps: int) -> dict:
    """Milliseconds a step under each scope group of the chunk program
    (``scoped`` has ``scope_s`` and ``program_s``, as ``bench.xplane.Scoped``
    and ``bench.trace.Reduced`` do). A group none of whose scopes ran is
    left out."""
    from bench import xplane

    chunk = scoped.scope_s.get(CHUNK, {})
    groups = {"sched_ms": SCHED, "pop_ms": ("pop",),
              "train_ms": ("local_train",), "agg_ms": ("aggregate",),
              "unattributed_ms": (xplane.UNATTRIBUTED,)}
    out = {name: 1e3 * sum(chunk[n] for n in scopes if n in chunk) / steps
           for name, scopes in groups.items()
           if any(n in chunk for n in scopes)}
    program = scoped.program_s.get(CHUNK, 0.0)
    if program:
        out["chunk_ms"] = 1e3 * program / steps
        out["covered"] = sum(out.get(n, 0.0) for n in (
            "sched_ms", "train_ms", "agg_ms")) / out["chunk_ms"]
    return out


def under_ms(scoped, name: str, steps: int, program: str = CHUNK):
    """Milliseconds a step of device self time under ``name`` in
    ``program``: any ``jax.named_scope`` a model puts in its layers
    (``scoped.under_s``, as ``bench.trace.Reduced`` has it), nested in
    an engine scope or not. None where no op of the program ran under it."""
    s = scoped.under_s.get(program, {}).get(name)
    return None if s is None else 1e3 * s / steps


def summary(scoped, steps: int, n_ops: int = 8) -> dict:
    from bench import xplane

    programs = {}
    for program, by_scope in scoped.scope_s.items():
        loose = sorted(((op, s) for (p, sc, op), s in scoped.op_s.items()
                        if p == program and sc == xplane.UNATTRIBUTED),
                       key=lambda x: -x[1])[:n_ops]
        top = {}
        for scope in by_scope:
            ops = sorted(((op, s) for (p, sc, op), s in scoped.op_s.items()
                          if p == program and sc == scope),
                         key=lambda x: -x[1])[:3]
            top[scope] = [[op, 1e3 * s / steps] for op, s in ops]
        programs[program] = {
            "ms_per_step": {k: 1e3 * v / steps for k, v in by_scope.items()},
            "program_ms_per_step": 1e3 * scoped.program_s.get(program, 0.0)
            / steps,
            "top_ops_ms": top,
            "unattributed_ops": [[op, 1e3 * s / steps, scoped.paths.get(op, "")]
                                 for op, s in loose],
        }
    return {"steps": steps, "readings": readings(scoped, steps),
            "programs": programs,
            "idle_gaps_ms": [[n, 1e3 * s] for n, s in scoped.idle_gaps]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default="",
                    help="directory to copy the profiler's file into")
    args = ap.parse_args(argv)
    import run as bench_run  # bench/run.py: puts the checkout on sys.path
    from bench import trace, xplane
    from bench.cell import load_cell

    cell = load_cell(args.workload)
    reduce_trace = trace.reduce_trace
    found = {}

    def reduce_and_scope(path, devices, *a, **kw):
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(path, os.path.join(
                args.keep, f"{args.workload}-{args.seed}.xplane.pb"))
        try:
            found["scoped"] = xplane.scope_times(path, devices)
        except Exception:  # the harness's own line still prints
            found["error"] = traceback.format_exc()
        return reduce_trace(path, devices, *a, **kw)

    trace.reduce_trace = reduce_and_scope
    try:
        out = bench_run.run(cell, args.seed, args.seconds, True)
    except bench_run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    finally:
        trace.reduce_trace = reduce_trace
    bench_run.emit(out)
    if "scoped" not in found:
        print(json.dumps({"scopes": None, "error": found.get("error")}),
              flush=True)
        return 1
    print(json.dumps({"scopes": summary(found["scoped"],
                                        out["window"]["steps"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

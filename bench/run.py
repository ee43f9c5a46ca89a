#!/usr/bin/env python3
"""Runs one benchmark cell on the chips of this machine and prints one
JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data on the device from the seed and builds the
program's task, both through the configuration's model module
(``bench/models``), and the engine (``repro.engine.make_engine``). It
drives ``repro.engine.run_engine`` over the cell's compared steps, which
compiles every program the window uses and is what the correctness check
reads. The window is one ``run_engine`` call over a whole number of eval
periods: the cell's ``window_steps`` where it fixes them, else about
``--seconds`` long, as sized by the time of one more eval period in
set-up. ``steps_per_s`` is its steps over its wall time.

With ``--trace 1`` the window runs under the profiler, with host spans
around the engine's hooks, and the line carries the per-layer metrics.
After the window the engine is dropped and the plain reference follows
the compared steps; each number compared is printed beside its limit on
the last lines of standard error and under ``checks`` in the result.

With no accelerator, or fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# one fixed directory in the checkout: the path is part of the cache key
CACHE_DIR = ROOT / ".jax_cache"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)


class NoChip(RuntimeError):
    pass


def _hash31(text: str, i: int = 0) -> int:
    h = hashlib.sha256(text.encode()).digest()
    return int.from_bytes(h[4 * i:4 * i + 4], "little") & 0x7FFFFFFF


def seeds(seed: int):
    """(run seed, data seed), each under 2**31, from any whole number."""
    return _hash31(str(int(seed))), _hash31(str(int(seed)), 1)


# candidate run seeds a seed draws from for a window of fixed work; about
# one in five of sync-paper's hits its work
CANDIDATES = 256


def equal_work_seed(cell, seed: int) -> int:
    """The run seed of a cell that fixes its window's work: the first of
    ``seed``'s candidate run seeds whose window trains the cell's number of
    cohort slots (each round's cohort in whole groups), so every seed's
    window does the same work, in another order."""
    import numpy as np

    from bench.reference import cohort_sizes

    work = cell.window_work
    cands = [_hash31(f"{int(seed)}:{j}") for j in range(CANDIDATES)]
    sizes = cohort_sizes(cell.config, cell.traffic, cands, cell.window_steps)
    g = work["group"]
    hits = np.flatnonzero((g * -(-sizes // g)).sum(1) == work["slots"])
    if not hits.size:
        raise ValueError(f"cell {cell.name}: no candidate of seed {seed} "
                         f"trains {work['slots']} slots in its window")
    return cands[int(hits[0])]


class CompileCount:
    """Programs lowered while open (a persistent-cache hit is lowered too)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, name, secs, **kw):
        if self.on and name == self.EVENT:
            self.n += 1


def _spans(engine):
    """Host spans around the engine's hooks, wrapped from outside."""
    import jax

    def wrap(name):
        fn = getattr(engine, name)

        def spanned(*a, **kw):
            with jax.profiler.TraceAnnotation(f"engine.{name}"):
                return fn(*a, **kw)

        setattr(engine, name, spanned)

    for name in ("run_chunk", "evaluate", "record", "finalize"):
        wrap(name)


class HostClock:
    """The longest call of each engine hook and of ``jax.device_get`` (the
    loop's wait for each chunk's outputs), and the time the garbage
    collector ran, while open: where a window's wall time went on the
    host. Wraps from outside and restores on ``close``; changes nothing
    that runs."""

    HOOKS = ("init", "run_chunk", "evaluate", "record", "finalize")

    def __init__(self, engine):
        import jax

        self.longest = {}
        self.gc_s, self.gc_runs, self.on, self._gc_t0 = 0.0, 0, False, None
        for name in self.HOOKS:
            setattr(engine, name, self._timed(name, getattr(engine, name)))
        self._device_get = jax.device_get
        jax.device_get = self._timed("device_get", jax.device_get)
        gc.callbacks.append(self._gc)

    def _timed(self, name, fn):
        def timed(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                if self.on:
                    self.longest[name] = max(self.longest.get(name, 0.0),
                                             time.perf_counter() - t0)

        return timed

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.on and self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_runs += 1

    def close(self):
        import jax

        self.on = False
        jax.device_get = self._device_get
        gc.callbacks.remove(self._gc)

    def summary(self) -> dict:
        return {"longest_call_s": self.longest, "gc_s": self.gc_s,
                "gc_runs": self.gc_runs}


def _trained_clients(cfg, res) -> float:
    """Clients whose update the window aggregated."""
    if res.selection is not None:
        width = cfg.cohort_width()
        return float(sum(min(int(s), width) for s in res.selection.sum(1)))
    return float(res.wall_stats["updates_applied"])


def build(cell, seed: int):
    """The cell's data, task and engine from ``seed``, and its run seed."""
    import jax

    from bench import models
    from repro.engine import make_engine

    conf = cell.config
    run_seed, data_seed = seeds(seed)
    if cell.window_work:
        run_seed = equal_work_seed(cell, seed)
    shards = conf["run"].get("mesh_shards") or 1
    model = models.load(conf)
    data = model.make_data(jax.random.PRNGKey(data_seed), conf, shards)
    task = model.build_task(conf, data)
    cfg = cell.run_config(run_seed, cell.compare_steps)
    return data, model, task, make_engine(task, cfg), run_seed


def compared(engine) -> dict:
    """The program's outputs over ``engine.cfg.rounds`` steps from the
    seed, through ``run_engine`` (set-up: this compiles the window's
    programs)."""
    from bench import oracle
    from repro.engine import run_engine

    capture = oracle.Capture(engine)
    run_engine(engine)
    capture.restore()
    return capture.outputs()


def use_cache() -> None:
    import jax

    CACHE_DIR.mkdir(exist_ok=True)  # jax writes no entry without it
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_chips(cell, require_chip: bool = True):
    """The chips the cell runs on; ``NoChip`` without enough of them."""
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform == "cpu"
                         or len(devices) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} accelerator "
                     f"chip(s); JAX found {len(devices)} "
                     f"{devices[0].platform} device(s)")
    return devices[:cell.chips]


def run(cell, seed: int, seconds: float, trace: bool,
        require_chip: bool = True) -> dict:
    import jax

    from bench import oracle
    from bench.peaks import peaks
    from bench.reference import Reference
    from repro.engine import run_engine

    used = check_chips(cell, require_chip)
    use_cache()
    counter = CompileCount()
    conf = cell.config
    data, model, task, engine, run_seed = build(cell, seed)
    cfg, period = engine.cfg, cell.period
    compare_steps = cfg.rounds
    got = compared(engine)

    period_s = None
    if cell.window_steps:
        periods = cell.window_steps // period
    else:
        engine.cfg = dataclasses.replace(cfg, rounds=period)
        t0 = time.perf_counter()
        run_engine(engine)
        period_s = time.perf_counter() - t0
        periods = max(1, round(seconds / period_s))
    if trace:
        periods = min(periods, int(cell.check["trace_max_periods"]))
    engine.cfg = dataclasses.replace(cfg, rounds=periods * period)

    gc.collect()  # set-up's garbage is set-up's to collect
    log_dir = None
    host = HostClock(engine)
    if trace:
        _spans(engine)
        log_dir = tempfile.TemporaryDirectory()
        jax.profiler.start_trace(log_dir.name)
    counter.on = host.on = True
    window_start = time.time()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        res = run_engine(engine)
        jax.block_until_ready(res.params)
    window_s = time.perf_counter() - t0
    counter.on = False
    host.close()
    if trace:
        jax.profiler.stop_trace()
    mem = peak_bytes(used)
    steps = periods * period
    epc = data.x.shape[1]  # examples per client
    # the local SGD steps of the clients whose update the window
    # aggregated, the examples in each, and the examples its evals ran
    local_steps = (_trained_clients(engine.cfg, res) * cfg.local_epochs
                   * max(epc // cfg.batch_size, 1))
    local_batch = min(cfg.batch_size, epc)
    eval_examples = periods * model.eval_examples(data)
    flops = (local_steps * local_batch * model.train_flops(conf)
             + eval_examples * model.forward_flops(conf))
    slots = None
    if cell.window_work and res.selection is not None:
        g, width = cell.window_work["group"], cfg.cohort_width()
        slots = sum(g * -(-min(int(c), width) // g)
                    for c in res.selection.sum(1))
    out = {
        "window": {"periods": periods, "steps": steps, "seconds": window_s,
                   "compiles": counter.n, "period_s": period_s,
                   "slots": slots, "host": host.summary()},
        "device": {"platform": used[0].platform, "kind": used[0].device_kind,
                   "count": len(used), "memory_peak_bytes": int(mem)},
        "setup_s": window_start - T_START,
    }
    if trace:
        from bench import trace as trace_mod
        from bench.metrics import Observed, read

        red = trace_mod.reduce_trace(trace_mod.xplane_file(log_dir.name),
                                     cell.chips)
        log_dir.cleanup()
        obs = Observed(trace=red, steps=steps, flops=flops, chips=cell.chips,
                       peak=peaks(used[0].device_kind),
                       n_clients=cfg.n_clients,
                       buffer=cfg.buffer_size if cfg.mode == "async" else None,
                       config=conf, local_steps=local_steps,
                       local_batch=local_batch, eval_examples=eval_examples)
        metrics = {}
        for m in cell.per_layer:
            value = read(m["name"], obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = {"device_ops": [list(x) for x in trace_mod.top_ops(red)],
                            "idle_gaps": [list(x) for x in red.idle_gaps]}
    else:
        values = {"steps_per_s": steps / window_s, "setup_s": out["setup_s"]}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}

    # the reference runs once the program's state is freed
    del engine, task, res
    gc.collect()
    t0 = time.perf_counter()
    ref = Reference(model, conf, cell.traffic, data, run_seed).follow(
        compare_steps, cfg.resolved_steps_per_chunk())
    out["reference"] = {"seconds": time.perf_counter() - t0,
                        "process_peak_bytes": peak_bytes(used)}
    readings = oracle.compare(got, ref)
    correct, rows = oracle.judge(readings, cell.check["limits"])
    out.update(correct=correct, rows=rows, readings=readings, compared={
        "eval": (list(got["eval"]), list(ref["eval"])),
        "loss": (got["loss"].tolist(), ref["loss"].tolist())})
    return out


def peak_bytes(devices) -> int:
    """The process's peak device memory on the fullest of ``devices``."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def emit(out: dict) -> None:
    """Window, reference and check lines, then the result as the last
    stdout line; the checks are also the last lines of standard error."""
    w, ref = out["window"], out["reference"]
    sized = ("fixed by the cell" if w["period_s"] is None else
             f"one period took {w['period_s']:.4f} s in set-up")
    if w["slots"] is not None:
        sized += f"; {w['slots']} cohort slots trained"
    print(f"window: {w['periods']} eval periods, {w['steps']} steps in "
          f"{w['seconds']:.4f} s ({sized}); compiles in window: "
          f"{w['compiles']}", flush=True)
    host = w["host"]
    print("window on the host: longest call " + ", ".join(
        f"{k} {v:.4f} s" for k, v in sorted(host["longest_call_s"].items()))
        + f"; garbage collection {host['gc_s']:.4f} s in {host['gc_runs']} "
        "runs", flush=True)
    print(f"reference: {ref['seconds']:.4f} s; process peak after it "
          f"{ref['process_peak_bytes']} bytes, the window's "
          f"{out['device']['memory_peak_bytes']}", flush=True)
    for what, (prog, r) in out["compared"].items():
        print(f"compared {what} ({len(r)}, the first 8): program "
              f"{prog[:8]} reference {r[:8]}", flush=True)
    rows = out["rows"]
    for name, value in sorted(out["readings"].items()):
        if all(name != n for n, _, _ in rows):
            print(f"reading {name}: {value!r} (not compared)", flush=True)
    for name, value, limit in rows:
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    failed = sum(1 for _, v, lim in rows if not v <= lim)
    line = {"correct": out["correct"], "attempted": len(rows),
            "failed": failed, "metrics": out["metrics"],
            "device": out["device"]}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    # JSON has no infinity: a reading that is not finite prints as 1e300
    line["checks"] = {n: {"value": v if math.isfinite(v) else 1e300,
                          "limit": lim} for n, v, lim in rows}
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench.cell import load_cell

    try:
        out = run(load_cell(args.workload), args.seed, args.seconds,
                  bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

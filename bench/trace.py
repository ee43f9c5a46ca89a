"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb`` per host. In it each chip is a
plane named ``/device:TPU:<i>`` whose ``XLA Ops`` line holds one event per
operation run (named by the HLO instruction's text: ``%fusion.12 = ...``,
a Pallas kernel under its own name, ``%event_topk.1 = ...``) and whose
``XLA Modules`` line holds one event per program run (``jit_chunk(7)``,
with a ``run_id`` stat). The host plane ``/host:CPU`` holds the Python
thread's ``TraceAnnotation`` spans and the runtime's ``DoEnqueueProgram``
and ``CompleteCallbacks`` events, which carry the ``run_id`` of the
program run they enqueue or complete.

A chip's clock in the trace runs behind the host's by about a
millisecond on a v5e (a program starts on the device "before" the host
enqueued it). Each chip's events are shifted onto the host clock by the
least offset that puts every program run after its enqueue, or else
before its completion callback. Everything is then clipped to the
``bench.window`` span the harness puts around the timed ``run_engine``
call, and averaged over the chips used. The device self time under each
of the engine's scopes, and under any name on the ops' name paths, comes
from ``bench/xplane.py``, on that same clock and window.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench.window"
# the harness's host spans around the engine's hooks
HOST_SPANS = ("engine.run_chunk", "engine.evaluate", "engine.record",
              "engine.finalize")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_s: float  # length of the traced window
    busy_s: float  # union of op intervals in the window, mean over chips
    devices: int
    program_s: Dict[str, float]  # device seconds per program, mean over chips
    op_s: Dict[str, float]  # device self seconds per op, mean over chips
    collective_s: float  # device seconds in collective ops, mean over chips
    idle_gaps: List[Tuple[str, float]]  # longest gaps on the first chip
    # device self seconds per program and engine scope, mean over chips
    # (``bench.xplane.scope_times``)
    scope_s: Dict[str, Dict[str, float]]
    # device self seconds per program under each name on the ops' name
    # paths, any ``jax.named_scope`` among them (``bench.xplane.Scoped``)
    under_s: Dict[str, Dict[str, float]]


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(ops):
    """Each op's time less the ops nested inside it (a ``while`` holds its
    body's ops), as ``(name, seconds)``."""
    out, stack = [], []  # stack of [name, start, end, child time]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][2]:
            n, s0, e0, kids = stack.pop()
            out.append((n, e0 - s0 - kids))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    out += [(n, e0 - s0 - kids) for n, s0, e0, kids in stack]
    return out


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def clock_offset(modules, host_runs, chip: int) -> float:
    """Seconds to add to ``chip``'s times to put them on the host clock.
    ``host_runs`` maps ``(event name, device ordinal, run_id)`` to host
    start seconds."""
    lower, upper = [], []
    for ev in modules.events:
        st = _stats(ev)
        run = st.get("run_id")
        start = ev.start_ns * 1e-9
        end = start + ev.duration_ns * 1e-9
        enq = host_runs.get(("DoEnqueueProgram", chip, run))
        done = host_runs.get(("CompleteCallbacks", chip, run))
        if enq is not None:
            lower.append(enq - start)
        if done is not None:
            upper.append(done - end)
    if lower:
        return max(lower)
    return min(upper) if upper else 0.0


def xplane_file(log_dir: str) -> str:
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(files)}")
    return files[0]


def reduce_trace(path: str, devices: int, n_gaps: int = 10) -> Reduced:
    """Reduce the trace at ``path`` over the first ``devices`` chips."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, host_runs = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN or ev.name in HOST_SPANS:
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
                    elif ev.name in ("DoEnqueueProgram", "CompleteCallbacks"):
                        st = _stats(ev)
                        key = (ev.name, st.get("device_ordinal", 0),
                               st.get("run_id"))
                        host_runs.setdefault(key, ev.start_ns * 1e-9)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    chips = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:[A-Z]+:(\d+)", plane.name)
        if m and int(m.group(1)) < devices:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                chips.append((int(m.group(1)), lines))
    if len(chips) != devices:
        raise ValueError(f"trace holds {len(chips)} device planes with ops, "
                         f"expected {devices}")
    chips.sort(key=lambda c: c[0])
    busy, coll = 0.0, 0.0
    op_s: Dict[str, float] = defaultdict(float)
    program_s: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for i, lines in chips:
        modules = lines.get("XLA Modules")
        shift = clock_offset(modules, host_runs, i) if modules else 0.0
        ops = []
        for name, s, e in _events(lines["XLA Ops"]):
            c = _clip(s + shift, e + shift, lo, hi)
            if c:
                ops.append((op_name(name),) + c)
        for name, secs in self_times(ops):
            op_s[name] += secs / devices
            if COLLECTIVE.match(name):
                coll += secs / devices
        merged = _union([o[1:] for o in ops])
        busy += sum(e - s for s, e in merged) / devices
        for name, s, e in (_events(modules) if modules is not None else ()):
            c = _clip(s + shift, e + shift, lo, hi)
            if c:
                program_s[_SUFFIX.sub("", name)] += (c[1] - c[0]) / devices
        if i == chips[0][0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    host = [(n, s, e) for n, s, e in spans if n in HOST_SPANS]

    def doing(s, e):
        mid = 0.5 * (s + e)
        inside = [(e2 - s2, n) for n, s2, e2 in host if s2 <= mid <= e2]
        return min(inside)[1] if inside else "run_engine loop"

    from bench import xplane

    longest = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:n_gaps]
    scoped = xplane.scope_times(path, devices)
    return Reduced(
        window_s=hi - lo, busy_s=busy, devices=devices,
        program_s=dict(program_s), op_s=dict(op_s), collective_s=coll,
        idle_gaps=[(doing(s, e), e - s) for s, e in longest],
        scope_s=scoped.scope_s, under_s=scoped.under_s)


def top_ops(red: Reduced, n: int = 10) -> List[Tuple[str, float]]:
    return sorted(red.op_s.items(), key=lambda kv: kv[1], reverse=True)[:n]

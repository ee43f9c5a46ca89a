"""The JAX profiler's ``.xplane.pb`` read from its wire format, for what
``jax.profiler.ProfileData`` leaves out: the name path of each device op.

ProfileData hands each ``XLA Ops`` event its HLO text and its times, and
nothing of the ``tf_op`` stat that XLA stores on the event's metadata:
the op's name path as JAX lowered it, such as
``jit(chunk)/while/body/local_train/vmap(jvp(conv_general_dilated))``.
Every ``jax.named_scope`` the op was traced under is a component of that
path. Here the file is decoded with the standard library alone (protobuf
wire format; the message layout is ``tsl/profiler/protobuf/xplane.proto``),
and each event is joined to its metadata by the metadata id.

``scope_times`` then gives the device self seconds under each of a set of
scopes, and under any name on the ops' paths, on the same clock and window
as ``bench.trace.reduce_trace``.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, Iterator, List, Sequence, Tuple

from bench import trace

UNATTRIBUTED = "unattributed"
# the program's device scopes (repro.engine), and the run loop's host spans
ENGINE_SCOPES = ("admission", "dispatch", "pop", "local_train", "aggregate",
                 "load_metric")
RUN_ENGINE_SPANS = ("run_engine.init", "run_engine.chunk",
                    "run_engine.dispatch", "run_engine.pull",
                    "run_engine.history", "run_engine.evaluate",
                    "run_engine.record", "run_engine.finalize")


# ---------------------------------------------------------------- wire


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, bytes for a length-delimited one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} is not in xplane.proto")
        yield field, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


# ---------------------------------------------------------------- messages


@dataclasses.dataclass(frozen=True)
class Op:
    name: str  # the HLO instruction's name, as ``bench.trace.op_name``
    path: str  # its ``tf_op`` name path; "" where XLA stored none
    start_s: float  # on the chip's own clock, as ProfileData gives it
    end_s: float


def _str_stat(buf: bytes, names: Dict[int, str]) -> Tuple[int, str]:
    """``(stat metadata id, string value)``, "" for a number; a
    ``ref_value`` is resolved to the name of the stat metadata it points
    at (how XLA interns strings)."""
    mid, value = 0, ""
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 5:
            value = v.decode("utf-8", "replace")
        elif f == 7:
            value = names.get(v, "")
    return mid, value


def _plane(buf: bytes):
    """(name, lines, event metadata {id: (name, tf_op)})."""
    name, lines, meta_raw, stat_names = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = v.decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            meta_raw.append(v)
        elif f == 5:  # map<int64, XStatMetadata>
            for ef, ev in _fields(v):
                if ef == 2:
                    sid, sname = 0, ""
                    for sf, sv in _fields(ev):
                        if sf == 1:
                            sid = sv
                        elif sf == 2:
                            sname = sv.decode()
                    stat_names[sid] = sname
    tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
    meta: Dict[int, Tuple[str, str]] = {}
    for entry in meta_raw:  # map<int64, XEventMetadata>
        for ef, ev in _fields(entry):
            if ef != 2:
                continue
            mid, mname, path = 0, "", ""
            for mf, mv in _fields(ev):
                if mf == 1:
                    mid = mv
                elif mf == 2:
                    mname = mv.decode("utf-8", "replace")
                elif mf == 5:
                    sid, value = _str_stat(mv, stat_names)
                    if sid in tf_op:
                        # "<name path>:<op type>", the type empty for XLA
                        path = re.sub(r":[^/]*$", "", value)
            meta[mid] = (mname, path)
    return name, lines, meta


def _line(buf: bytes):
    """(name, timestamp_ns, [(metadata id, offset_ps, duration_ps)])."""
    name, ts, events = "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = v.decode()
        elif f == 3:
            ts = _signed(v)
        elif f == 4:
            mid = off = dur = 0
            for ef, ev in _fields(v):
                if ef == 1:
                    mid = ev
                elif ef == 2:
                    off = _signed(ev)
                elif ef == 3:
                    dur = _signed(ev)
            events.append((mid, off, dur))
    return name, ts, events


def device_ops(path: str) -> Dict[int, List[Op]]:
    """Each device plane's ops (``/device:<kind>:<i>`` -> ``i``), with the
    name path XLA stored on each op's metadata."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[int, List[Op]] = {}
    for field, buf in _fields(space):
        if field != 1:
            continue
        name, lines, meta = _plane(buf)
        m = re.fullmatch(r"/device:[A-Z]+:(\d+)", name)
        if not m:
            continue
        ops = out.setdefault(int(m.group(1)), [])
        for raw in lines:
            lname, ts, events = _line(raw)
            if lname != "XLA Ops":
                continue
            for mid, off, dur in events:
                text, tf_op = meta.get(mid, ("", ""))
                s = ts * 1e-9 + off * 1e-12
                ops.append(Op(trace.op_name(text), tf_op, s, s + dur * 1e-12))
    return out


# ---------------------------------------------------------------- scopes


def scope_of(path: str, scopes: Sequence[str]) -> str:
    """The scope of ``scopes`` that a component of ``path`` names, alone or
    inside transformation wrappers (``transpose(jvp(local_train))``);
    the innermost where several do; ``UNATTRIBUTED`` where none does."""
    found = UNATTRIBUTED
    for part in path.split("/"):
        core = re.sub(r"\)+$", "", part)
        core = core.rsplit("(", 1)[-1]
        if core in scopes and re.fullmatch(
                r"(?:[\w.-]+\()*" + re.escape(core) + r"\)*", part):
            found = core
    return found


def components(path: str) -> frozenset:
    """The names a name path is made of, each taken out of its
    transformation wrappers (``vmap(transpose(jvp(moe)))`` -> ``moe``): the
    ``jax.named_scope``s the op ran under, among the program's, loops' and
    primitives' own names."""
    out = set()
    for part in path.split("/"):
        m = re.fullmatch(r"(?:[\w.-]+\()*([^()]*?)\)*", part)
        out.add(m.group(1) if m else part)
    out.discard("")
    return frozenset(out)


@dataclasses.dataclass
class Scoped:
    # device self seconds per program (``jit_chunk``) and scope, mean over
    # chips; an op belongs to the program run whose interval holds its start
    scope_s: Dict[str, Dict[str, float]]
    op_s: Dict[Tuple[str, str, str], float]  # (program, scope, op) -> seconds
    paths: Dict[str, str]  # op -> its path (the first one seen)
    program_s: Dict[str, float]  # device seconds per program, mean over chips
    idle_gaps: List[Tuple[str, float]]  # gaps named by their host span
    # device self seconds per program under each component of the ops'
    # name paths (``components``), mean over chips: an op counts once
    # under each, so a model's own scope nested inside an engine scope
    # reads its time without taking it from ``scope_s``
    under_s: Dict[str, Dict[str, float]]


def _host(path: str, span_names: Iterable[str]):
    """The window, the named host spans and the enqueue/complete times of
    program runs, read as ``bench.trace.reduce_trace`` reads them."""
    from jax.profiler import ProfileData

    span_names = set(span_names)
    pd = ProfileData.from_file(path)
    windows, spans, host_runs, modules = [], [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if ev.name == trace.WINDOW_SPAN:
                        windows.append((s, e))
                    elif ev.name in span_names:
                        spans.append((ev.name, s, e))
                    elif ev.name in ("DoEnqueueProgram", "CompleteCallbacks"):
                        st = dict(ev.stats)
                        host_runs.setdefault(
                            (ev.name, st.get("device_ordinal", 0),
                             st.get("run_id")), s)
        m = re.fullmatch(r"/device:[A-Z]+:(\d+)", plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[int(m.group(1))] = line
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    return windows[0], spans, host_runs, modules


def scope_times(path: str, devices: int, n_gaps: int = 10) -> Scoped:
    """Device self seconds under each of ``ENGINE_SCOPES`` (and
    ``UNATTRIBUTED``), and under every component of the ops' name paths,
    per program, in the ``bench.window`` span of the
    trace at ``path``, over the first ``devices`` chips; the ``n_gaps``
    longest idle gaps on the first chip, each named by the one of
    ``RUN_ENGINE_SPANS`` that is the innermost for the longest part of it
    ("no span" where none is)."""
    (lo, hi), host, host_runs, modules = _host(path, RUN_ENGINE_SPANS)
    per_chip = device_ops(path)
    chips = sorted(i for i in per_chip if i < devices)
    if len(chips) != devices:
        raise ValueError(f"trace holds {len(chips)} device planes with ops, "
                         f"expected {devices}")
    scope_s: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    under_s: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    op_s: Dict[Tuple[str, str, str], float] = defaultdict(float)
    program_s: Dict[str, float] = defaultdict(float)
    paths: Dict[str, str] = {}
    names: Dict[str, frozenset] = {}  # path -> its components
    gaps: List[Tuple[float, float]] = []
    for i in chips:
        mod = modules.get(i)
        shift = trace.clock_offset(mod, host_runs, i) if mod else 0.0
        runs = sorted((s + shift, e + shift, trace._SUFFIX.sub("", n))
                      for n, s, e in (trace._events(mod) if mod else ()))
        starts = [r[0] for r in runs]
        for s, e, name in runs:
            c = trace._clip(s, e, lo, hi)
            if c:
                program_s[name] += (c[1] - c[0]) / devices
        ops = []
        for k, op in enumerate(per_chip[i]):
            c = trace._clip(op.start_s + shift, op.end_s + shift, lo, hi)
            if c:
                ops.append((k,) + c)
                paths.setdefault(op.name, op.path)
        for k, secs in trace.self_times(ops):
            op = per_chip[i][k]
            j = bisect.bisect_right(starts, op.start_s + shift) - 1
            program = (runs[j][2] if j >= 0 and op.start_s + shift <= runs[j][1]
                       else "no program")
            scope = scope_of(op.path, ENGINE_SCOPES)
            scope_s[program][scope] += secs / devices
            op_s[(program, scope, op.name)] += secs / devices
            if op.path not in names:
                names[op.path] = components(op.path)
            for name in names[op.path]:
                under_s[program][name] += secs / devices
        if i == chips[0]:
            merged = trace._union([o[1:] for o in ops])
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]

    def doing(s, e):
        """The span that is innermost for the longest part of the gap."""
        cuts = sorted({s, e} | {t for _, s2, e2 in host for t in (s2, e2)
                                if s < t < e})
        held: Dict[str, float] = defaultdict(float)
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            inside = [(e2 - s2, n) for n, s2, e2 in host if s2 <= mid <= e2]
            held[min(inside)[1] if inside else "no span"] += b - a
        return max(held, key=held.get)

    longest = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:n_gaps]
    return Scoped(scope_s={p: dict(v) for p, v in scope_s.items()},
                  op_s=dict(op_s), paths=paths, program_s=dict(program_s),
                  idle_gaps=[(doing(s, e), e - s) for s, e in longest],
                  under_s={p: dict(v) for p, v in under_s.items()})

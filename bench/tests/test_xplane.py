"""The name paths of device ops, read from the profiler's file itself
(``bench/xplane.py``), and the device time under each scope.

``data/scoped.xplane.pb`` was recorded on one TPU v5e chip by
``record_scoped_trace.py``: three calls of a ``chunk`` program whose work
is under the scopes ``pop`` (the ``event_topk`` kernel) and
``local_train`` (a ``jax.grad`` under ``vmap``, the scope inside the
differentiated function), inside the run loop's host spans.
``data/small.xplane.pb`` is ``test_trace.py``'s trace, with no scope."""
import dataclasses
import re
from pathlib import Path

import pytest

from bench import trace, xplane

DATA = Path(__file__).with_name("data")
SMALL = DATA / "small.xplane.pb"
SCOPED = DATA / "scoped.xplane.pb"
SCOPES = ("pop", "local_train")


@pytest.fixture(scope="module")
def scoped():
    return xplane.scope_times(str(SCOPED), devices=1)


@pytest.mark.parametrize("path,scope", [
    ("jit(chunk)/while/body/closed_call/pop/jit(tile_next_k)/event_topk/"
     "pallas_call", "pop"),
    ("jit(chunk)/local_train/vmap(jvp())/dot_general", "local_train"),
    ("jit(chunk)/vmap(transpose(jvp(local_train)))/mul", "local_train"),
    ("jit(chunk)/jvp(local_train)/tanh", "local_train"),
    ("jit(chunk)/popcount/add", xplane.UNATTRIBUTED),
    ("jit(chunk)/local_training/add", xplane.UNATTRIBUTED),
    ("jit(chunk)/while/body/gather", xplane.UNATTRIBUTED),
    ("", xplane.UNATTRIBUTED),
])
def test_a_scope_is_a_component_alone_or_inside_wrappers(path, scope):
    assert xplane.scope_of(path, SCOPES) == scope


def test_small_trace_ops_are_profile_datas_with_their_paths():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(SMALL))
    chip = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(x for x in chip.lines if x.name == "XLA Ops")
    theirs = [(trace.op_name(ev.name), ev.start_ns * 1e-9)
              for ev in line.events]
    ops = xplane.device_ops(str(SMALL))[0]
    assert [o.name for o in ops] == [n for n, _ in theirs]
    assert all(abs(o.start_s - s) < 1e-9 for o, (_, s) in zip(ops, theirs))
    paths = {o.name: o.path for o in ops}
    assert paths["event_topk.1"] == (
        "jit(chunk)/jit(tile_next_k)/event_topk/pallas_call")


def test_small_trace_reduction_is_unchanged():
    red = trace.reduce_trace(str(SMALL), devices=1)
    got = xplane.scope_times(str(SMALL), devices=1)
    # no op of that trace carries a scope
    assert set().union(*got.scope_s.values()) == {xplane.UNATTRIBUTED}
    # ProfileData rounds each time down to a whole nanosecond
    ns = 1e-9 * len(xplane.device_ops(str(SMALL))[0])
    total = sum(sum(v.values()) for v in got.scope_s.values())
    assert total == pytest.approx(sum(red.op_s.values()), abs=ns)
    assert got.program_s == pytest.approx(red.program_s, abs=ns)


def test_every_scoped_op_resolves_to_its_scope(scoped):
    ops = xplane.device_ops(str(SCOPED))[0]
    kernel = [o for o in ops if "event_topk" in o.path]
    backward = [o for o in ops if "transpose(jvp(local_train))" in o.path]
    assert kernel and backward
    assert {xplane.scope_of(o.path, SCOPES) for o in kernel} == {"pop"}
    assert {xplane.scope_of(o.path, SCOPES) for o in backward} == {
        "local_train"}
    for o in ops:
        if o.path.startswith("jit(chunk)/") and any(
                s in o.path for s in SCOPES):
            assert xplane.scope_of(o.path, SCOPES) in SCOPES, o.path


def test_scope_times_add_up_within_the_program(scoped):
    chunk = scoped.scope_s["jit_chunk"]
    assert chunk["pop"] > 0 and chunk["local_train"] > 0
    assert sum(chunk.values()) <= scoped.program_s["jit_chunk"] * 1.0001
    # the scopes hold the chunk's work: what is left is XLA's own copies
    assert chunk.get(xplane.UNATTRIBUTED, 0.0) < 0.1 * sum(chunk.values())


def test_idle_gaps_are_named_by_the_run_loop_spans(scoped):
    names = [n for n, _ in scoped.idle_gaps]
    secs = [s for _, s in scoped.idle_gaps]
    assert secs == sorted(secs, reverse=True)
    assert names[:3] == ["run_engine.record"] * 3
    assert all(s >= 0.002 for s in secs[:3])


# ``scope_s`` of ``scoped.xplane.pb`` to the bit: the engine scopes' readers
# (``sched_ms``, ``train_ms``, ...) read the same whatever else is kept
PINNED = {"jit_chunk": {"unattributed": 5.2578000002412306e-08,
                        "pop": 2.4760467999998537e-05,
                        "local_train": 2.8147421999996647e-05}}


def test_the_engine_scopes_read_as_before_to_the_bit(scoped):
    assert scoped.scope_s == PINNED


def test_the_time_under_a_component_holds_its_engine_scope(scoped):
    under, chunk = scoped.under_s["jit_chunk"], scoped.scope_s["jit_chunk"]
    assert set(chunk) - {xplane.UNATTRIBUTED} <= set(xplane.ENGINE_SCOPES)
    for scope in set(chunk) - {xplane.UNATTRIBUTED}:
        assert under[scope] >= chunk[scope] > 0
    # every op that carries a path is under the program's own name, once
    scoped_ops = sum(v for k, v in chunk.items() if k != xplane.UNATTRIBUTED)
    assert scoped_ops <= under["chunk"] <= sum(chunk.values())


@pytest.mark.parametrize("path,names", [
    ("jit(chunk)/vmap(transpose(jvp(local_train)))/moe/mul",
     {"chunk", "local_train", "moe", "mul"}),
    ("jit(chunk)/while/body/local_train/while/body/dot_general",
     {"chunk", "while", "body", "local_train", "dot_general"}),
    ("jit(chunk)/local_train/vmap(jvp())/dot_general",
     {"chunk", "local_train", "dot_general"}),
    ("", set()),
])
def test_a_component_is_a_name_out_of_its_wrappers(path, names):
    assert xplane.components(path) == names


def with_moe(monkeypatch):
    """The scoped trace's ops with a model's ``moe`` scope put inside
    every ``local_train``: ``.../vmap(transpose(jvp(local_train)))/moe/
    dot_general``."""
    ops = xplane.device_ops(str(SCOPED))
    nested = {i: [dataclasses.replace(o, path=re.sub(
        r"(local_train\)*)/", r"\1/moe/", o.path, count=1)) for o in chip]
        for i, chip in ops.items()}
    monkeypatch.setattr(xplane, "device_ops", lambda path: nested)
    return nested


def test_a_model_scope_in_an_engine_scope_counts_under_both(scoped,
                                                            monkeypatch):
    nested = with_moe(monkeypatch)
    assert any(o.path.endswith("(local_train)))/moe/dot_general")
               for o in nested[0])
    got = xplane.scope_times(str(SCOPED), devices=1)
    # the engine scopes keep every op: moe is none of them
    assert got.scope_s == scoped.scope_s
    under = got.under_s["jit_chunk"]
    assert under["moe"] == under["local_train"] == (
        scoped.scope_s["jit_chunk"]["local_train"])
    assert "moe" not in scoped.under_s["jit_chunk"]

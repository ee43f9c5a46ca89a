"""The trace reduction on a small trace recorded on one TPU v5e chip
(``data/small.xplane.pb``, made by ``record_trace.py``): three calls of a
``chunk`` program holding the ``event_topk`` kernel, each followed by a
2 ms host sleep and an ``eval_scan`` program, inside ``bench.window``."""
from pathlib import Path

import pytest

from bench import trace

SMALL = Path(__file__).with_name("data") / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce_trace(str(SMALL), devices=1)


def test_window_and_busy_time(red):
    assert 0.006 < red.window_s < 1.0  # three 2 ms sleeps at least
    assert 0 < red.busy_s < red.window_s
    assert red.devices == 1


def test_programs_are_found_by_their_jit_names(red):
    assert set(red.program_s) >= {"jit_chunk", "jit_eval_scan"}
    assert all(s > 0 for s in red.program_s.values())
    # every op runs inside a program; a program also holds short gaps
    assert red.busy_s <= sum(red.program_s.values()) * 1.0001
    assert sum(red.program_s.values()) < red.window_s


def test_the_kernel_is_an_op_of_its_own(red):
    pops = [name for name in red.op_s if "event_topk" in name]
    assert pops and sum(red.op_s[n] for n in pops) > 0


def test_idle_gaps_are_named_by_the_host_span(red):
    names = [name for name, _ in red.idle_gaps]
    secs = [s for _, s in red.idle_gaps]
    assert secs == sorted(secs, reverse=True)
    # the three longest gaps are the 2 ms sleeps under engine.evaluate:
    # named right only once the chip's clock is shifted onto the host's
    assert names[:3] == ["engine.evaluate"] * 3
    assert all(s >= 0.002 for s in secs[:3])
    assert red.collective_s == 0.0


def test_chip_clock_is_shifted_onto_the_host_clock():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(SMALL))
    chip = next(p for p in pd.planes if p.name == "/device:TPU:0")
    modules = next(line for line in chip.lines if line.name == "XLA Modules")
    # this trace's chip ran 1.40-1.45 ms behind the host
    runs = {("DoEnqueueProgram", 0, 9): 0.052304880,
            ("CompleteCallbacks", 0, 9): 0.052861540}
    assert 0.00140 < trace.clock_offset(modules, runs, 0) < 0.00145
    assert trace.clock_offset(modules, {}, 0) == 0.0


def test_self_time_leaves_out_nested_ops():
    ops = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 3.0),
           ("fusion.2", 4.0, 5.0), ("copy.1", 11.0, 12.0)]
    assert dict(trace.self_times(ops)) == {
        "while.1": 7.0, "fusion.1": 2.0, "fusion.2": 1.0, "copy.1": 1.0}


def test_union_merges_overlapping_intervals():
    assert trace._union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [[0, 3], [5, 7]]


@pytest.mark.parametrize("name,hit", [
    ("all-gather.3", True), ("all-reduce-start.1", True),
    ("collective-permute-done", True), ("fusion.12", False),
    ("event_topk", False)])
def test_collective_names(name, hit):
    assert bool(trace.COLLECTIVE.match(name)) == hit

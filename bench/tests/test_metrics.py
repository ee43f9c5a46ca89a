"""The per-layer readers of the engine's scopes (``bench/metrics/``), on the
chip-recorded trace ``data/scoped.xplane.pb`` (see ``test_xplane.py``):
three calls of a ``chunk`` program with work under ``pop`` and
``local_train`` and none under ``aggregate``."""
from pathlib import Path

import pytest

from bench import trace, xplane
from bench.metrics import Observed, read

DATA = Path(__file__).with_name("data")
STEPS = 3


def _observed(path):
    return Observed(trace=trace.reduce_trace(str(path), devices=1),
                    steps=STEPS, flops=0.0, chips=1, peak={}, n_clients=1,
                    buffer=None)


@pytest.fixture(scope="module")
def scoped():
    return _observed(DATA / "scoped.xplane.pb")


def test_the_reduction_carries_the_scope_times(scoped):
    theirs = xplane.scope_times(str(DATA / "scoped.xplane.pb"), devices=1)
    assert scoped.trace.scope_s == theirs.scope_s


@pytest.mark.parametrize("name,scopes", [
    ("pop_ms", ("pop",)),
    ("sched_ms", ("pop",)),  # of the scheduler's scopes only pop ran
    ("train_ms", ("local_train",)),
])
def test_a_reader_reads_its_scopes_a_step(scoped, name, scopes):
    chunk = scoped.trace.scope_s["jit_chunk"]
    want = 1e3 * sum(chunk[s] for s in scopes) / STEPS
    got = read(name, scoped)
    assert got == pytest.approx(want, rel=1e-12) and got > 0


@pytest.mark.parametrize("path,name", [
    ("scoped.xplane.pb", "agg_ms"),  # no op ran under aggregate
    ("small.xplane.pb", "agg_ms"),  # no op of that trace has a scope
    ("small.xplane.pb", "pop_ms"),
    ("small.xplane.pb", "sched_ms"),
    ("small.xplane.pb", "train_ms"),
])
def test_a_reader_with_nothing_to_read_returns_nothing(scoped, path, name):
    obs = scoped if path == "scoped.xplane.pb" else _observed(DATA / path)
    assert read(name, obs) is None

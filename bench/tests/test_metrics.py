"""The per-layer readers of the engine's scopes (``bench/metrics/``), on the
chip-recorded trace ``data/scoped.xplane.pb`` (see ``test_xplane.py``):
three calls of a ``chunk`` program with work under ``pop`` and
``local_train`` and none under ``aggregate``; and the seams a model's own
readers take, the time under its scopes and a kernel's roofline share."""
from pathlib import Path

import pytest

import bench.metrics
from bench import trace, xplane
from bench.metrics import Observed, read, roofline_share

DATA = Path(__file__).with_name("data")
STEPS = 3


def _observed(path):
    return Observed(trace=trace.reduce_trace(str(path), devices=1),
                    steps=STEPS, flops=0.0, chips=1, peak={}, n_clients=1,
                    buffer=None, config={}, local_steps=0.0, local_batch=1,
                    eval_examples=0)


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


def _peaked(chips=1, config=None, local_steps=0.0, local_batch=1,
            trace_=None, steps=STEPS):
    return Observed(trace=trace_, steps=steps, flops=0.0, chips=chips,
                    peak={"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0},
                    n_clients=1, buffer=None, config=config or {},
                    local_steps=local_steps, local_batch=local_batch,
                    eval_examples=0)


@pytest.mark.parametrize("chips,flops,bytes_,seconds,want", [
    (1, 1000.0, 50.0, 20.0, (50.0, "compute")),  # 10 s of compute, 5 of HBM
    (1, 100.0, 500.0, 100.0, (50.0, "memory")),  # 1 s of compute, 50 of HBM
    (2, 1000.0, 50.0, 20.0, (25.0, "compute")),  # two chips share the work
    (1, 0.0, 500.0, 200.0, (25.0, "memory")),
])
def test_roofline_share_is_the_least_time_over_the_time_taken(
        chips, flops, bytes_, seconds, want):
    share, bound = roofline_share(_peaked(chips), seconds, flops, bytes_)
    assert (share, bound) == (pytest.approx(want[0], rel=1e-12), want[1])


@pytest.mark.parametrize("seconds,flops,bytes_", [
    (None, 1000.0, 50.0),  # the kernel did not run
    (0.0, 1000.0, 50.0),
    (20.0, 0.0, 0.0),  # it needs no work
])
def test_roofline_share_of_nothing_is_nothing(seconds, flops, bytes_):
    assert roofline_share(_peaked(), seconds, flops, bytes_) is None


@pytest.fixture
def readers(monkeypatch):
    """Per-layer readers from ``readers/``, kept under the tests and in no
    cell: a new metric is one file there, read through ``bench.metrics``
    unedited."""
    monkeypatch.setattr(bench.metrics, "METRICS_DIR",
                        Path(__file__).with_name("readers"))


def test_a_new_file_reads_the_time_under_a_model_scope(readers, monkeypatch):
    from test_xplane import with_moe

    with_moe(monkeypatch)
    obs = _observed(DATA / "scoped.xplane.pb")
    want = 1e3 * obs.trace.scope_s["jit_chunk"]["local_train"] / STEPS
    assert read("moe_ms", obs) == pytest.approx(want, rel=1e-12)
    assert read("moe_ms", _observed(DATA / "small.xplane.pb")) is None


def test_a_new_file_reads_a_kernels_roofline_share(readers, monkeypatch):
    from test_xplane import with_moe

    with_moe(monkeypatch)
    red = trace.reduce_trace(str(DATA / "scoped.xplane.pb"), devices=1)
    obs = _peaked(config={"widths": {"d": 2048, "f": 1408}},
                  local_steps=120.0, local_batch=50, trace_=red)
    seconds = red.under_s["jit_chunk"]["moe"]
    flops = 3 * 2 * 120 * 50 * 2048 * 1408
    bytes_ = 120 * 3 * 4 * 2048 * 1408
    want = 100.0 * max(flops / 100.0, bytes_ / 10.0) / seconds
    assert read("moe_roofline", obs) == pytest.approx(want, rel=1e-12)
    # with no moe scope in the trace the reader reads nothing
    plain = trace.reduce_trace(str(DATA / "small.xplane.pb"), devices=1)
    assert read("moe_roofline", _peaked(config=obs.config, local_steps=120.0,
                                        local_batch=50, trace_=plain)) is None

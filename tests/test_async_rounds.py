"""Buffered asynchronous training loop: staleness weighting and the
degenerate reduction onto the synchronous FedAvg round."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import MNIST_CNN
from repro.data.synthetic import make_image_dataset
from repro.engine import RunConfig, make_engine, run_engine
from repro.engine.aggregators import staleness_weight
from repro.fl import make_cnn_task
from repro.sim import get_profile

SMALL_CNN = dataclasses.replace(
    MNIST_CNN, name="paper-cnn-mnist-small", image_size=16,
    conv_channels=(8, 16), fc_width=64,
)


@pytest.fixture(scope="module")
def small_task():
    train, test = make_image_dataset(
        "mnist-small", 10, 16, 1, 600, 500, seed=0, difficulty=0.8
    )
    return make_cnn_task(SMALL_CNN, train, test, n_clients=20)


def _cfg(policy, rounds=6, **kw):
    base = dict(
        n_clients=20, k=4, m=6, policy=policy, rounds=rounds,
        local_epochs=2, batch_size=10, eval_every=2,
    )
    base.update(kw)
    return RunConfig(**base)


def _async(task, cfg, **kw):
    return run_engine(make_engine(task, dataclasses.replace(
        cfg, mode="async", **kw)))


# ---------------------------------------------------------------------------
# staleness weights
# ---------------------------------------------------------------------------


def test_staleness_weight_polynomial():
    s = jnp.array([0, 1, 3, 8])
    w = staleness_weight(s, "poly", 0.5)
    np.testing.assert_allclose(
        np.asarray(w), (1.0 + np.array([0, 1, 3, 8])) ** -0.5, rtol=1e-6
    )
    # fresh updates always carry full weight; staler never weighs more
    assert float(w[0]) == 1.0
    assert (np.diff(np.asarray(w)) <= 0).all()


def test_staleness_weight_const_and_errors():
    s = jnp.array([0, 5, 2])
    np.testing.assert_allclose(np.asarray(staleness_weight(s, "const")), 1.0)
    with pytest.raises(ValueError):
        staleness_weight(s, "geometric")


def test_staleness_weights_sum_in_aggregation(small_task):
    """Under a heterogeneous profile the realized weights are normalized:
    each aggregation advances exactly one version and the loop reports one
    successful update per buffered completion (no double counting)."""
    cfg = _cfg("markov", rounds=10)
    out = _async(small_task, cfg, buffer_size=4, profile="lognormal")
    ws = out.wall_stats
    assert 0 < ws["aggregations"] <= cfg.rounds
    assert ws["updates_applied"] <= cfg.rounds * 4
    assert out.history()["version"][-1] == ws["aggregations"]
    assert ws["mean_staleness"] >= 0.0
    assert ws["max_staleness"] >= ws["mean_staleness"]
    # params actually moved
    assert out.history()["train_loss"][-1] > 0


def test_dropouts_reduce_applied_updates(small_task):
    cfg = _cfg("markov", rounds=10)
    drop = _async(
        small_task, cfg, buffer_size=4,
        profile=dataclasses.replace(get_profile("lognormal"), dropout=0.6),
    )
    clean = _async(small_task, cfg, buffer_size=4, profile="lognormal")
    assert drop.wall_stats["updates_applied"] < clean.wall_stats["updates_applied"]


def test_all_idle_fleet_does_not_freeze_clock(small_task):
    """With long availability gaps and a buffer that drains the whole
    fleet, one step leaves everyone idle inside their off-window; the
    clock must jump to the next window opening instead of deadlocking."""
    cfg = _cfg("random", rounds=8, k=20)
    prof = dataclasses.replace(get_profile("uniform"), avail_gap=50.0)
    out = _async(small_task, cfg, buffer_size=cfg.n_clients,
                 aggregator_kwargs={"staleness_mode": "const"}, profile=prof)
    ws = out.wall_stats
    # without the clock jump the run freezes after the first aggregation
    # at sim_time == 1.0 (the one unit-latency cohort)
    assert ws["aggregations"] >= 2
    assert ws["sim_time"] > 1.5


# ---------------------------------------------------------------------------
# degenerate reduction: zero latency spread + buffer k == sync FedAvg
# ---------------------------------------------------------------------------


def test_degenerate_profile_matches_sync_fedavg(small_task):
    cfg = _cfg("random", rounds=6)
    sync = run_engine(make_engine(small_task, cfg))
    asy = _async(small_task, cfg, buffer_size=cfg.k,
                 aggregator_kwargs={"staleness_mode": "const"},
                 profile="uniform")
    # identical realized cohorts round for round
    np.testing.assert_array_equal(sync.selection, np.asarray(asy.selection))
    # per-update losses and eval trajectory match within float tolerance
    hs, ha = sync.history(), asy.history()
    np.testing.assert_allclose(hs["train_loss"], ha["train_loss"], rtol=1e-4)
    np.testing.assert_allclose(hs["eval_loss"], ha["eval_loss"], rtol=1e-4)
    np.testing.assert_allclose(hs["accuracy"], ha["accuracy"], atol=1e-3)
    ws = asy.wall_stats
    assert ws["mean_staleness"] == 0.0 and ws["max_staleness"] == 0
    assert ws["aggregations"] == cfg.rounds
    # one unit-latency cohort per step: simulated clock counts the steps
    assert ws["sim_time"] == pytest.approx(cfg.rounds)


def test_degenerate_markov_policy_also_reduces(small_task):
    """Same reduction with the paper's Markov policy (variable cohorts):
    buffer >= max cohort drains every completion each step, so version
    lags never appear."""
    cfg = _cfg("markov", rounds=8)
    asy = _async(small_task, cfg, buffer_size=cfg.n_clients,
                 aggregator_kwargs={"staleness_mode": "const"},
                 profile="uniform")
    ws = asy.wall_stats
    assert ws["max_staleness"] == 0
    # empirical epoch-indexed X sees the same chain the sync loop would
    assert ws["mean_X_epoch"] == pytest.approx(cfg.n_clients / cfg.k, rel=0.5)

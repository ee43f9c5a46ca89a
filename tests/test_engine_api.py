"""Engine API surface: registries, pluggable aggregators, RunConfig
validation, and the shared JSON-safe serializer.

The headline property (acceptance criterion of the redesign): a new
policy and a new aggregator can each be added via the registry and driven
through either engine without editing any round loop.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import MNIST_CNN
from repro.core.selection import Policy, make_policy
from repro.data.synthetic import make_image_dataset
from repro.engine import (
    AsyncEngine,
    RunConfig,
    SyncEngine,
    aggregator_names,
    make_aggregator,
    make_engine,
    policy_names,
    register_aggregator,
    register_policy,
    rounds_to_target,
    run_engine,
    to_jsonable,
)
from repro.engine.aggregators import Aggregator
from repro.fl import make_cnn_task

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


def _cfg(**kw):
    base = dict(
        n_clients=20, k=4, m=6, policy="markov", rounds=3,
        local_epochs=1, batch_size=10, eval_every=3,
    )
    base.update(kw)
    return RunConfig(**base)


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------


def test_all_paper_policies_registered_and_constructible():
    expected = {"random", "markov", "markov_probs", "markov_hetero",
                "oldest_age", "round_robin", "gumbel_age"}
    assert expected <= set(policy_names())
    for name in expected:
        pol = make_policy(name, 20, 4, 6)
        state = pol.init(jax.random.PRNGKey(0), 20)
        sel, state2 = jax.jit(pol.step)(state, jax.random.PRNGKey(1))
        assert sel.shape == (20,) and sel.dtype == jnp.bool_

    with pytest.raises(ValueError, match="unknown policy"):
        make_policy("nope", 20, 4, 6)


def test_markov_probs_accepts_custom_probs():
    probs = np.array([0.0, 0.1, 0.2, 0.3, 0.5, 0.7, 1.0], dtype=np.float32)
    pol = make_policy("markov_probs", 30, 5, 6, probs=probs, steady_start=False)
    assert pol.name == "markov" and not pol.exact_k


def test_markov_hetero_rate_spread():
    pol = make_policy("markov_hetero", 30, 6, 8, rate_spread=1.0)
    state = pol.init(jax.random.PRNGKey(0), 30)
    sel, _ = pol.step(state, jax.random.PRNGKey(1))
    assert sel.shape == (30,)


def test_aggregator_registry():
    assert {"fedavg", "fedbuff", "fedprox"} <= set(aggregator_names())
    with pytest.raises(ValueError, match="unknown aggregator"):
        make_aggregator("geometric_median")
    with pytest.raises(ValueError):
        make_aggregator("fedprox", prox_mu=-1.0)


def test_duplicate_registration_rejected():
    @register_policy("dup_policy_test")
    def _f(n, k, m=10):
        return make_policy("random", n, k, m)

    with pytest.raises(ValueError, match="already registered"):
        register_policy("dup_policy_test")(_f)


# ---------------------------------------------------------------------------
# New policy + new aggregator via the registry, no round-loop edits
# ---------------------------------------------------------------------------


@register_policy("first_k_test")
def _make_first_k(n, k, m=10):
    """Degenerate deterministic policy: always clients 0..k-1."""

    def init(key, n_=n):
        return {"ages": jnp.zeros((n_,), jnp.int32),
                "round": jnp.zeros((), jnp.int32)}

    def step(state, key):
        sel = jnp.arange(n) < k
        return sel, {**state, "round": state["round"] + 1}

    return Policy("first_k_test", init, step, exact_k=True)


def test_registered_policy_drives_sync_engine(small_task):
    res = run_engine(SyncEngine(small_task, _cfg(policy="first_k_test")))
    # every round selected exactly clients 0..k-1
    assert res.selection.shape == (3, 20)
    assert (res.selection[:, :4]).all() and not (res.selection[:, 4:]).any()
    assert np.isfinite([r.train_loss for r in res.records]).all()


def test_registered_policy_drives_async_engine(small_task):
    cfg = _cfg(policy="first_k_test", mode="async", buffer_size=4,
               profile="uniform")
    res = run_engine(AsyncEngine(small_task, cfg))
    assert res.wall_stats["aggregations"] > 0


@register_aggregator("signmean_test")
def _make_signmean():
    """Toy robust aggregator: sign of the weighted mean delta, tiny lr."""
    fedbuff = make_aggregator("fedbuff", staleness_mode="const")

    def finalize(g, acc):
        has = acc["wsum"] > 0
        denom = jnp.maximum(acc["wsum"], 1e-9)

        def fin(gl, s):
            return jnp.where(has, gl + 1e-3 * jnp.sign(s / denom).astype(gl.dtype), gl)

        return jax.tree.map(fin, g, acc["dsum"])

    return Aggregator("signmean_test", fedbuff.weigh, fedbuff.init,
                      fedbuff.accumulate, finalize)


def test_registered_aggregator_drives_both_engines(small_task):
    for mode in ("sync", "async"):
        cfg = _cfg(mode=mode, aggregator="signmean_test",
                   profile="uniform", buffer_size=4)
        res = run_engine(make_engine(small_task, cfg))
        assert len(res.records) == 1
        assert np.isfinite(res.records[-1].eval_loss)


def test_fedprox_zero_mu_equals_fedbuff(small_task):
    kw = dict(mode="async", rounds=4, profile="lognormal", buffer_size=4)
    buff = run_engine(AsyncEngine(small_task, _cfg(aggregator="fedbuff", **kw)))
    prox = run_engine(AsyncEngine(
        small_task, _cfg(aggregator="fedprox",
                         aggregator_kwargs={"prox_mu": 0.0}, **kw)
    ))
    for a, b in zip(jax.tree.leaves(buff.params), jax.tree.leaves(prox.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fedprox_damps_updates(small_task):
    kw = dict(mode="async", rounds=3, profile="uniform", buffer_size=4,
              eval_every=1)
    buff = run_engine(AsyncEngine(small_task, _cfg(aggregator="fedbuff", **kw)))
    prox = run_engine(AsyncEngine(
        small_task, _cfg(aggregator="fedprox",
                         aggregator_kwargs={"prox_mu": 4.0}, **kw)
    ))
    init = SyncEngine(small_task, _cfg()).init()["params"]

    def dist(p):
        return sum(
            float(jnp.sum((a - b).astype(jnp.float32) ** 2))
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(init))
        )

    # heavy proximal damping keeps the global model closer to its start
    assert dist(prox.params) < dist(buff.params)


# ---------------------------------------------------------------------------
# RunConfig validation
# ---------------------------------------------------------------------------


def test_run_config_validates_mode_and_k():
    with pytest.raises(ValueError, match="mode"):
        RunConfig(mode="semi_sync")
    with pytest.raises(ValueError, match="k="):
        RunConfig(n_clients=10, k=11)


def test_max_cohort_below_k_rejected():
    with pytest.raises(ValueError, match="max_cohort"):
        RunConfig(n_clients=100, k=15, max_cohort=10)


def test_cohort_width_default_padding():
    cfg = RunConfig(n_clients=100, k=15)
    assert 15 < cfg.cohort_width() <= 100
    assert RunConfig(n_clients=100, k=15, max_cohort=40).cohort_width() == 40


# ---------------------------------------------------------------------------
# Serializer
# ---------------------------------------------------------------------------


def test_to_jsonable_nan_and_numpy():
    payload = {
        "nan": float("nan"), "inf": float("inf"),
        "np_f": np.float32(1.5), "np_i": np.int64(3), "np_b": np.bool_(True),
        "arr": np.array([1.0, np.nan]),
        "jax": jnp.ones((2,)),
        "nested": [{"x": (1, 2)}],
    }
    out = to_jsonable(payload)
    assert out["nan"] is None and out["inf"] is None
    assert out["np_f"] == 1.5 and out["np_i"] == 3 and out["np_b"] is True
    assert out["arr"] == [1.0, None]
    assert out["jax"] == [1.0, 1.0]
    # strict JSON round-trips (this is what allow_nan=False consumers need)
    json.dumps(out, allow_nan=False)


def test_run_result_jsonable(small_task):
    res = run_engine(SyncEngine(small_task, _cfg()))
    payload = res.to_jsonable()
    s = json.dumps(payload, allow_nan=False)
    back = json.loads(s)
    assert back["config"]["policy"] == "markov"
    assert back["history"]["round"] == [3]
    assert back["wall_stats"] is None


def test_rounds_to_target_reads_the_history():
    history = {"round": [2, 4, 6, 8], "accuracy": [0.1, 0.55, 0.5, 0.7]}
    assert rounds_to_target(history, 0.5) == 4
    assert rounds_to_target(history, 0.7) == 8
    assert rounds_to_target(history, 0.9) is None

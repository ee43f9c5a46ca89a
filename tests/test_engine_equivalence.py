"""Golden seed-equivalence: the unified engines must reproduce the
pre-refactor sync and async round loops bit-for-bit.

The reference implementations below are frozen copies of the round loops
as they stood before the ``repro.engine`` refactor (PR 1), with the
aggregation math inlined exactly as it was hardwired then. If the engines
or the fedavg/fedbuff aggregators drift numerically — different op order,
dtype, or key schedule — these tests fail on exact comparison.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import MNIST_CNN
from repro.core.selection import make_policy
from repro.data.synthetic import make_image_dataset
from repro.engine import (
    AsyncEngine,
    RunConfig,
    SyncEngine,
    make_engine,
    run_engine,
)
from repro.engine.aggregators import staleness_weight
from repro.fl import make_cnn_task
from repro.fl.client import make_local_update
from repro.fl.server import broadcast_to_cohort, cohort_indices, fedavg_aggregate
from repro.optim.schedules import exponential_decay
from repro.sim import events as ev_mod
from repro.sim import latency as lat_mod

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


def _cfg(policy, rounds=5, **kw):
    base = dict(
        n_clients=20, k=4, m=6, policy=policy, rounds=rounds,
        local_epochs=2, batch_size=10, eval_every=1,
    )
    base.update(kw)
    return RunConfig(**base)


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# Reference sync loop (pre-refactor fl/rounds.py, verbatim math)
# ---------------------------------------------------------------------------


def _reference_sync_run(task, cfg):
    policy = make_policy(cfg.policy, cfg.n_clients, cfg.k, cfg.m)
    width = cfg.cohort_width() if not policy.exact_k else cfg.k
    local_update = make_local_update(
        task.loss_fn, cfg.local_epochs, cfg.batch_size, task.examples_per_client
    )
    lr_fn = exponential_decay(cfg.lr0, cfg.lr_decay)

    @jax.jit
    def round_fn(params, sched_state, key):
        k_sel, k_local = jax.random.split(key)
        selected, sched_state = policy.step(sched_state, k_sel)
        idx, weights = cohort_indices(selected, width)
        shards = jax.tree.map(lambda a: a[idx], task.client_data)
        lr = lr_fn(sched_state["round"] - 1)
        cohort_params = broadcast_to_cohort(params, width)
        keys = jax.random.split(k_local, width)
        updated, losses = jax.vmap(local_update, in_axes=(0, 0, 0, None))(
            cohort_params, shards, keys, lr
        )
        params = fedavg_aggregate(params, updated, weights)
        mean_loss = jnp.sum(losses * weights) / jnp.maximum(weights.sum(), 1.0)
        return params, sched_state, selected, mean_loss

    key = jax.random.PRNGKey(cfg.seed)
    k_init, k_policy, k_run = jax.random.split(key, 3)
    params = task.init(k_init)
    sched_state = policy.init(k_policy, cfg.n_clients)
    sel_hist = np.zeros((cfg.rounds, cfg.n_clients), dtype=bool)
    losses = []
    for r in range(cfg.rounds):
        params, sched_state, selected, loss = round_fn(
            params, sched_state, jax.random.fold_in(k_run, r)
        )
        sel_hist[r] = np.asarray(selected)
        losses.append(float(loss))
    return params, sel_hist, losses


@pytest.mark.parametrize("policy", ["markov", "random"])
def test_sync_engine_matches_prerefactor_loop(small_task, policy):
    cfg = _cfg(policy)
    ref_params, ref_sel, ref_losses = _reference_sync_run(small_task, cfg)
    res = run_engine(make_engine(small_task, cfg))
    np.testing.assert_array_equal(res.selection, ref_sel)
    np.testing.assert_array_equal(res.history()["train_loss"], ref_losses)
    _assert_trees_equal(res.params, ref_params)


def test_sync_engine_direct_api_matches_prerefactor_loop(small_task):
    cfg = _cfg("markov")
    ref_params, ref_sel, ref_losses = _reference_sync_run(small_task, cfg)
    res = run_engine(SyncEngine(small_task, cfg))
    np.testing.assert_array_equal(res.selection, ref_sel)
    np.testing.assert_array_equal(
        [r.train_loss for r in res.records], ref_losses
    )
    _assert_trees_equal(res.params, ref_params)


# ---------------------------------------------------------------------------
# Reference async loop (pre-refactor sim/async_rounds.py, verbatim math)
# ---------------------------------------------------------------------------


def _reference_async_run(task, cfg):
    policy = make_policy(cfg.policy, cfg.n_clients, cfg.k, cfg.m)
    n = cfg.n_clients
    B = cfg.resolved_buffer_size()
    H = cfg.max_versions
    profile = cfg.profile
    if isinstance(profile, str):
        profile = lat_mod.get_profile(profile)
    staleness_mode = cfg.aggregator_kwargs.get("staleness_mode", "poly")
    staleness_exp = cfg.aggregator_kwargs.get("staleness_exp", 0.5)
    local_update = make_local_update(
        task.loss_fn, cfg.local_epochs, cfg.batch_size, task.examples_per_client
    )
    lr_fn = exponential_decay(cfg.lr0, cfg.lr_decay)

    def init_state(params, sched_state, key):
        return {
            "params": params,
            "hist": jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (H,) + p.shape), params
            ),
            "sched": sched_state,
            "ev": ev_mod.init_event_state(n),
            "speed": lat_mod.client_speed(key, n, profile),
            "clock": jnp.zeros((), jnp.float32),
            "version": jnp.zeros((), jnp.int32),
        }

    @jax.jit
    def step(state, key):
        ev, sched = state["ev"], state["sched"]
        clock, version = state["clock"], state["version"]
        k_sel, k_local = jax.random.split(key)
        k_lat = jax.random.fold_in(k_sel, 101)
        k_drop = jax.random.fold_in(k_sel, 102)
        k_gap = jax.random.fold_in(k_sel, 103)

        from repro.core.aoi import age_update

        prev_ages = sched["ages"]
        idle = jnp.isinf(ev["t_done"])
        available = ev["next_avail"] <= clock
        want, sched = policy.step(sched, k_sel)
        send = want & idle & available
        sched = {**sched, "ages": age_update(prev_ages, send)}

        latency = lat_mod.sample_latency(k_lat, profile, state["speed"])
        dropped = lat_mod.sample_dropout(k_drop, profile, n)
        ev = ev_mod.schedule_completions(ev, send, clock, latency, version, dropped)

        t_ev, idx, valid, ev = ev_mod.pop_events(ev, B, use_kernel=cfg.use_kernel)
        new_clock = jnp.maximum(clock, jnp.max(jnp.where(valid, t_ev, -jnp.inf)))
        new_clock = jnp.where(
            valid.any(), new_clock,
            jnp.maximum(new_clock, jnp.min(ev["next_avail"])),
        )

        disp_ver = ev["disp_ver"][idx]
        read_ver = jnp.clip(disp_ver, jnp.maximum(version - (H - 1), 0), version)
        disp_params = jax.tree.map(lambda h: h[read_ver % H], state["hist"])
        shards = jax.tree.map(lambda a: a[idx], task.client_data)
        keys = jax.random.split(k_local, B)
        lr = lr_fn(jnp.maximum(disp_ver, 0))
        updated, losses = jax.vmap(local_update, in_axes=(0, 0, 0, 0))(
            disp_params, shards, keys, lr
        )

        succ = valid & ~ev["dropped"][idx]
        staleness = jnp.maximum(version - disp_ver, 0)
        w = succ.astype(jnp.float32) * staleness_weight(
            staleness, staleness_mode, staleness_exp
        )
        wsum = w.sum()
        has = wsum > 0
        denom = jnp.maximum(wsum, 1e-9)

        def agg(g, u, d):
            wshape = (-1,) + (1,) * (g.ndim)
            delta = (u - d).astype(jnp.float32)
            upd = g + (jnp.sum(delta * w.reshape(wshape), axis=0) / denom).astype(g.dtype)
            return jnp.where(has, upd, g)

        params = jax.tree.map(agg, state["params"], updated, disp_params)
        version = version + has.astype(jnp.int32)
        hist = jax.tree.map(
            lambda h, p: h.at[version % H].set(p), state["hist"], params
        )
        mean_loss = jnp.where(has, jnp.sum(losses * w) / denom, jnp.nan)

        gaps = lat_mod.sample_avail_gap(k_gap, profile, B)
        ev = {
            **ev,
            "next_avail": ev["next_avail"]
            .at[ev_mod.scatter_idx(idx, valid)]
            .set(new_clock + gaps, mode="drop"),
        }
        ev = {
            **ev,
            "last_done": ev["last_done"]
            .at[ev_mod.scatter_idx(idx, succ)]
            .set(t_ev, mode="drop"),
        }
        state = {
            **state,
            "params": params, "hist": hist, "sched": sched, "ev": ev,
            "clock": new_clock, "version": version,
        }
        return state, {"send": send, "loss": mean_loss}

    key = jax.random.PRNGKey(cfg.seed)
    k_init, k_policy, k_run = jax.random.split(key, 3)
    params = task.init(k_init)
    sched = policy.init(k_policy, cfg.n_clients)
    state = init_state(params, sched, jax.random.fold_in(k_run, 2**31))
    sel_hist = np.zeros((cfg.rounds, cfg.n_clients), dtype=bool)
    losses = []
    for s in range(cfg.rounds):
        state, aux = step(state, jax.random.fold_in(k_run, s))
        sel_hist[s] = np.asarray(aux["send"])
        losses.append(float(aux["loss"]))
    return state["params"], sel_hist, losses


def test_async_engine_matches_prerefactor_loop(small_task):
    cfg = _cfg("markov", mode="async", buffer_size=4, profile="lognormal")
    ref_params, ref_sel, ref_losses = _reference_async_run(small_task, cfg)
    res = run_engine(make_engine(small_task, cfg))
    np.testing.assert_array_equal(np.asarray(res.selection), ref_sel)
    np.testing.assert_array_equal(res.history()["train_loss"], ref_losses)
    _assert_trees_equal(res.params, ref_params)


def test_async_engine_with_dropout_matches_prerefactor_loop(small_task):
    prof = dataclasses.replace(lat_mod.get_profile("mobile"), dropout=0.3)
    cfg = _cfg(
        "random", rounds=6, mode="async", aggregator="fedbuff",
        aggregator_kwargs={"staleness_mode": "poly", "staleness_exp": 0.7},
        buffer_size=3, max_versions=4, profile=prof,
    )
    ref_params, ref_sel, ref_losses = _reference_async_run(small_task, cfg)
    res = run_engine(AsyncEngine(small_task, cfg))
    np.testing.assert_array_equal(np.asarray(res.selection), ref_sel)
    np.testing.assert_array_equal(
        np.asarray([r.train_loss for r in res.records]),
        np.asarray(ref_losses),
    )
    _assert_trees_equal(res.params, ref_params)


# ---------------------------------------------------------------------------
# Zero-spread async == sync FedAvg through the new API
# ---------------------------------------------------------------------------


def test_degenerate_async_equals_sync_through_engine_api(small_task):
    base = RunConfig(
        n_clients=20, k=4, m=6, policy="random", rounds=5,
        local_epochs=2, batch_size=10, eval_every=1,
    )
    sync = run_engine(SyncEngine(small_task, base))
    acfg = dataclasses.replace(
        base, mode="async", buffer_size=base.k,
        aggregator_kwargs={"staleness_mode": "const"}, profile="uniform",
    )
    asy = run_engine(AsyncEngine(small_task, acfg))
    np.testing.assert_array_equal(sync.selection, asy.selection)
    np.testing.assert_allclose(
        [r.train_loss for r in sync.records],
        [r.train_loss for r in asy.records], rtol=1e-4,
    )
    np.testing.assert_allclose(
        [r.eval_loss for r in sync.records],
        [r.eval_loss for r in asy.records], rtol=1e-4,
    )
    assert asy.wall_stats["max_staleness"] == 0
    assert asy.wall_stats["aggregations"] == base.rounds

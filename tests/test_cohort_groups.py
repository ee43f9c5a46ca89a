"""The sync round's cohort group loop (``engine.sync._train_groups``).

A variable-size policy's cohort is packed into a prefix of
``cfg.cohort_width()`` slots (``fl.server.cohort_indices``), and on one
device local training runs only over the groups of slots that hold a
selected client. These tests pin that the grouped round is bit for bit
the single full-width vmap round of the ``cohort_layout`` seam, that the
packing the loop relies on holds, and that the round's ``trained_slots``
counter reads ``G * ceil(count / G)`` on the grouped path and the whole
(padded) width where every slot is trained.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import MNIST_CNN
from repro.core.selection import Policy, make_policy
from repro.data.synthetic import make_image_dataset
from repro.engine import RunConfig, SyncEngine, run_engine
from repro.engine import sync
from repro.engine.registry import make_aggregator
from repro.engine.sync import _group_size, _train_groups
from repro.fl import make_cnn_task
from repro.fl.client import make_local_update
from repro.fl.server import cohort_indices

SMALL_CNN = dataclasses.replace(
    MNIST_CNN, name="paper-cnn-mnist-small", image_size=16,
    conv_channels=(8, 16), fc_width=64,
)

N, K, M = 20, 4, 6
WIDTH = 12  # default_cohort_width(20, 4)
G = 3  # _group_size(12)


@pytest.fixture(scope="module")
def small_task():
    train, test = make_image_dataset(
        "mnist-small", 10, 16, 1, 600, 500, seed=0, difficulty=0.8
    )
    return make_cnn_task(SMALL_CNN, train, test, n_clients=N)


def _cfg(policy="markov", **kw):
    base = dict(n_clients=N, k=K, m=M, policy=policy, rounds=4,
                local_epochs=2, batch_size=10, eval_every=1)
    base.update(kw)
    return RunConfig(**base)


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _scripted_policy(counts):
    """A variable-size policy whose round r selects ``counts[r]`` clients,
    drawn from the key, so a test chooses each round's cohort size."""
    counts = jnp.asarray(counts, jnp.int32)

    def init(key, n_=N):
        return {"ages": jnp.zeros((n_,), jnp.int32),
                "round": jnp.zeros((), jnp.int32)}

    def step(state, key):
        rank = jnp.argsort(jax.random.permutation(key, N))
        sel = rank < counts[state["round"]]
        ages = jnp.where(sel, 0, state["ages"] + 1)
        return sel, {"ages": ages, "round": state["round"] + 1}

    return Policy("scripted", init, step, exact_k=False)


@pytest.mark.parametrize("width,group", [
    (30, 5), (12, 3), (16, 4), (29, 5), (1, 1), (6, 2), (23, 4), (10, 2),
])
def test_group_size_is_about_sqrt_with_least_padding(width, group):
    assert _group_size(width) == group


@pytest.mark.parametrize("count", [0, 1, 5, 12, 13, 20])
def test_cohort_indices_packs_valid_slots_into_a_prefix(count):
    sel = np.zeros(N, bool)
    sel[np.random.default_rng(count).permutation(N)[:count]] = True
    idx, w = cohort_indices(jnp.asarray(sel), WIDTH)
    idx, w = np.asarray(idx), np.asarray(w)
    c = min(count, WIDTH)
    np.testing.assert_array_equal(w, (np.arange(WIDTH) < c).astype(np.float32))
    np.testing.assert_array_equal(idx[:c], np.flatnonzero(sel)[:c])
    np.testing.assert_array_equal(idx[c:], 0)


@pytest.mark.parametrize("count,group", [
    (0, G), (3, G), (7, G), (WIDTH, G), (7, 5), (WIDTH, 5),
])
def test_train_groups_matches_one_full_vmap_on_selected_slots(small_task, count,
                                                             group):
    """Groups of ``group`` slots and one group of the whole width give the
    full vmap's results on the selected slots; 5 does not divide the
    width, so the last pass runs over padding slots that are dropped."""
    cfg = _cfg()
    local_update = make_local_update(small_task.loss_fn, cfg.local_epochs,
                                     cfg.batch_size,
                                     small_task.examples_per_client)
    params = small_task.init(jax.random.PRNGKey(0))
    sel = jnp.arange(N) % 2 == 1
    sel = sel & (jnp.cumsum(sel) <= count)
    idx, mask = cohort_indices(sel, WIDTH)
    keys = jax.random.split(jax.random.PRNGKey(1), WIDTH)
    lr = jnp.float32(cfg.lr0)
    data = small_task.client_data

    grouped = jax.jit(_train_groups, static_argnums=(0, 7))
    up, losses, trained = grouped(local_update, params, data, idx, keys, lr,
                                  jnp.int32(count), group)
    up1, losses1, trained1 = grouped(local_update, params, data, idx, keys,
                                     lr, jnp.int32(count), WIDTH)
    full_up, full_losses = jax.jit(jax.vmap(
        local_update, in_axes=(None, 0, 0, None)))(
        params, jax.tree.map(lambda a: a[idx], data), keys, lr)

    assert int(trained) == group * math.ceil(count / group)
    assert int(trained1) == (WIDTH if count else 0)
    assert jax.tree.map(jnp.shape, up) == jax.tree.map(jnp.shape, full_up)
    assert losses.shape == (WIDTH,)
    t = min(int(trained), WIDTH)
    # the trained slots are the full vmap's, bit for bit, in both groupings
    for got in (up, up1):
        _assert_trees_equal(jax.tree.map(lambda a: a[:count], got),
                            jax.tree.map(lambda a: a[:count], full_up))
    for got in (losses, losses1):
        np.testing.assert_array_equal(np.asarray(got)[:count],
                                      np.asarray(full_losses)[:count])
    # the groups past the count keep the global params and loss 0
    for leaf, p in zip(jax.tree.leaves(up), jax.tree.leaves(params)):
        np.testing.assert_array_equal(
            np.asarray(leaf)[t:],
            np.broadcast_to(np.asarray(p), (WIDTH - t,) + p.shape))
    np.testing.assert_array_equal(np.asarray(losses)[t:], 0.0)


def _run_core(task, cfg, policy, rounds, **kw):
    core = jax.jit(sync._make_round_core(task, cfg, policy,
                                         make_aggregator("fedavg"), **kw))
    key = jax.random.PRNGKey(cfg.seed)
    k_init, k_policy, k_run = jax.random.split(key, 3)
    params = task.init(k_init)
    sched = policy.init(k_policy, cfg.n_clients)
    out = []
    for r in range(rounds):
        params, sched, selected, loss, trained, _, _, _ = core(
            params, sched, jax.random.fold_in(k_run, r), task.client_data)
        out.append((jax.device_get(params), np.asarray(selected),
                    float(loss), int(trained)))
    return out


# round 0 selects nobody, round 1 a count that is no multiple of G,
# round 2 more clients than the width holds (overflow), round 3 exactly G
COUNTS = [0, 7, 15, G]


def _identity(tree):
    return tree


def test_grouped_round_equals_one_group_round(small_task, monkeypatch):
    """The grouped round against the one-group round (the loop with one
    pass over the whole width) and against the single full-width vmap
    that the ``cohort_layout`` seam keeps (here the identity, on one
    device)."""
    cfg = _cfg()
    policy = _scripted_policy(COUNTS)
    grouped = _run_core(small_task, cfg, policy, len(COUNTS))
    vmap = _run_core(small_task, cfg, policy, len(COUNTS),
                     cohort_layout=_identity)
    monkeypatch.setattr(sync, "_group_size", lambda width: width)
    one = _run_core(small_task, cfg, policy, len(COUNTS))
    for r, (got, o, v) in enumerate(zip(grouped, one, vmap)):
        (p, s, l, t), (p1, s1, l1, t1), (pv, sv, lv, tv) = got, o, v
        np.testing.assert_array_equal(s, s1)
        np.testing.assert_array_equal(s, sv)
        assert int(s.sum()) == COUNTS[r]
        _assert_trees_equal(p, p1)
        _assert_trees_equal(p, pv)
        np.testing.assert_array_equal(l, l1)  # NaN in the empty round
        # the full vmap's program sums the weighted losses in another
        # fusion: equal to the last bit or two of float32
        np.testing.assert_allclose(l, lv, rtol=1e-6)
        c = min(COUNTS[r], WIDTH)
        assert t == G * math.ceil(c / G)
        assert t1 == WIDTH * math.ceil(c / WIDTH)
        assert tv == WIDTH
    assert np.isnan(grouped[0][2])
    assert grouped[2][3] == WIDTH


def test_engine_records_trained_slots_per_round(small_task):
    cfg = _cfg(rounds=6, steps_per_chunk=3)
    engine = SyncEngine(small_task, cfg)
    res = run_engine(engine)
    width = cfg.cohort_width()
    assert width == WIDTH
    counts = np.minimum(res.selection.sum(1), width)
    want = [G * math.ceil(int(c) / G) for c in counts]
    assert [r.trained_slots for r in res.records] == want
    state = engine.init()
    for r in range(3):
        state, aux = engine.step(state, r)
        c = min(int(np.asarray(aux["send"]).sum()), width)
        assert int(aux["trained_slots"]) == G * math.ceil(c / G)


def test_exact_k_rounds_train_the_whole_width(small_task):
    res = run_engine(SyncEngine(small_task, _cfg(policy="random")))
    assert [r.trained_slots for r in res.records] == [K] * 4


@pytest.mark.parametrize("shards,padded", [(1, WIDTH), (5, 15)])
def test_cohort_layout_rounds_train_the_whole_padded_width(small_task, shards,
                                                           padded):
    """The cohort-sharded seam (a ``cohort_layout`` hook, here the
    identity so it runs on one device) keeps the single vmap over every
    slot, padding to the mesh multiple included."""
    cfg = _cfg()
    policy = make_policy("markov", N, K, M)
    out = _run_core(small_task, cfg, policy, 3,
                    cohort_layout=_identity, cohort_shards=shards)
    assert [t for *_, t in out] == [padded] * 3

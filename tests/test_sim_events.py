"""Event engine: determinism, Pallas event_topk vs jnp reference
(interpret mode on CPU), latency-model properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.sim import events as ev_mod
from repro.sim import latency as lat_mod

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# latency models
# ---------------------------------------------------------------------------


def test_uniform_profile_is_degenerate():
    p = lat_mod.get_profile("uniform")
    speed = lat_mod.client_speed(KEY, 64, p)
    lat = lat_mod.sample_latency(jax.random.fold_in(KEY, 1), p, speed)
    np.testing.assert_allclose(np.asarray(lat), 1.0)
    assert not bool(lat_mod.sample_dropout(KEY, p, 64).any())
    np.testing.assert_allclose(np.asarray(lat_mod.sample_avail_gap(KEY, p, 64)), 0.0)


def test_latency_samples_positive_and_shaped():
    for name in ("datacenter", "lognormal", "mobile"):
        p = lat_mod.get_profile(name)
        speed = lat_mod.client_speed(KEY, 128, p)
        lat = lat_mod.sample_latency(jax.random.fold_in(KEY, 2), p, speed)
        assert lat.shape == (128,)
        assert bool((lat > 0).all())
        # spread profiles actually spread
        assert float(lat.std()) > 0.0


def test_dropout_rate_matches_hazard():
    p = lat_mod.get_profile("mobile")
    drops = lat_mod.sample_dropout(KEY, p, 20000)
    assert abs(float(drops.mean()) - p.dropout) < 0.02


def test_unknown_profile_raises():
    with pytest.raises(ValueError):
        lat_mod.get_profile("nope")


def test_mean_latency_closed_form():
    p = lat_mod.get_profile("lognormal")
    speed = lat_mod.client_speed(KEY, 200_000, p)
    lat = lat_mod.sample_latency(jax.random.fold_in(KEY, 3), p, speed)
    assert abs(float(lat.mean()) - p.mean_latency()) / p.mean_latency() < 0.05


# ---------------------------------------------------------------------------
# next-k extraction: jnp reference vs Pallas kernel (interpret on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k,block_n,pending_frac,ties", [
    (64, 4, 16, 1.0, False),
    (1000, 16, 128, 0.3, False),
    (1000, 16, 256, 0.01, False),  # fewer pending events than k in most tiles
    (513, 8, 128, 0.5, False),  # ragged final tile
    (3000, 130, 1024, 0.5, False),  # k not a multiple of 128, n not of the block
    (5000, 200, 1024, 0.5, True),  # heavy ties across tiles
    (3000, 1500, 1024, 0.02, False),  # k above the block: tiles widen to k
    (10, 3, 1024, 1.0, True),  # fleet smaller than one vreg tile
])
def test_event_topk_kernel_matches_reference(n, k, block_n, pending_frac, ties):
    kx, km = jax.random.split(jax.random.fold_in(KEY, n * k))
    if ties:
        t = jax.random.randint(kx, (n,), 0, 5).astype(jnp.float32)
    else:
        t = jax.random.uniform(kx, (n,)) * 100
    pending = jax.random.uniform(km, (n,)) < pending_frac
    times = jnp.where(pending, t, jnp.inf).astype(jnp.float32)
    ref_v, ref_i = ev_mod.next_k_events(times, k, use_kernel=False)
    ker_v, ker_i = ops.event_next_k(times, k, block_n=block_n)
    # every slot, idle (+inf) ones included, in lax.top_k's tie order
    np.testing.assert_array_equal(np.asarray(ker_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(ker_i), np.asarray(ref_i))


def test_next_k_ties_break_low_index():
    times = jnp.full((10,), 5.0, jnp.float32)
    for use_kernel in (False, True):
        _, idx = ev_mod.next_k_events(times, 3, use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(idx), [0, 1, 2])


def test_next_k_all_idle_returns_inf():
    times = jnp.full((32,), jnp.inf, jnp.float32)
    v, _ = ev_mod.next_k_events(times, 4, use_kernel=False)
    assert not np.isfinite(np.asarray(v)).any()


# ---------------------------------------------------------------------------
# engine semantics
# ---------------------------------------------------------------------------


def test_pop_removes_events_and_is_deterministic():
    n, k = 50, 8

    def run():
        ev = ev_mod.init_event_state(n)
        lat = lat_mod.sample_latency(
            KEY, lat_mod.get_profile("lognormal"),
            lat_mod.client_speed(jax.random.fold_in(KEY, 9), n,
                                 lat_mod.get_profile("lognormal")),
        )
        send = jnp.arange(n) % 2 == 0
        ev = ev_mod.schedule_completions(
            ev, send, jnp.float32(0.0), lat, jnp.int32(0),
            jnp.zeros((n,), jnp.bool_),
        )
        pops = []
        for _ in range(3):
            t, idx, valid, ev = ev_mod.pop_events(ev, k)
            pops.append((np.asarray(t), np.asarray(idx), np.asarray(valid)))
        return pops, np.asarray(ev["t_done"])

    pops_a, tdone_a = run()
    pops_b, tdone_b = run()
    for (ta, ia, va), (tb, ib, vb) in zip(pops_a, pops_b):
        np.testing.assert_array_equal(ta, tb)
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(va, vb)
    np.testing.assert_array_equal(tdone_a, tdone_b)
    # 25 dispatched, popped 8+8+8=24 valid, never the same client twice
    all_idx = np.concatenate([i[v] for _, i, v in pops_a])
    assert len(all_idx) == len(set(all_idx.tolist())) == 24
    # popped clients are idle again
    assert np.isinf(tdone_a[all_idx]).all()
    # pops arrive in nondecreasing time order across batches
    all_t = np.concatenate([t[v] for t, _, v in pops_a])
    assert (np.diff(all_t) >= -1e-6).all()


def test_pop_kernel_path_fewer_events_than_k():
    """Exhausted kernel tiles emit idle clients for their +inf filler
    slots; the scatter back must drop them — the popped event must stay
    cleared, and no idle client may be written."""
    n = 8
    ev = ev_mod.init_event_state(n)
    ev = ev_mod.schedule_completions(
        ev, jnp.arange(n) == 0, jnp.float32(0.0),
        jnp.full((n,), 2.0, jnp.float32), jnp.int32(0),
        jnp.zeros((n,), jnp.bool_),
    )
    t, idx, valid, ev2 = ev_mod.pop_events(ev, 4, use_kernel=True)
    assert int(valid.sum()) == 1
    assert float(t[0]) == pytest.approx(2.0) and int(idx[0]) == 0
    assert np.isinf(np.asarray(ev2["t_done"])).all()
    _, _, valid2, _ = ev_mod.pop_events(ev2, 4, use_kernel=True)
    assert not bool(valid2.any())


def test_pop_invalid_slots_are_noops():
    ev = ev_mod.init_event_state(16)
    ev = ev_mod.schedule_completions(
        ev, jnp.arange(16) == 3, jnp.float32(1.0),
        jnp.full((16,), 2.0, jnp.float32), jnp.int32(0),
        jnp.zeros((16,), jnp.bool_),
    )
    t, idx, valid, ev2 = ev_mod.pop_events(ev, 4)
    assert int(valid.sum()) == 1
    assert float(t[0]) == pytest.approx(3.0)
    assert np.isinf(np.asarray(ev2["t_done"])).all()

"""Property test: the sharded buffer pop equals the global top-k.

``sharded_next_k_events`` (per-shard local top-B -> all_gather of the
``devices x B`` candidates -> one stable merge) must reproduce a global
``lax.top_k`` over the full fleet *exactly* — times, indices, and tie
order — including ragged fleets where ``n % devices != 0`` (padded
internally with ``+inf`` sentinels) and times vectors dense with ties and
idle ``+inf`` slots. Hypothesis drives sizes and contents; the reference
is the unsharded ``next_k_events`` path the single-device engine uses.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import distributed as dist
from repro.sim import events as ev_mod

DEVICES = jax.local_device_count()
MESH = dist.fleet_mesh(DEVICES)

# a small value pool forces heavy ties; +inf models idle clients
_times = st.lists(
    st.one_of(
        st.sampled_from([1.0, 2.0, 3.0, jnp.inf]),
        # width-32 bounds must be float32 values themselves
        st.floats(float(np.float32(0.01)), 100.0, allow_nan=False,
                  allow_infinity=False, width=32),
    ),
    min_size=1, max_size=4 * DEVICES + 5,
)


def _check(times_list, k):
    times = jnp.asarray(times_list, jnp.float32)
    n = times.shape[0]
    ref_t, ref_i = ev_mod.next_k_events(times, k, use_kernel=False)
    merge = jax.jit(dist.sharded_next_k_events(MESH, n, k))
    sh_t, sh_i = merge(times)
    # identical times everywhere, identical indices (tie order included)
    # wherever a real event exists
    np.testing.assert_array_equal(np.asarray(sh_t), np.asarray(ref_t))
    valid = np.isfinite(np.asarray(ref_t))
    np.testing.assert_array_equal(
        np.asarray(sh_i)[valid], np.asarray(ref_i)[valid]
    )


@settings(deadline=None, max_examples=25)
@given(data=st.data())
def test_sharded_pop_matches_global_topk(data):
    times = data.draw(_times)
    k = data.draw(st.integers(1, len(times)))
    _check(times, k)


def test_sharded_pop_ragged_all_tied():
    # ragged n for every device count > 1, all times tied: indices must
    # come back 0..k-1 in order (lower-global-index tie contract)
    n = 4 * DEVICES + 3
    times = jnp.full((n,), 7.5, jnp.float32)
    merge = jax.jit(dist.sharded_next_k_events(MESH, n, 5))
    t, idx = merge(times)
    np.testing.assert_array_equal(np.asarray(t), np.full(5, 7.5))
    np.testing.assert_array_equal(np.asarray(idx), np.arange(5))


def test_sharded_pop_all_idle():
    n = 2 * DEVICES + 1
    merge = jax.jit(dist.sharded_next_k_events(MESH, n, 3))
    t, _ = merge(jnp.full((n,), jnp.inf, jnp.float32))
    assert not np.isfinite(np.asarray(t)).any()


def test_sharded_pop_feeds_apply_pop():
    # end to end through the event-engine bookkeeping: popped clients go
    # idle, invalid slots never write back
    n = 3 * DEVICES + 1
    ev = ev_mod.init_event_state(n)
    send = jnp.arange(n) % 3 == 0
    ev = ev_mod.schedule_completions(
        ev, send, jnp.float32(0.0), jnp.full((n,), 2.0, jnp.float32),
        jnp.int32(0), jnp.zeros((n,), jnp.bool_),
    )
    merge = jax.jit(dist.sharded_next_k_events(MESH, n, n))
    t, idx = merge(ev["t_done"])
    t, idx_safe, valid, ev2 = ev_mod.apply_pop(ev, t, idx)
    assert int(valid.sum()) == int(send.sum())
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx_safe)[np.asarray(valid)]),
        np.flatnonzero(np.asarray(send)),
    )
    assert np.isinf(np.asarray(ev2["t_done"])).all()

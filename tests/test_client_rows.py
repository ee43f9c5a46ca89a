"""The async engine's client data held as rows.

``client_rows`` flattens every client-data leaf of more than two axes to
``(n_clients, prod(rest))`` and ``gather_rows`` takes a cohort's rows back
to the per-client shapes: together they must equal the plain gather
``jax.tree.map(lambda a: a[idx], data)`` value for value, for every rank
and dtype, with repeated clients and the zero-padded slots of a
cohort-parallel mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine.async_engine import client_rows, gather_rows

N = 12

SHAPES = {
    "rank1": (N,),
    "rank2": (N, 4),
    "rank3": (N, 3, 5),
    "rank5": (N, 4, 6, 6, 1),
}

IDX = {
    "distinct": [3, 0, 11, 7],
    "repeated": [5, 5, 2, 5, 9],
    "padded": [4, 10, 1, 0, 0, 0],  # cohort_pad appends client 0
}


def _leaf(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    if dtype == jnp.int32:
        return jnp.asarray(rng.integers(-1000, 1000, shape), jnp.int32)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("rank", sorted(SHAPES))
@pytest.mark.parametrize("which", sorted(IDX))
def test_gather_rows_equals_plain_gather(rank, dtype, which):
    data = {"a": _leaf(SHAPES[rank], dtype, 0),
            "b": [_leaf(SHAPES[rank], dtype, 1)]}
    idx = jnp.asarray(IDX[which], jnp.int32)
    rows = client_rows(data)
    for r, a in zip(jax.tree.leaves(rows), jax.tree.leaves(data)):
        assert r.shape == (a.shape if a.ndim <= 2
                           else (N, int(np.prod(a.shape[1:]))))
    want = jax.tree.map(lambda a: a[idx], data)
    got = jax.jit(gather_rows)(rows, idx, data)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_client_rows_maps_shapes_to_shapes():
    """Shapes alone (compiling without data) take the rows' shapes and
    keep their sharding."""
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    data = {"x": jax.ShapeDtypeStruct((N, 4, 6, 6, 1), jnp.float32,
                                      sharding=sharding),
            "y": jax.ShapeDtypeStruct((N, 4), jnp.int32, sharding=sharding)}
    rows = client_rows(data)
    assert rows["x"].shape == (N, 144) and rows["x"].dtype == jnp.float32
    assert rows["x"].sharding == sharding
    assert rows["y"] is data["y"]

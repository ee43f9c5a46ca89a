"""FedAvg server: cohort gather, aggregation, global state.

Aggregation handles *variable-size* cohorts (the Markov policy selects a
Binomial(~k) number of clients each round): selected indices are padded to
``max_cohort`` and averaged with 0/1 weights. On TPU the weighted mean is
the ``fedavg_reduce`` Pallas kernel; the jnp path is its reference.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def cohort_indices(selected: jnp.ndarray, width: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(indices (width,), weights (width,)) from an (n,) bool mask.

    The selected clients are packed, in ascending order, into a prefix of
    the slots: the first min(count, width) slots are valid and the rest
    padding, so the sync round's group loop (``engine.cohort._train_groups``)
    trains only the groups that hold a selected client. Overflow beyond
    ``width`` is dropped (rare: the default width is k + 4 sigma of the
    binomial cohort size); padding entries point at client 0 with
    weight 0.
    """
    idx = jnp.nonzero(selected, size=width, fill_value=-1)[0]
    w = (idx >= 0).astype(jnp.float32)
    return jnp.maximum(idx, 0), w


def fedavg_aggregate(
    global_params, cohort_params, weights: jnp.ndarray, use_kernel: bool = False
):
    """Weighted mean over the stacked cohort axis; falls back to the global
    params when the cohort is empty (no update this round).

    cohort_params: pytree with leading axis = max_cohort.
    """
    wsum = weights.sum()
    empty = wsum == 0.0
    denom = jnp.maximum(wsum, 1.0)

    if use_kernel:
        from repro.kernels import ops as kops

        def agg(g, c):
            flat = c.reshape(c.shape[0], -1).astype(jnp.float32)
            out = kops.fedavg_reduce(flat, weights / denom)
            return jnp.where(empty, g, out.reshape(g.shape).astype(g.dtype))

    else:

        def agg(g, c):
            wshape = (-1,) + (1,) * (c.ndim - 1)
            out = jnp.sum(c * weights.reshape(wshape).astype(c.dtype), axis=0) / denom.astype(c.dtype)
            return jnp.where(empty, g, out.astype(g.dtype))

    return jax.tree.map(agg, global_params, cohort_params)


def broadcast_to_cohort(params, width: int):
    """Replicate global params along a new cohort axis (for vmap)."""
    return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (width,) + p.shape), params)

"""Pallas TPU kernel: next-k-completion extraction at fleet scale.

The async event engine needs the k *earliest* pending completion times
among n in-flight clients, where n may be millions and idle clients carry
``+inf``. Phase 1 (this kernel) tiles the time vector and extracts each
tile's k earliest events by k successive VPU min-reduces (no sort);
phase 2 (``ops.event_next_k``) runs a tiny jnp top-k over the
``tiles * k`` candidates.

Iteration i picks the earliest entry strictly *after* the one picked at
i - 1 in (time, position) order, so nothing is written back into the
tile: the selected-element mask is a compare against the previous pick.
Ties break toward the lower position, and idle (+inf) entries are walked
in position order once a tile's pending events run out, so every tile
emits exactly what ``lax.top_k(-times)`` would rank first among its
entries. Because each tile holds at least k entries, the phase-2 merge
reproduces a global ``lax.top_k(-times, k)`` on every slot: values,
indices and tie order.

Layout (TPU rules): the fleet is padded with ``+inf`` to ``tiles * bn``
with ``bn`` a multiple of 8 x 128 and reshaped to ``(rows, 128)``, one
``(bn // 128, 128)`` block per program. Each program's k results are
built as ``(kr, 128)`` vectors in registers (an iota compare selects slot
i) and stored once; ``kr * 128`` is k rounded up to a multiple of 1024,
and ``ops.event_next_k`` slices the padding back off.

VMEM per program at block_n=65536: a 256 KiB time tile (double-buffered)
plus two ``(kr, 128)`` outputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 65536
LANES = 128
# one f32 vreg: 8 sublanes x 128 lanes; blocks and outputs are multiples
TILE = 8 * LANES


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _next_k_kernel(times_ref, vals_ref, idx_ref, *, k: int):
    rows = times_ref.shape[0]
    base = pl.program_id(0) * (rows * LANES)
    out_shape = vals_ref.shape  # (kr, 128)
    slot = (jax.lax.broadcasted_iota(jnp.int32, out_shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, out_shape, 1))
    none = jnp.int32(rows * LANES)

    def body(i, carry):
        t_prev, p_prev, out_v, out_i = carry  # t_prev, p_prev: (1, 1)
        t = times_ref[...]  # (rows, 128)
        pos = (jax.lax.broadcasted_iota(jnp.int32, t.shape, 0) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1))
        after = (t > t_prev) | ((t == t_prev) & (pos > p_prev))
        t_min = jnp.min(jnp.where(after, t, jnp.inf), keepdims=True)
        p_min = jnp.min(jnp.where(after & (t == t_min), pos, none),
                        keepdims=True)
        hit = slot == i
        out_v = jnp.where(hit, t_min, out_v)
        out_i = jnp.where(hit, base + p_min, out_i)
        return t_min, p_min, out_v, out_i

    init = (
        jnp.full((1, 1), -jnp.inf, jnp.float32),
        jnp.full((1, 1), -1, jnp.int32),
        jnp.full(out_shape, jnp.inf, jnp.float32),
        jnp.zeros(out_shape, jnp.int32),
    )
    _, _, out_v, out_i = jax.lax.fori_loop(0, k, body, init)
    vals_ref[...] = out_v
    idx_ref[...] = out_i


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def tile_next_k(
    times: jnp.ndarray,  # (n,) f32 completion times, +inf when idle
    *,
    k: int,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
):
    """Per-tile earliest events: ``(vals (tiles, kp), idx (tiles, kp))``
    with ``kp >= k``; columns ``k:`` are padding for the caller to drop."""
    times = times.astype(jnp.float32)
    n = times.shape[0]
    tiles = -(-n // max(min(block_n, n), k))
    # balance the tiles, and give each at least k entries
    bn = max(_round_up(-(-n // tiles), TILE), _round_up(k, TILE))
    pad = tiles * bn - n
    if pad:
        times = jnp.pad(times, (0, pad), constant_values=jnp.inf)
    rows = bn // LANES
    kr = _round_up(k, TILE) // LANES
    vals, idx = pl.pallas_call(
        functools.partial(_next_k_kernel, k=k),
        grid=(tiles,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((None, kr, LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, kr, LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles, kr, LANES), jnp.float32),
            jax.ShapeDtypeStruct((tiles, kr, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="event_topk",
    )(times.reshape(tiles * rows, LANES))
    return vals.reshape(tiles, kr * LANES), idx.reshape(tiles, kr * LANES)

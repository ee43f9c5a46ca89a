"""Jit'd public wrappers around the Pallas kernels.

On CPU (this container) kernels run with ``interpret=True`` — the kernel
body executes step-by-step with correct semantics, which is what the
allclose tests validate. On a real TPU backend ``interpret`` flips off
automatically.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import aoi_topk as _topk
from repro.kernels import event_topk as _etopk
from repro.kernels import fedavg_reduce as _fedavg
from repro.kernels import flash_attention as _flash
from repro.kernels import flash_decode as _fdec
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, scale, kind="full", window=0, block_q=None, block_k=None):
    kw = {}
    if block_q:
        kw["block_q"] = block_q
    if block_k:
        kw["block_k"] = block_k
    return _flash.flash_attention(
        q, k, v, scale=scale, kind=kind, window=window, interpret=_interpret(), **kw
    )


def flash_decode(q, k, v, valid_len, *, scale, block_l=None):
    kw = {"block_l": block_l} if block_l else {}
    return _fdec.flash_decode(
        q, k, v, valid_len, scale=scale, interpret=_interpret(), **kw
    )


def ssd_scan(x, dt, A, B_, C_, *, chunk=256):
    return _ssd.ssd_scan(x, dt, A, B_, C_, chunk=chunk, interpret=_interpret())


def fedavg_reduce(params, weights, *, block_n=None):
    kw = {"block_n": block_n} if block_n else {}
    return _fedavg.fedavg_reduce(params, weights, interpret=_interpret(), **kw)


def oldest_age_topk(ages, k, *, block_n=None):
    """Fleet-scale oldest-age selection: tiled kernel phase + tiny global
    top-k over candidates. Returns (values, indices)."""
    kw = {"block_n": block_n} if block_n else {}
    vals, idx = _topk.tile_topk(ages, k=k, interpret=_interpret(), **kw)
    flat_v, flat_i = vals.reshape(-1), idx.reshape(-1)
    top_v, pos = jax.lax.top_k(flat_v, k)
    return top_v, flat_i[pos]


def event_next_k(times, k, *, block_n=None):
    """Fleet-scale next-k-completion extraction: tiled kernel phase + tiny
    global top-k over per-tile candidates. Returns (times (k,), indices
    (k,)) of the k earliest events; slots with no pending event carry
    ``+inf`` times (mask by finiteness). Values and indices equal
    ``lax.top_k(-times, k)`` on every slot, tie order included."""
    kw = {"block_n": block_n} if block_n else {}
    vals, idx = _etopk.tile_next_k(times, k=k, interpret=_interpret(), **kw)
    flat_v, flat_i = vals[:, :k].reshape(-1), idx[:, :k].reshape(-1)
    neg_v, pos = jax.lax.top_k(-flat_v, k)
    return -neg_v, flat_i[pos]

"""The event engine: pending-completion times with next-k extraction.

State is a flat struct-of-arrays over the fleet — one f32 completion time
per client (``+inf`` when idle) plus availability/dropout bookkeeping —
so every engine operation is a fused vector op and the whole engine jits
into the training step. The only "priority queue" operation the async
loop needs is *pop the k earliest events*, which is a top-k over negated
times: the ``event_topk`` Pallas kernel at fleet scale, a plain
``lax.top_k`` reference otherwise — or, with the fleet state sharded
over a device mesh, the ``core.distributed.sharded_next_k_events``
local-top-k + gather + merge feeding ``apply_pop``. All paths break ties
toward the lower client index, which the sync-equivalence test relies on.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# fleets at or above this size route through the tiled Pallas kernel
KERNEL_THRESHOLD = 16384


def init_event_state(n: int) -> Dict[str, jnp.ndarray]:
    """Fresh engine state: everyone idle, available at t=0, never done."""
    return {
        "t_done": jnp.full((n,), jnp.inf, jnp.float32),  # completion time
        "disp_ver": jnp.full((n,), -1, jnp.int32),  # model version at dispatch
        "next_avail": jnp.zeros((n,), jnp.float32),  # availability-window start
        "dropped": jnp.zeros((n,), jnp.bool_),  # current dispatch will be lost
        "last_done": jnp.full((n,), -1.0, jnp.float32),  # last *successful* update
    }


def schedule_completions(
    ev: Dict[str, jnp.ndarray],
    send: jnp.ndarray,  # (n,) bool — clients dispatched this step
    clock: jnp.ndarray,  # () f32 current simulated time
    latency: jnp.ndarray,  # (n,) f32 per-client wall time if dispatched
    version: jnp.ndarray,  # () i32 current model version
    dropped: jnp.ndarray,  # (n,) bool per-dispatch dropout draw
) -> Dict[str, jnp.ndarray]:
    """Mark ``send`` clients in flight: completion at clock + latency."""
    return {
        **ev,
        "t_done": jnp.where(send, clock + latency, ev["t_done"]),
        "disp_ver": jnp.where(send, version, ev["disp_ver"]),
        "dropped": jnp.where(send, dropped, ev["dropped"]),
    }


def next_k_events(
    times: jnp.ndarray, k: int, *, use_kernel: bool | None = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(times (k,), idx (k,)) of the k earliest pending events.

    Slots beyond the number of pending events carry ``+inf`` times —
    callers mask by ``jnp.isfinite``. Ties break toward lower index.
    """
    n = times.shape[0]
    if use_kernel is None:
        # interpret-mode Pallas on CPU is far slower than lax.top_k
        use_kernel = n >= KERNEL_THRESHOLD and jax.default_backend() != "cpu"
    if use_kernel:
        from repro.kernels import ops

        return ops.event_next_k(times, k)
    neg, idx = jax.lax.top_k(-times.astype(jnp.float32), k)
    return -neg, idx


def pop_events(
    ev: Dict[str, jnp.ndarray], k: int, *, use_kernel: bool | None = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Extract the next k completions and return those clients to idle.

    Returns (event times (k,), client idx (k,), valid mask (k,), state').
    Invalid slots (fewer than k events pending) carry the indices of
    idle clients or of padding, so they gather client 0 data under a zero
    mask and are scattered to an out-of-range sentinel (dropped), never
    to a real client.
    """
    t, idx = next_k_events(ev["t_done"], k, use_kernel=use_kernel)
    return apply_pop(ev, t, idx)


def apply_pop(
    ev: Dict[str, jnp.ndarray], t: jnp.ndarray, idx: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Bookkeeping shared by every pop path (kernel, reference, and the
    mesh-sharded merge): mask invalid slots, return popped clients to
    idle. ``(t, idx)`` is any next-k extraction over ``ev["t_done"]``."""
    valid = jnp.isfinite(t)
    idx_safe = jnp.where(valid, idx, 0)
    t_done = ev["t_done"].at[scatter_idx(idx, valid)].set(jnp.inf, mode="drop")
    return t, idx_safe, valid, {**ev, "t_done": t_done}


def scatter_idx(idx: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Indices for a masked scatter over popped events: masked-out slots
    go out of range so ``.at[...].set(..., mode="drop")`` ignores them —
    an invalid slot names an idle client or padding, never to be written."""
    return jnp.where(mask, idx, jnp.iinfo(jnp.int32).max)

"""Distributed decentralized scheduling via ``shard_map``.

The paper's key systems claim: the Markov policy needs *no coordination* —
each client decides from its own age. At fleet scale this maps onto
``shard_map``: the (n,) age vector is sharded across the ``data`` axis, each
device runs the Bernoulli decisions for its local client shard with an
independent per-device RNG fold, and the only cross-device traffic is the
O(1) ``psum`` of cohort counts (vs. an O(n) gather that a centralized
policy such as oldest-age top-k requires — which we also provide, for an
honest comparison of communication volume).

This module also owns the fleet-mesh primitives the sharded async engine
(``repro.engine.sharded``) is built on: ``fleet_mesh`` (a 1-D device mesh
over a ``fleet`` axis) and ``sharded_next_k_events`` — the O(devices * k)
buffer-pop merge (per-shard local top-k, an ``all_gather`` of the
``devices x k`` candidates, then a global merge) that replaces
materializing the full (n,) completion-time vector on one device.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core.aoi import age_update

# the engine's fleet-sharding axis name (1-D mesh over client shards)
FLEET_AXIS = "fleet"


def fleet_mesh(shards: int = 0, axis: str = FLEET_AXIS) -> Mesh:
    """1-D mesh of the first ``shards`` local devices over ``axis``
    (``shards=0`` takes every available device)."""
    devices = jax.devices()
    d = shards or len(devices)
    if d > len(devices):
        raise ValueError(
            f"requested {d} fleet shards but only {len(devices)} devices "
            "are available (on CPU, XLA_FLAGS="
            "--xla_force_host_platform_device_count=N makes N fake devices)"
        )
    return Mesh(np.asarray(devices[:d]), (axis,))


def cohort_padding(b: int, shards: int) -> int:
    """Zero-weight slots appended to a ``b``-wide cohort so its axis
    divides a ``shards``-device mesh — the cohort-parallel execution mode
    shards the padded axis evenly and the padding slots carry weight 0
    (they never touch the aggregate, the telemetry, or the event state,
    which masks them exactly like invalid buffer slots)."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    return -b % shards


def resolve_fleet_shards(n: int, shards: int, available: int) -> int:
    """Shard count for an ``n``-client fleet: ``shards`` when explicit
    (must divide ``n`` so every device owns an equal client block), else
    the largest divisor of ``n`` at most ``available`` — auto-detection
    never fails, it just leaves devices idle for awkward fleet sizes."""
    if shards:
        if n % shards:
            raise ValueError(
                f"n_clients={n} is not divisible by mesh_shards={shards}; "
                "pick a shard count dividing the fleet (or 0 to auto-detect)"
            )
        return shards
    d = max(min(available, n), 1)
    while n % d:
        d -= 1
    return d


def markov_step_sharded(
    mesh: Mesh,
    axis: str,
    probs: jnp.ndarray,
    m: int,
):
    """Returns a jit'able f(ages, round_idx, seed) -> (selected, new_ages, count).

    ``ages`` is sharded over ``axis``; decisions are computed purely locally
    (decentralized), only the cohort count is psum'd.
    """
    spec = P(axis)

    def local(ages, round_idx, seed):
        di = jax.lax.axis_index(axis)
        key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), seed), di)
        key = jax.random.fold_in(key, round_idx)
        chain = jnp.minimum(ages, m)
        send_p = probs[chain]
        sel = jax.random.uniform(key, ages.shape) < send_p
        new_ages = age_update(ages, sel)
        count = jax.lax.psum(jnp.sum(sel.astype(jnp.int32)), axis)
        return sel, new_ages, count

    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, P(), P()),
        out_specs=(spec, spec, P()),
    )
    return jax.jit(f)


def oldest_age_step_sharded(mesh: Mesh, axis: str, k: int):
    """Centralized oldest-age at fleet scale: per-shard local top-k then a
    global top-k over the gathered per-shard candidates (communication
    O(devices * k), vs O(1) for the Markov policy — this asymmetry is the
    paper's decentralization argument, made concrete).

    Ties break toward the lower *global* client index, deterministically,
    matching the contract of ``sim/events.py``: ``lax.top_k`` is stable
    (equal scores surface the lower local index first) and the gathered
    candidate list is ordered by shard, so the flat merge prefers lower
    shards — i.e. lower global ids — among equal ages. No RNG is involved.
    """
    spec = P(axis)

    def local(ages):
        di = jax.lax.axis_index(axis)
        kk = min(k, ages.shape[0])
        top_v, top_i = jax.lax.top_k(ages, kk)
        # global offset of this shard
        base = di * ages.shape[0]
        cand_v = jax.lax.all_gather(top_v, axis)  # (devices, kk)
        cand_i = jax.lax.all_gather(top_i + base, axis)
        flat_v = cand_v.reshape(-1)
        flat_i = cand_i.reshape(-1)
        _, sel_pos = jax.lax.top_k(flat_v, k)
        chosen = flat_i[sel_pos]  # (k,) global ids, replicated
        # local selection mask
        local_ids = base + jnp.arange(ages.shape[0])
        sel = jnp.isin(local_ids, chosen)
        new_ages = age_update(ages, sel)
        return sel, new_ages, chosen

    # ``chosen`` is replicated by construction (every device merges the
    # same gathered candidates), which the static checker can't infer
    f = shard_map(
        local,
        mesh=mesh,
        in_specs=(spec,),
        out_specs=(spec, spec, P()),
        check_vma=False,
    )
    return jax.jit(f)


def sharded_next_k_events(
    mesh: Mesh, n: int, k: int, axis: str = FLEET_AXIS
) -> Callable:
    """The sharded buffer pop: ``f(times (n,)) -> (t (k,), idx (k,))``,
    bit-identical (values, indices, and tie order) to a global
    ``lax.top_k(-times, k)`` over the full fleet.

    Each shard extracts its local k earliest events with a stable local
    top-k, the ``devices x k`` candidates are ``all_gather``-ed, and one
    merge picks the global k — O(devices * k) communication per step
    instead of materializing the (n,) completion-time vector on a single
    device. Tie order is preserved for free: candidates arrive ordered by
    (shard, local rank), both orderings ascending in global index, and
    ``lax.top_k`` stability does the rest.

    Fleets with ``n % devices != 0`` are padded with ``+inf`` sentinels up
    to the next multiple (a padded slot can only surface as an *invalid*
    pop — callers already mask by ``jnp.isfinite``). Returns a function to
    be called under ``jit``; ``k <= n`` as everywhere in the event engine.
    """
    devices = mesh.shape[axis]
    n_pad = -(-n // devices) * devices
    spec = P(axis)

    def local(times):  # (n_pad / devices,)
        di = jax.lax.axis_index(axis)
        shard = times.shape[0]
        kk = min(k, shard)
        neg_v, loc_i = jax.lax.top_k(-times, kk)
        base = di * shard
        cand_v = jax.lax.all_gather(neg_v, axis)  # (devices, kk)
        cand_i = jax.lax.all_gather(loc_i + base, axis)
        # k <= n <= devices * kk: the merge always has enough candidates
        top_v, pos = jax.lax.top_k(cand_v.reshape(-1), k)
        return -top_v, cand_i.reshape(-1)[pos]

    # outputs are replicated by construction (every device merges the same
    # gathered candidates); the static replication checker can't see that
    # through the gather + indexing, hence check_vma=False
    merge = shard_map(
        local, mesh=mesh, in_specs=(spec,), out_specs=(P(), P()),
        check_vma=False,
    )

    def next_k(times):
        if n_pad != n:
            times = jnp.concatenate(
                [times, jnp.full((n_pad - n,), jnp.inf, times.dtype)]
            )
        times = jax.lax.with_sharding_constraint(
            times, NamedSharding(mesh, spec)
        )
        return merge(times)

    return next_k


def scheduler_comm_bytes(n: int, k: int, devices: int) -> Tuple[int, int]:
    """(markov, oldest_age) per-round scheduler communication in bytes —
    the decentralization win, quantified."""
    markov = 4  # one int32 psum
    oldest = devices * k * 8  # gathered (value, index) candidates
    return markov, oldest

"""Tier reductions and per-hop latency: the jnp half of ``repro.topo``.

``tiered_apply`` turns a :class:`~repro.topo.graph.Topology` into the
engines' ``aggregate(global_params, updates, bases, w, idx) ->
(params, stats)`` hook. It is pure *reduction structure* over the existing aggregator
protocol — no new aggregator math:

  1. every cohort slot becomes its own additive accumulator
     (``agg.init`` is the zero element, so a one-slot ``accumulate``
     is exact);
  2. slot accumulators ``segment_sum`` into their tier-0 node by the
     topology's client assignment — the edge aggregation;
  3. each tier's node accumulators ``segment_sum`` up the parent maps
     (regional aggregation), and the top tier sums into the implicit
     global root — or, for gossip graphs, the flat peer tier mixes
     accumulators through the doubly stochastic ring matrix for
     ``gossip_rounds`` rounds and the global model reads node 0's view;
  4. one ``agg.finalize`` on the merged accumulator.

Because each merge is a plain leaf-wise sum of accumulators, the whole
tree costs O(params) traffic per cross-tier edge and requires
``agg.additive`` — exactly the contract ``cohort_sharded_apply``
established. Under cohort-parallel execution (``mesh`` given) steps 1-2
run inside a ``shard_map`` over the sharded cohort axis and the per-node
accumulator merges with one ``psum`` — the identical
shard-local-accumulate + psum path, just keyed by tier-0 node instead of
a single server, so the hierarchical reduction compiles to the same
cross-device pattern the star does.

``make_hop_latency`` prices the DAG: an update pays one latency draw per
cross-tier hop (client->tier0 per client from ``tier_profiles[0]``, then
one draw per *aggregation node* per upper hop — clients under the same
edge node share that node's uplink draw; gossip peers pay their link
once per gossip round). The (n,) extra wall time adds onto the client's
own dispatch latency in the async engine under a dedicated key fold.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.aggregators import Aggregator, acc_stats
from repro.sim import latency as lat_mod
from repro.topo.graph import Topology


def _segment_sum_tree(tree, seg, num_segments: int):
    return jax.tree.map(
        lambda a: jax.ops.segment_sum(a, seg, num_segments=num_segments),
        tree,
    )


def _slot_accums(agg: Aggregator, g, updates, bases, w, stacked_bases: bool):
    """(B,)-stacked per-slot accumulators: each cohort slot accumulated
    alone into the zero element (exact because the aggregator is
    additive)."""
    zero = agg.init(g)

    def lift(t):
        return jax.tree.map(lambda x: x[None], t)

    if stacked_bases:
        def one(u, b, wi):
            return agg.accumulate(zero, lift(u), lift(b), wi[None])

        return jax.vmap(one)(updates, bases, w)

    # sync convention: bases is the unstacked global tree, broadcast
    def one(u, wi):
        return agg.accumulate(zero, lift(u), bases, wi[None])

    return jax.vmap(one)(updates, w)


def tiered_apply(
    agg: Aggregator,
    topo: Topology,
    n_clients: int,
    mesh=None,
    axis: Optional[str] = None,
    stacked_bases: bool = True,
):
    """Build the tiered ``aggregate(g, updates, bases, w, idx)`` hook.

    ``idx`` is the (B,) cohort -> client index map the engines already
    hold; padded/invalid slots carry weight 0 and contribute the zero
    accumulator, exactly like an under-filled buffer. With ``mesh``/
    ``axis`` the slot accumulation and the tier-0 segment sum run
    shard-locally over the cohort axis and merge with one psum
    (requires the cohort length, after engine padding, to divide the
    mesh — the same contract as ``cohort_sharded_apply``).
    """
    if topo.is_star:
        raise ValueError(
            f"topology {topo.name!r} is a star: engines use the plain "
            "aggregator path (bit-for-bit identical), not tiered_apply"
        )
    if not agg.additive:
        raise ValueError(
            f"aggregator {agg.name!r} is not additive: tier reductions "
            "are accumulator merges, so non-additive aggregators cannot "
            "run under a multi-tier topology"
        )
    assign_dev = jnp.asarray(topo.assign(n_clients))
    parents_dev = [jnp.asarray(p) for p in topo.parents()]
    e0 = int(topo.tier_sizes[0])
    mix = (
        jnp.asarray(topo.gossip_mixing()) if topo.kind == "gossip" else None
    )

    def local_tier0(g, updates, bases, w, seg):
        accs = _slot_accums(agg, g, updates, bases, w, stacked_bases)
        return _segment_sum_tree(accs, seg, e0)

    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        spec = P(axis)

        def tier0(g, updates, bases, w, seg):
            def local(g_l, u_l, b_l, w_l, s_l):
                return jax.lax.psum(
                    local_tier0(g_l, u_l, b_l, w_l, s_l), axis
                )

            return shard_map(
                local,
                mesh=mesh,
                in_specs=(P(), spec, spec if stacked_bases else P(), spec,
                          spec),
                out_specs=P(),
            )(g, updates, bases, w, seg)
    else:
        tier0 = local_tier0

    def apply(g, updates, bases, w, idx):
        acc = tier0(g, updates, bases, w, assign_dev[idx])
        for pmap, size in zip(parents_dev, topo.tier_sizes[1:]):
            acc = _segment_sum_tree(acc, pmap, int(size))
        if mix is not None:
            for _ in range(topo.gossip_rounds):
                acc = jax.tree.map(
                    lambda a: jnp.tensordot(
                        mix, a, axes=(1, 0)
                    ).astype(a.dtype),
                    acc,
                )
            # node 0's decentralized estimate of the network sum: the
            # doubly stochastic mixing preserves the total, so as rounds
            # grow every node's view -> (sum / E) and the x E readout
            # converges to the hierarchical reduction (finalize ratios
            # are scale-invariant for the built-in aggregators anyway)
            acc = jax.tree.map(lambda a: a[0] * e0, acc)
        else:
            acc = jax.tree.map(lambda a: a.sum(axis=0), acc)
        return agg.finalize(g, acc), acc_stats(acc)

    return apply


def tier_suspect_counts(topo: Topology, n_clients: int, status) -> list:
    """Host-side per-edge-node suspect census for run telemetry.

    Buckets the defense tier's final per-client status (non-zero =
    quarantined or on probation) by the topology's tier-0 assignment, so
    operators can see *where* in the aggregation DAG the flagged clients
    sit. Star topologies have one implicit edge node — the whole fleet
    buckets into it."""
    suspect = (np.asarray(status) != 0).astype(np.float64)
    if topo.is_star:
        return [float(suspect.sum())]
    assign = np.asarray(topo.assign(n_clients))
    counts = np.bincount(
        assign, weights=suspect, minlength=int(topo.tier_sizes[0])
    )
    return [float(c) for c in counts]


def make_hop_latency(topo: Topology, n_clients: int):
    """Per-client extra wall time through the aggregation DAG.

    Returns ``hop(key) -> (n,) f32`` (or None for a star — no extra
    hops): one draw per client for the client->tier0 link, then one draw
    per *aggregation node* for each upper hop, gathered down to the
    clients through the assignment maps — clients under the same edge
    node share its uplink draw. Gossip peers pay their link profile once
    per gossip round. Profiles default to ``datacenter`` when the
    topology names none.
    """
    if topo.is_star:
        return None
    hops = topo.n_tiers + 1
    names = topo.tier_profiles or ("datacenter",) * hops
    profs = [lat_mod.get_profile(p) for p in names]
    assign = jnp.asarray(topo.assign(n_clients))
    parents = [jnp.asarray(p) for p in topo.parents()]
    sizes = [int(s) for s in topo.tier_sizes]
    n_links = max(topo.gossip_rounds, 1) if topo.kind == "gossip" else 1

    def hop(key):
        keys = jax.random.split(key, hops + n_links - 1)
        ones_n = jnp.ones((n_clients,), jnp.float32)
        extra = lat_mod.sample_latency(keys[0], profs[0], ones_n)
        node = assign
        for lvl, size in enumerate(sizes):
            ones_e = jnp.ones((size,), jnp.float32)
            if topo.kind == "gossip":
                draw = jnp.zeros((size,), jnp.float32)
                for rr in range(topo.gossip_rounds):
                    draw = draw + lat_mod.sample_latency(
                        keys[1 + rr], profs[1], ones_e
                    )
            else:
                draw = lat_mod.sample_latency(
                    keys[1 + lvl], profs[1 + lvl], ones_e
                )
            extra = extra + draw[node]
            if lvl < len(parents):
                node = parents[lvl][node]
        return extra

    return hop

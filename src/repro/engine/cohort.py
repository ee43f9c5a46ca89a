"""The cohort stage both engines run, from the cohort's data to new params.

An engine picks a cohort (the sync round's packed selection, the async
step's popped buffer) and hands it here with the params each slot trains
from. This module owns everything from there to the new global params:

  * the client-data layout (``client_rows`` / ``gather_rows``);
  * local training: one vmap over every slot, or, for a packed,
    variable-size cohort on one device, the group loop
    (``_train_groups``) over the groups that hold a client;
  * the opt-in tiers' cohort seams: fault corruption then collusion,
    ``defense.observe``, the collusion weight scale;
  * ``agg.weigh`` and the aggregate hook (``make_aggregate``: inline,
    tiered, cohort-sharded or both), wrapped in the moving-target ladder
    when the defense arms it;
  * the tiers' end-of-run reports (``run_result``).

Key folds off the step's ``k_sel``: 105 for faults (sub-fold 1 for the
pop coins, 2 for corruption noise), 108 for the defense. A tier that is
not armed adds no state, no fold and no op.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.load_metric import (
    empirical_load_stats,
    selection_stats_from_accum,
    tier_stats_from_accum,
)
from repro.defense.adaptive import adaptive_aggregate
from repro.engine.aggregators import acc_stats, cohort_sharded_apply
from repro.engine.config import RunResult
from repro.faults.inject import collude_updates, corrupt_updates, effects_hit
from repro.fl.client import make_local_update
from repro.topo.reduce import tier_suspect_counts, tiered_apply


def client_rows(data):
    """``data`` with each leaf of more than two axes held as rows,
    ``(n_clients, prod(rest))``; leaves of one or two axes as they are.

    A leaf whose per-client axes end in a small one (28x28x1 images) gets a
    client-minor device layout, and a per-step gather of a few clients from
    it then relays out the whole fleet's examples inside the step loop.
    Rows keep the client axis major, so the gather reads whole rows. Shapes
    (``jax.ShapeDtypeStruct``) map to shapes, for compiling without data.
    """
    def rows(a):
        if a.ndim <= 2:
            return a
        shape = (a.shape[0], math.prod(a.shape[1:]))
        if isinstance(a, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(shape, a.dtype,
                                        sharding=a.sharding)
        return jnp.reshape(a, shape)

    return jax.tree.map(rows, data)


def gather_rows(rows, idx, like):
    """The clients ``idx`` of ``rows`` (``client_rows(like)``, or ``like``'s
    own layout) in ``like``'s per-client shapes: ``a[idx]`` for each leaf
    ``a`` of ``like``, value for value."""
    return jax.tree.map(
        lambda r, a: r[idx].reshape(idx.shape + a.shape[1:]), rows, like)


def _group_size(width: int) -> int:
    """Slots per pass of the cohort group loop, about sqrt(width): of the
    sizes from half of isqrt(width) up to it, the one whose passes pad the
    width least, the largest on a tie (30 -> 5, 12 -> 3, 23 -> 4 and 29 -> 5
    with one padding slot, where a divisor would be 1)."""
    top = math.isqrt(width)
    return min(range((top + 1) // 2, top + 1),
               key=lambda g: (-(-width // g) * g, -g))


def _train_groups(local_update, params, data, like, idx, keys, lr, count,
                  group):
    """Local training of the packed cohort, ``group`` slots at a time.

    ``idx`` and ``keys`` are the cohort's ``width`` slots, the ``count``
    selected clients packed into a prefix (``cohort_indices``); where
    ``group`` does not divide ``width`` they are padded to whole groups
    here, and what the padding slots train is dropped. A
    ``lax.fori_loop`` with a dynamic trip count trains the groups that
    hold a selected client, ceil(count / group) of them: each pass slices
    ``group`` entries of ``idx`` and ``keys``, gathers only those
    clients' examples (``gather_rows`` of ``data`` in ``like``'s shapes)
    and runs ``vmap(local_update)`` over them, so every selected slot
    computes exactly what one full-width vmap computes for it. Slots of
    untrained groups keep ``params`` (a zero update) and loss 0; the
    caller gives them weight 0.

    Returns ``(updated, losses, trained)``: the (width, ...) params, the
    (width,) losses and the slots trained, ``group * ceil(count / group)``.
    """
    width = idx.shape[0]
    pad = -width % group
    if pad:
        # real slots keep their exact keys
        idx = jnp.concatenate([idx, jnp.zeros((pad,), idx.dtype)])
        keys = jnp.concatenate([keys, keys[:pad]])
    train = jax.vmap(local_update, in_axes=(None, 0, 0, None))

    def spec(a):
        return jax.ShapeDtypeStruct((group,) + a.shape[1:], a.dtype)

    loss_dtype = jax.eval_shape(train, params, jax.tree.map(spec, like),
                                spec(keys), lr)[1].dtype
    init = (
        jax.tree.map(lambda p: jnp.broadcast_to(p, (width + pad,) + p.shape),
                     params),
        jnp.zeros((width + pad,), loss_dtype),
    )

    def body(g, carry):
        updated, losses = carry
        start = g * group
        sl = jax.lax.dynamic_slice_in_dim(idx, start, group)
        up, loss = train(params, gather_rows(data, sl, like),
                         jax.lax.dynamic_slice_in_dim(keys, start, group), lr)
        updated = jax.tree.map(
            lambda u, x: jax.lax.dynamic_update_slice_in_dim(u, x, start, 0),
            updated, up)
        return updated, jax.lax.dynamic_update_slice_in_dim(losses, loss,
                                                            start, 0)

    n_groups = (count + group - 1) // group
    updated, losses = jax.lax.fori_loop(0, n_groups, body, init)
    if pad:
        updated, losses = jax.tree.map(lambda a: a[:width], (updated, losses))
    return updated, losses, n_groups * group


def cohort_layout(mesh, axis):
    """The layout hook for cohort-sized intermediates on ``mesh``: laid out
    over ``axis`` (the cohort-parallel mode), or replicated where ``axis``
    is None (the bit-exact fleet-sharded engine, whose reduction order
    then cannot drift from one device's)."""
    sharding = NamedSharding(mesh, P(axis) if axis else P())

    def layout(tree):
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, sharding), tree)

    return layout


def make_aggregate(agg, topo, n_clients, mesh=None, axis=None,
                   stacked_bases=True):
    """The aggregate hook ``aggregate(g, updates, bases, w, idx) ->
    (params, stats)``: the tiered reduction under a multi-tier ``topo``,
    the shard-local accumulation merged by one psum over ``axis`` of a
    cohort ``mesh`` (both, when both are given), else the inline
    ``init/accumulate/finalize`` chain. ``stacked_bases=False`` is for a
    cohort that trained from one unstacked tree (the sync round)."""
    if topo is not None and not topo.is_star:
        return tiered_apply(agg, topo, n_clients, mesh=mesh, axis=axis,
                            stacked_bases=stacked_bases)
    if mesh is not None:
        return cohort_sharded_apply(agg, mesh, axis,
                                    stacked_bases=stacked_bases)

    def aggregate(g, updates, bases, w, idx=None):
        acc = agg.accumulate(agg.init(g), updates, bases, w)
        return agg.finalize(g, acc), acc_stats(acc)

    return aggregate


class CohortOut(NamedTuple):
    params: object  # the new global params
    losses: jnp.ndarray  # (B,) per-slot local training losses
    weights: jnp.ndarray  # (B,) aggregation weights
    arrived: jnp.ndarray  # (B,) the arrived mask less defense suspects
    dstate: object  # the defense state (None when disarmed)
    stats: dict  # the aggregator's telemetry
    trained: jnp.ndarray  # slots that ran local training


class CohortStage:
    """Local training through aggregation for one engine's cohorts.

    ``aggregate`` is a ``make_aggregate`` hook (default: the inline
    chain); ``layout`` lays out every cohort-sized intermediate (default:
    as it is). ``packed`` says the engine packs a variable number of
    clients into a prefix of the cohort: without a ``layout`` such a
    cohort trains through the group loop, ``_group_size(width)`` slots a
    pass; any other cohort trains in one vmap over every slot.
    """

    def __init__(self, task, cfg, agg, width, aggregate=None, layout=None,
                 packed=False, faults=None, defense=None):
        self.agg = agg
        self.like = task.client_data
        self.local_update = make_local_update(
            task.loss_fn, cfg.local_epochs, cfg.batch_size,
            task.examples_per_client,
        )
        self.group = _group_size(width) if packed and layout is None else 0
        self.layout = layout or (lambda tree: tree)
        self.aggregate = aggregate or make_aggregate(agg, None, cfg.n_clients)
        self.faults = faults
        self.defense = defense
        self.pop_on = pops = faults is not None and faults.has_pop
        self.kill_on = pops and faults.has("kill")
        self.corrupt_on = pops and (faults.has("scale") or faults.has("noise"))
        self.collude_on = pops and faults.has("collude")
        self.col_on = defense is not None and defense.collusion
        # supervised labels for the learned detector head: only when the
        # run opted into exposure ground truth and some fault pops
        self.sup_on = (defense is not None and defense.wants_labels and pops
                       and cfg.fault_exposure)
        self.mtd = None
        if defense is not None and defense.mtd:
            # config rejects mtd under tiered or cohort-sharded
            # aggregation, so the wrapped hook is the inline (or bit-exact
            # sharded) one; level 0 routes through it untouched
            self.mtd = adaptive_aggregate(self.aggregate,
                                          defense.cfg.mtd_trims,
                                          families=defense.cfg.mtd_families)

    def on_pop(self, fstate, k_sel, idx, valid):
        """The faults' per-slot coins over the cohort (fold 105, sub-fold
        1): ``(fstate, effects)``, effects None where no fault pops."""
        if not self.pop_on:
            return fstate, None
        fstate, eff = self.faults.on_pop(
            fstate, jax.random.fold_in(jax.random.fold_in(k_sel, 105), 1),
            idx, valid,
        )
        return fstate, self.layout(eff)

    def delivered(self, mask, eff):
        """``mask`` less the slots whose update a dropout fault killed on
        the way to the server."""
        return mask & ~eff.kill if self.kill_on else mask

    def __call__(self, params, bases, data, idx, keys, lr, count, arrived,
                 staleness, eff=None, dstate=None, k_sel=None, ages=None):
        """One cohort from local training to the new global ``params``.

        ``bases`` is what each slot trains from: ``params`` itself (one
        tree, broadcast lazily over the cohort) or a per-slot stack. ``lr``
        is a scalar or per-slot. ``count`` is the packed cohort's size
        (read by the group loop only). ``arrived`` masks the slots whose
        update reached the server, ``staleness`` their version lag;
        ``ages`` is the fleet's (n,) age vector the defense scores with
        (or None). Returns a ``CohortOut``.
        """
        layout = self.layout
        with jax.named_scope("local_train"):
            if self.group:
                updated, losses, trained = _train_groups(
                    self.local_update, bases, data, self.like, idx, keys, lr,
                    count, self.group,
                )
            else:
                stacked = (jax.tree.leaves(bases)[0].ndim
                           > jax.tree.leaves(params)[0].ndim)
                shards = layout(gather_rows(data, idx, self.like))
                # an unstacked base is a lazy vmap broadcast: no (B, ...)
                # copies; aggregators broadcast it in their deltas
                updated, losses = layout(jax.vmap(
                    self.local_update,
                    in_axes=(0 if stacked else None, 0, 0,
                             0 if jnp.ndim(lr) else None),
                )(bases, shards, keys, lr))
                trained = jnp.asarray(idx.shape[0], jnp.int32)
            if self.corrupt_on:
                # fold 105/2: corruption noise. Missed slots keep their
                # exact input buffers, so a rate-0 set is bitwise identity
                updated = corrupt_updates(
                    updated, bases, eff,
                    jax.random.fold_in(jax.random.fold_in(k_sel, 105), 2),
                    self.faults.has("scale"), self.faults.has("noise"),
                )
            if self.collude_on:
                # after corrupt: a coalition member's replacement is
                # authoritative over any scale/noise it also drew. Keyless —
                # the direction is a trace-time constant, the jitter rode
                # the fault's own pop fold
                updated = collude_updates(updated, bases, eff)
        with jax.named_scope("aggregate"):
            if self.defense is not None:
                # fold 108: the defense tier's dedicated key (sub-folds
                # 0 probation / 1 readmit coins). Every update that arrived
                # is scored — including probation clients — then
                # post-transition suspects are excluded from the reduction
                # through the seam dark and killed slots use, closing the
                # detect->quarantine loop within the step
                dstate, suspect, w_scale = self.defense.observe(
                    dstate, jax.random.fold_in(k_sel, 108),
                    updated, bases, idx, arrived, staleness,
                    losses=losses,
                    ages=None if ages is None else layout(ages[idx]),
                    labels=layout(effects_hit(eff)) if self.sup_on else None,
                )
                arrived = arrived & ~layout(suspect[idx])
            w = self.agg.weigh(arrived, staleness)
            if self.col_on:
                # clique members keep a (discounted) vote rather than a
                # binary exclusion: w_scale is exact 1.0 on clique-free
                # slots, so a calm armed step multiplies by ones
                w = w * w_scale
            if self.mtd is not None:
                params, stats = self.mtd(params, updated, bases, w, idx,
                                         dstate["level"])
            else:
                params, stats = self.aggregate(params, updated, bases, w, idx)
        return CohortOut(params, losses, w, arrived, dstate, stats, trained)


def run_result(engine, state, records, sel_hist, wall_time_s, stats,
               wall_stats=None) -> RunResult:
    """The ``RunResult`` of a finished run: the load metric (from the
    selection history, else the device accumulators), the engine's own
    ``stats``, then the reports of the armed tiers (per-tier load, fault
    counters, the defense report with its per-tier suspects, and the
    per-client fault exposure and defense arrays)."""
    cfg = engine.cfg
    if sel_hist is not None:
        load_stats = dict(empirical_load_stats(sel_hist))
    else:
        load_stats = dict(selection_stats_from_accum(state["load_acc"]))
    load_stats.update(stats)
    if "tier_acc" in state:
        load_stats.update(tier_stats_from_accum(state["tier_acc"]))
    if "faults" in state:
        for nm, cnt in engine.fault_set.counters(state["faults"]).items():
            load_stats[f"fault_{nm}_injected"] = cnt
    defense = None
    if "defense" in state:
        load_stats.update(engine.defense.report(state["defense"]))
        if "tier_acc" in state:
            load_stats["tier_suspects"] = tier_suspect_counts(
                engine.topo, cfg.n_clients, state["defense"]["status"])
        defense = engine.defense.arrays(state["defense"])
    fault_exposure = None
    if "faults" in state and cfg.fault_exposure:
        fault_exposure = engine.fault_set.exposure(state["faults"])
    return RunResult(
        config=cfg,
        records=records,
        selection=sel_hist,
        load_stats=load_stats,
        wall_stats=wall_stats,
        params=state["params"],
        wall_time_s=wall_time_s,
        fault_exposure=fault_exposure,
        defense=defense,
    )

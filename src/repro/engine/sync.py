"""The synchronous FedAvg engine.

One step = policy step -> cohort gather -> vmapped local training (on
one device, only the groups of slots that hold a selected client) ->
aggregator ``weigh/init/accumulate/finalize`` -> age update. This is the
round loop of ``fl/rounds.py`` re-expressed against the ``Engine``
protocol (`init/step/run_chunk/finalize`) with the aggregation seam
opened up: the default ``fedavg`` aggregator reproduces the pre-refactor
weighted cohort mean bit-for-bit (pinned by
``tests/test_engine_equivalence.py``), while delta-based aggregators
(``fedprox``) drop in without touching this file.

The hot loop runs through ``ChunkRunner``: ``steps_per_chunk`` rounds per
host dispatch via a donated ``lax.scan``, with the selection-gap load
accumulators updated on device (``tests/test_engine_chunked.py`` pins
chunked == per-step bit-for-bit). The cohort vmap broadcasts the global
params lazily (``in_axes=(None, ...)``) and aggregators receive the
unstacked global tree as ``bases``; the group loop's (width, ...) output
starts as that broadcast, so untrained slots are a zero update.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.load_metric import (
    empirical_load_stats,
    init_selection_accum,
    init_tier_accum,
    selection_stats_from_accum,
    tier_stats_from_accum,
    update_tier_accum,
)
from repro.core.selection import Policy
from repro.engine.aggregators import Aggregator
from repro.engine.chunk import ChunkRunner, dealias_pytree, run_key, step_once
from repro.engine.config import RoundRecord, RunConfig, RunResult
from repro.engine.registry import make_aggregator, make_policy
from repro.fl.client import make_local_update
from repro.fl.server import cohort_indices
from repro.fl.task import FLTask
from repro.optim.schedules import exponential_decay


class SyncEngine:
    """Synchronous rounds: every selected client trains from the current
    global params and the buffer is flushed once per round."""

    def __init__(
        self,
        task: FLTask,
        cfg: RunConfig,
        policy: Optional[Policy] = None,
        aggregator: Optional[Aggregator] = None,
    ):
        if cfg.mode != "sync":
            raise ValueError(f"SyncEngine needs mode='sync', got {cfg.mode!r}")
        self.task = task
        self.cfg = cfg
        self.policy = policy or make_policy(
            cfg.policy, cfg.n_clients, cfg.k, cfg.m, **dict(cfg.policy_kwargs)
        )
        self.aggregator = aggregator or make_aggregator(
            cfg.resolved_aggregator(), **dict(cfg.aggregator_kwargs)
        )
        self.topo = cfg.resolved_topology()
        if self.topo is not None and self.topo.heartbeat_timeout > 0:
            raise ValueError(
                "heartbeat churn is wall-clock-based and needs the async "
                "engine's event clock; sync rounds have no mid-round time "
                "for a client to go dark in — drop heartbeat_timeout or "
                "use mode='async'"
            )
        self.fault_set = cfg.resolved_faults()
        if self.fault_set is not None:
            only = self.fault_set.async_only_names()
            if only:
                raise ValueError(
                    f"fault(s) {', '.join(only)} act on the async engine's "
                    "wall clock / version ring; sync rounds have neither — "
                    "drop them or use mode='async'"
                )
        self.defense_cfg = cfg.resolved_defense()
        if self.defense_cfg is not None:
            from repro.defense import make_defense

            self.defense = make_defense(cfg.n_clients, self.defense_cfg)
        else:
            self.defense = None
        tiered = self.topo is not None and not self.topo.is_star
        self._assign = (
            jnp.asarray(self.topo.assign(cfg.n_clients)) if tiered else None
        )
        self._sharded_eval = None
        if cfg.shard_cohort:
            # cohort-parallel sync rounds: the cohort vmap (and the
            # aggregator accumulation) partitions over a device mesh —
            # sync has no per-client device state, so the mesh shards the
            # *cohort* axis only. mesh_shards=0 takes every local device.
            from repro.core import distributed as dist
            from repro.engine.aggregators import cohort_sharded_apply
            from repro.engine.sharded import (
                make_sharded_eval,
                require_cohort_mesh,
            )

            shards = cfg.mesh_shards or len(jax.devices())
            require_cohort_mesh(shards, f"mesh_shards={cfg.mesh_shards}")
            self.mesh = dist.fleet_mesh(shards, dist.FLEET_AXIS)
            self.mesh_shards = shards
            from jax.sharding import NamedSharding, PartitionSpec as P

            cohort = NamedSharding(self.mesh, P(dist.FLEET_AXIS))

            def cohort_layout(tree):
                return jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(x, cohort),
                    tree,
                )

            if tiered:
                # the tiered reduction under the sharded cohort: slot
                # accumulation + the tier-0 segment sum run shard-locally
                # and merge with the same one-psum pattern
                from repro.topo.reduce import tiered_apply

                aggregate = tiered_apply(
                    self.aggregator, self.topo, cfg.n_clients,
                    mesh=self.mesh, axis=dist.FLEET_AXIS,
                    stacked_bases=False,
                )
            else:
                # sync passes the unstacked global tree as bases
                aggregate = cohort_sharded_apply(
                    self.aggregator, self.mesh, dist.FLEET_AXIS,
                    stacked_bases=False,
                )
            core = _make_round_core(
                task, cfg, self.policy, self.aggregator,
                cohort_layout=cohort_layout,
                aggregate=aggregate,
                cohort_shards=shards,
                faults=self.fault_set,
                defense=self.defense,
            )
            self._sharded_eval = make_sharded_eval(
                task, self.mesh, dist.FLEET_AXIS
            )
        elif tiered:
            from repro.topo.reduce import tiered_apply

            core = _make_round_core(
                task, cfg, self.policy, self.aggregator,
                aggregate=tiered_apply(
                    self.aggregator, self.topo, cfg.n_clients,
                    stacked_bases=False,
                ),
                faults=self.fault_set,
                defense=self.defense,
            )
        else:
            core = _make_round_core(task, cfg, self.policy, self.aggregator,
                                    faults=self.fault_set,
                                    defense=self.defense)

        assign = self._assign
        have_faults = self.fault_set is not None
        have_def = self.defense is not None
        stat_names = self.aggregator.stat_names

        def scan_step(state, key, data):
            params, sched, selected, loss, slots, fstate, dstate, tel = core(
                state["params"], state["sched"], key, data,
                state["faults"] if have_faults else None,
                state["defense"] if have_def else None,
            )
            out = {"params": params, "sched": sched}
            if assign is not None:
                with jax.named_scope("load_metric"):
                    out["tier_acc"] = update_tier_accum(
                        state["tier_acc"], selected, assign
                    )
            if have_faults:
                out["faults"] = fstate
            if have_def:
                out["defense"] = dstate
            if stat_names:
                with jax.named_scope("aggregate"):
                    out["agg_stats"] = {
                        s: state["agg_stats"][s] + tel[s] for s in stat_names
                    }
            return out, {"send": selected, "loss": loss,
                         "trained_slots": slots}

        self._chunk = ChunkRunner(scan_step,
                                  aux_keys=("loss", "trained_slots"),
                                  data=task.client_data)

    def init(self) -> Dict:
        cfg = self.cfg
        key = run_key(cfg.seed, cfg.rng_impl)
        k_init, k_policy, k_run = jax.random.split(key, 3)
        # donation-safe from the start: step() routes through the donated
        # chunk runner even for single steps
        state = {
            "params": self.task.init(k_init),
            "sched": self.policy.init(k_policy, cfg.n_clients),
            "k_run": k_run,
            "load_acc": init_selection_accum(cfg.n_clients, cfg.k),
        }
        if self._assign is not None:
            state["tier_acc"] = init_tier_accum(
                cfg.n_clients, int(self.topo.tier_sizes[0])
            )
        if self.fault_set is not None:
            # off the far end of the round-index fold range so fault-prone
            # draws never collide with a per-round fold_in(k_run, r)
            state["faults"] = self.fault_set.init(
                jax.random.fold_in(k_run, 2**31)
            )
        if self.defense is not None:
            state["defense"] = self.defense.init()  # deterministic zeros
        if self.aggregator.stat_names:
            state["agg_stats"] = {
                s: jnp.zeros((), jnp.float32)
                for s in self.aggregator.stat_names
            }
        return dealias_pytree(state)

    def step(self, state: Dict, r: int):
        return step_once(self._chunk, state, r)

    def run_chunk(self, state: Dict, r0: int, length: int, with_history: bool):
        return self._chunk(state, r0, length, with_history)

    def eval_params(self, state: Dict):
        return state["params"]

    def evaluate(self, state: Dict) -> Dict:
        if self._sharded_eval is not None:
            return self._sharded_eval(self.eval_params(state))
        return self.task.eval_fn(self.eval_params(state))

    def record(self, r: int, aux: Dict, ev: Dict) -> RoundRecord:
        return RoundRecord(
            round=r + 1,
            train_loss=float(aux["loss"]),
            eval_loss=float(ev["loss"]),
            accuracy=float(ev["accuracy"]),
            trained_slots=int(aux["trained_slots"]),
        )

    def progress_line(self, rec: RoundRecord, elapsed: float) -> str:
        tag = (
            f"/{self.topo.describe()}"
            if self.topo is not None and not self.topo.is_star else ""
        )
        return (
            f"  [{self.policy.name}{tag}] round {rec.round:4d} "
            f"acc={rec.accuracy:.4f} loss={rec.eval_loss:.4f} ({elapsed:.1f}s)"
        )

    def finalize(self, state, records, sel_hist, wall_time_s) -> RunResult:
        if sel_hist is not None:
            load_stats = empirical_load_stats(sel_hist)
        else:
            load_stats = selection_stats_from_accum(state["load_acc"])
        load_stats = dict(load_stats)
        if "tier_acc" in state:
            load_stats.update(tier_stats_from_accum(state["tier_acc"]))
        if "faults" in state:
            for nm, cnt in self.fault_set.counters(state["faults"]).items():
                load_stats[f"fault_{nm}_injected"] = cnt
        if "agg_stats" in state:
            for s in self.aggregator.stat_names:
                load_stats[f"agg_{s}"] = float(state["agg_stats"][s])
        if "defense" in state:
            load_stats.update(self.defense.report(state["defense"]))
            if "tier_acc" in state:
                from repro.topo.reduce import tier_suspect_counts

                load_stats["tier_suspects"] = tier_suspect_counts(
                    self.topo, self.cfg.n_clients,
                    state["defense"]["status"],
                )
        fault_exposure = None
        if "faults" in state and self.cfg.fault_exposure:
            fault_exposure = self.fault_set.exposure(state["faults"])
        return RunResult(
            config=self.cfg,
            records=records,
            selection=sel_hist,
            load_stats=load_stats,
            wall_stats=None,
            params=state["params"],
            wall_time_s=wall_time_s,
            fault_exposure=fault_exposure,
            defense=(self.defense.arrays(state["defense"])
                     if "defense" in state else None),
        )


def _group_size(width: int) -> int:
    """Slots per pass of the cohort group loop, about sqrt(width): of the
    sizes from half of isqrt(width) up to it, the one whose passes pad the
    width least, the largest on a tie (30 -> 5, 12 -> 3, 23 -> 4 and 29 -> 5
    with one padding slot, where a divisor would be 1)."""
    top = math.isqrt(width)
    return min(range((top + 1) // 2, top + 1),
               key=lambda g: (-(-width // g) * g, -g))


def _train_groups(local_update, params, data, idx, keys, lr, count, group):
    """Local training of the packed cohort, ``group`` slots at a time.

    ``idx`` and ``keys`` are the cohort's ``width`` slots, the ``count``
    selected clients packed into a prefix (``cohort_indices``); where
    ``group`` does not divide ``width`` they are padded to whole groups
    here, and what the padding slots train is dropped. A
    ``lax.fori_loop`` with a dynamic trip count trains the groups that
    hold a selected client, ceil(count / group) of them: each pass slices
    ``group`` entries of ``idx`` and ``keys``, gathers only those
    clients' examples and runs ``vmap(local_update)`` over them, so every
    selected slot computes exactly what one full-width vmap computes
    for it. Slots of untrained groups keep ``params`` (a zero update)
    and loss 0; the caller gives them weight 0.

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

    loss_dtype = jax.eval_shape(train, params, jax.tree.map(spec, data),
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
        up, loss = train(params, jax.tree.map(lambda a: a[sl], data),
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


def _make_round_core(task: FLTask, cfg: RunConfig, policy: Policy, agg: Aggregator,
                     cohort_layout=None, aggregate=None, cohort_shards: int = 1,
                     faults=None, defense=None):
    """The pure per-round function (no jit): shared by the legacy per-step
    path and the scan body of the chunked hot loop. It reads the clients'
    examples from its ``data`` argument (``task.client_data``), never
    from a closure, so no compiled round embeds them as constants.

    A variable-size policy's cohort is padded to ``cfg.cohort_width()``
    slots, about half of them empty under the Markov policy. On one
    device (no ``cohort_layout`` hook) local training therefore runs as a
    group loop (``_train_groups``) over the groups that hold a selected
    client, ``_group_size(width)`` slots each, and leaves the rest at a
    zero update with weight 0. Exact-k cohorts, which have no padding,
    and the cohort-sharded mesh, whose vmap is laid out over the mesh,
    train every slot in one vmap. The round returns the slots it trained
    as ``trained_slots``.

    The optional hooks are the cohort-parallel seam (mirroring
    ``_make_async_step``): ``cohort_layout`` lays the cohort-stacked
    intermediates out over the mesh, ``aggregate`` replaces the inline
    ``init/accumulate/finalize`` chain with the shard-local path, and
    ``cohort_shards`` pads the cohort axis with weight-0 slots to the
    next multiple of the mesh. Defaults reproduce the single-device
    round bit-for-bit.

    ``faults`` (a ``repro.faults.FaultSet``) threads per-client fault
    state through the round: fault keys fold off ``k_sel`` at 105 (the
    same schedule as the async engine — sub-fold 1 for ``on_pop``, 2 for
    update corruption), so with no faults armed no extra key material is
    drawn and the round is bit-for-bit the faultless one.

    ``defense`` (a ``repro.defense.Defense``) mirrors the async seams on
    the same fold schedule (108 off ``k_sel``): quarantined clients are
    masked out of ``selected`` right after the policy step (they still
    age — the policy's chain advanced; the defense vetoes the dispatch),
    every surviving slot is scored with staleness identically zero, and
    post-transition suspects lose their aggregation weight."""
    from repro.core.distributed import cohort_padding

    width = cfg.cohort_width() if not policy.exact_k else cfg.k
    cohort_pad = cohort_padding(width, cohort_shards)
    wp = width + cohort_pad
    # an exact-k cohort has no padding to skip: its single vmap stays,
    # whose train loss the pre-refactor golden tests pin to the bit
    grouped = not policy.exact_k and cohort_layout is None
    if grouped:
        group = _group_size(width)
    if cohort_layout is None:
        cohort_layout = lambda tree: tree  # noqa: E731
    if aggregate is None:
        from repro.engine.aggregators import acc_stats

        def aggregate(g, updates, bases, w, idx=None):
            acc = agg.accumulate(agg.init(g), updates, bases, w)
            return agg.finalize(g, acc), acc_stats(acc)
    have_faults = faults is not None
    have_def = defense is not None
    mtd_on = have_def and defense.mtd
    if mtd_on:
        from repro.defense.adaptive import adaptive_aggregate

        aggregate_mtd = adaptive_aggregate(aggregate, defense.cfg.mtd_trims,
                                           families=defense.cfg.mtd_families)
    kill_on = have_faults and faults.has("kill")
    corrupt_on = have_faults and (faults.has("scale") or faults.has("noise"))
    if corrupt_on:
        from repro.faults.inject import corrupt_updates
    collude_on = have_faults and faults.has("collude")
    if collude_on:
        from repro.faults.inject import collude_updates
    col_on = have_def and defense.collusion
    sup_on = (have_def and defense.wants_labels and have_faults
              and faults.has_pop and cfg.fault_exposure)
    if sup_on:
        from repro.faults.inject import effects_hit
    local_update = make_local_update(
        task.loss_fn, cfg.local_epochs, cfg.batch_size, task.examples_per_client
    )
    lr_fn = exponential_decay(cfg.lr0, cfg.lr_decay)

    def round_fn(params, sched_state, key, data, fstate=None, dstate=None):
        with jax.named_scope("admission"):
            k_sel, k_local = jax.random.split(key)
            selected, sched_state = policy.step(sched_state, k_sel)
            if have_def:
                selected = selected & ~defense.blocked(dstate)
            idx, mask = cohort_indices(selected, width)
            keys = jax.random.split(k_local, width)
            if cohort_pad:
                # pad to the mesh multiple with weight-0 slots; real slots
                # keep the exact unpadded key draws (split(k, wp) has a
                # different prefix than split(k, width))
                idx = jnp.concatenate([idx, jnp.zeros((cohort_pad,), idx.dtype)])
                mask = jnp.concatenate([mask, jnp.zeros((cohort_pad,), mask.dtype)])
                keys = keys[jnp.minimum(jnp.arange(wp), width - 1)]
        with jax.named_scope("local_train"):
            eff = None
            if have_faults:
                k_fault = jax.random.fold_in(k_sel, 105)
                fstate, eff = faults.on_pop(
                    fstate, jax.random.fold_in(k_fault, 1), idx, mask > 0
                )
                eff = cohort_layout(eff)
            lr = lr_fn(sched_state["round"] - 1)
            if grouped:
                count = jnp.sum(mask > 0, dtype=jnp.int32)
                updated, losses, trained = _train_groups(
                    local_update, params, data, idx, keys, lr, count, group
                )
            else:
                shards = cohort_layout(jax.tree.map(lambda a: a[idx], data))
                # the cohort axis of the global params is a lazy vmap
                # broadcast — no (width, ...) copies are materialized;
                # aggregators see the unstacked global tree as ``bases``
                # and broadcast in their deltas
                updated, losses = cohort_layout(
                    jax.vmap(local_update, in_axes=(None, 0, 0, None))(
                        params, shards, keys, lr
                    )
                )
                trained = jnp.asarray(wp, jnp.int32)
            if corrupt_on:
                updated = corrupt_updates(
                    updated, params, eff, jax.random.fold_in(k_fault, 2),
                    faults.has("scale"), faults.has("noise"),
                )
            if collude_on:
                # after corrupt: the coalition's replacement is authoritative
                updated = collude_updates(updated, params, eff)
        with jax.named_scope("aggregate"):
            valid = mask > 0
            if kill_on:
                # a dropped client's update never reaches the server: weight 0
                valid = valid & ~eff.kill
            if have_def:
                # fold 108 (same schedule as the async engine); staleness is
                # identically zero in a sync round
                ages = (cohort_layout(sched_state["ages"][idx])
                        if "ages" in sched_state else None)
                dstate, suspect, w_scale = defense.observe(
                    dstate, jax.random.fold_in(k_sel, 108),
                    updated, params, idx, valid, jnp.zeros_like(idx),
                    losses=losses, ages=ages,
                    labels=cohort_layout(effects_hit(eff)) if sup_on else None,
                )
                valid = valid & ~cohort_layout(suspect[idx])
            # sync cohorts are never stale: staleness is identically zero
            w = agg.weigh(valid, jnp.zeros_like(idx))
            if col_on:
                # exact 1.0 on clique-free slots: calm armed rounds multiply
                # the weights by ones
                w = w * w_scale
            if mtd_on:
                params, tel = aggregate_mtd(
                    params, updated, params, w, idx, dstate["level"]
                )
            else:
                params, tel = aggregate(params, updated, params, w, idx)
            wsum = w.sum()
            # NaN, not a fake near-0 datapoint, when nobody was selected
            # (matching the async engine's empty-buffer convention)
            mean_loss = jnp.where(
                wsum > 0, jnp.sum(losses * w) / jnp.maximum(wsum, 1.0), jnp.nan
            )
        return (params, sched_state, selected, mean_loss, trained, fstate,
                dstate, tel)

    return round_fn


def _make_round_fn(task: FLTask, cfg: RunConfig, policy: Policy, agg: Aggregator):
    """Jitted per-round step (legacy helper for ``fl/rounds.py``):
    the fault/telemetry-free 4-tuple view of the round core."""
    core = _make_round_core(task, cfg, policy, agg)

    def round_fn(params, sched_state, key, data):
        params, sched_state, selected, loss, _, _, _, _ = core(
            params, sched_state, key, data
        )
        return params, sched_state, selected, loss

    return functools.partial(jax.jit(round_fn), data=task.client_data)

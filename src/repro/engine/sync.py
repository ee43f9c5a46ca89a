"""The synchronous FedAvg engine.

One step = policy step -> cohort packing -> the cohort stage
(``repro.engine.cohort``: local training, on one device only the groups
of slots that hold a selected client, then the aggregator
``weigh/init/accumulate/finalize``) -> age update, against the
``Engine`` protocol (`init/step/run_chunk/finalize`). The default
``fedavg`` aggregator reproduces the pre-refactor weighted cohort mean
bit-for-bit (pinned by ``tests/test_engine_equivalence.py``), while
delta-based aggregators (``fedprox``) drop in without touching this file.

The hot loop runs through ``ChunkRunner``: ``steps_per_chunk`` rounds per
host dispatch via a donated ``lax.scan``, with the selection-gap load
accumulators updated on device (``tests/test_engine_chunked.py`` pins
chunked == per-step bit-for-bit). The cohort trains from the global
params broadcast lazily and aggregators receive the unstacked global
tree as ``bases``; the group loop's (width, ...) output starts as that
broadcast, so untrained slots are a zero update.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import distributed as dist
from repro.core.load_metric import (
    init_selection_accum,
    init_tier_accum,
    update_tier_accum,
)
from repro.core.selection import Policy
from repro.engine.aggregators import Aggregator
from repro.engine.chunk import ChunkRunner, dealias_pytree, run_key, step_once
from repro.engine.cohort import (
    CohortStage,
    cohort_layout,
    make_aggregate,
    run_result,
)
from repro.engine.config import RoundRecord, RunConfig, RunResult
from repro.engine.registry import make_aggregator, make_policy
from repro.engine.sharded import make_sharded_eval, require_cohort_mesh
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
        mesh = axis = layout = None
        shards = 1
        if cfg.shard_cohort:
            # cohort-parallel sync rounds: the cohort vmap (and the
            # aggregator accumulation) partitions over a device mesh —
            # sync has no per-client device state, so the mesh shards the
            # *cohort* axis only. mesh_shards=0 takes every local device.
            shards = cfg.mesh_shards or len(jax.devices())
            require_cohort_mesh(shards, f"mesh_shards={cfg.mesh_shards}")
            axis = dist.FLEET_AXIS
            mesh = self.mesh = dist.fleet_mesh(shards, axis)
            self.mesh_shards = shards
            layout = cohort_layout(mesh, axis)
            self._sharded_eval = make_sharded_eval(task, mesh, axis)
        # sync passes the unstacked global tree as bases
        core = _make_round_core(
            task, cfg, self.policy, self.aggregator,
            cohort_layout=layout,
            aggregate=make_aggregate(self.aggregator, self.topo,
                                     cfg.n_clients, mesh, axis,
                                     stacked_bases=False),
            cohort_shards=shards,
            faults=self.fault_set,
            defense=self.defense,
        )

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
        stats = {f"agg_{s}": float(state["agg_stats"][s])
                 for s in self.aggregator.stat_names}
        return run_result(self, state, records, sel_hist, wall_time_s, stats)


def _make_round_core(task: FLTask, cfg: RunConfig, policy: Policy, agg: Aggregator,
                     cohort_layout=None, aggregate=None, cohort_shards: int = 1,
                     faults=None, defense=None):
    """The pure per-round function (no jit), the scan body's round. It
    reads the clients' examples from its ``data`` argument
    (``task.client_data``), never from a closure, so no compiled round
    embeds them as constants.

    The round owns admission: the policy step, the defense's veto of
    quarantined clients (they still age — the policy's chain advanced),
    and the packing of the selection into ``cfg.cohort_width()`` slots
    (``cohort_indices``). Everything from local training to the new
    params is the cohort stage (``repro.engine.cohort.CohortStage``): a
    variable-size policy's packed cohort, about half of it empty under
    the Markov policy, trains on one device (no ``cohort_layout`` hook)
    as a group loop over the groups that hold a selected client, and
    exact-k cohorts and the cohort-sharded mesh train every slot in one
    vmap. The round returns the slots it trained as ``trained_slots``.

    The optional hooks are the cohort-parallel seam: ``cohort_layout``
    lays the cohort-stacked intermediates out over the mesh,
    ``aggregate`` is the stage's aggregate hook (``make_aggregate``), and
    ``cohort_shards`` pads the cohort axis with weight-0 slots to the
    next multiple of the mesh. Defaults reproduce the single-device
    round bit-for-bit. ``faults`` and ``defense`` arm the stage's tier
    seams on the async engine's fold schedule; sync cohorts are never
    stale, so every slot's staleness is zero."""
    width = cfg.cohort_width() if not policy.exact_k else cfg.k
    cohort_pad = dist.cohort_padding(width, cohort_shards)
    wp = width + cohort_pad
    # an exact-k cohort has no padding to skip: its single vmap stays,
    # whose train loss the pre-refactor golden tests pin to the bit
    stage = CohortStage(task, cfg, agg, width, aggregate=aggregate,
                        layout=cohort_layout, packed=not policy.exact_k,
                        faults=faults, defense=defense)
    lr_fn = exponential_decay(cfg.lr0, cfg.lr_decay)

    def round_fn(params, sched_state, key, data, fstate=None, dstate=None):
        with jax.named_scope("admission"):
            k_sel, k_local = jax.random.split(key)
            selected, sched_state = policy.step(sched_state, k_sel)
            if defense is not None:
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
            fstate, eff = stage.on_pop(fstate, k_sel, idx, mask > 0)
            lr = lr_fn(sched_state["round"] - 1)
            count = jnp.sum(mask > 0, dtype=jnp.int32)
        with jax.named_scope("aggregate"):
            # recomputed under `aggregate`: the scope an op names is where
            # the trace readers (`agg_ms`, `train_ms`) count its time
            arrived = stage.delivered(mask > 0, eff)
        # sync cohorts are never stale: staleness is identically zero
        out = stage(params, params, data, idx, keys, lr, count, arrived,
                    jnp.zeros_like(idx), eff, dstate, k_sel,
                    sched_state.get("ages"))
        with jax.named_scope("aggregate"):
            wsum = out.weights.sum()
            # NaN, not a fake near-0 datapoint, when nobody was selected
            # (matching the async engine's empty-buffer convention)
            mean_loss = jnp.where(
                wsum > 0,
                jnp.sum(out.losses * out.weights) / jnp.maximum(wsum, 1.0),
                jnp.nan,
            )
        return (out.params, sched_state, selected, mean_loss, out.trained,
                fstate, out.dstate, out.stats)

    return round_fn

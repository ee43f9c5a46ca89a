"""The buffered asynchronous engine over the event-driven fleet simulator.

One jit'd server step = admission control (idle+available clients consult
their selection policy — the Markov chain decides *locally* whether to
pull the model, preserving the paper's zero-coordination property) ->
dispatch with sampled wall-clock latencies -> pop the next ``buffer_size``
completions (event_topk kernel at fleet scale) -> vmapped local training
from each client's *dispatch-time* model version (a ring buffer of the
last ``max_versions`` global models) -> aggregator
``weigh/init/accumulate/finalize`` over the buffered deltas -> clock/
version advance.

This is ``sim/async_rounds.py`` re-expressed against the ``Engine``
protocol with the aggregation seam opened up: the default ``fedbuff``
aggregator reproduces the pre-refactor staleness-discounted delta mean
bit-for-bit (pinned by ``tests/test_engine_equivalence.py``). With the
degenerate ``uniform`` latency profile (zero spread, always available, no
dropout) and ``buffer_size = k`` every dispatch completes inside its own
step with staleness 0, and the loop reproduces the synchronous FedAvg
round of ``SyncEngine`` exactly.

The load metric is reported on two clocks: X in decision epochs (the
paper's round-indexed Var[X]) and X in simulated seconds (wall-clock
inter-update gaps per client), which is where stragglers and availability
windows actually show up.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.aoi import age_update, peak_age_accumulate
from repro.core.load_metric import (
    empirical_load_stats,
    init_selection_accum,
    selection_stats_from_accum,
    tier_stats_from_accum,
)
from repro.core.selection import Policy
from repro.engine.aggregators import Aggregator, acc_stats
from repro.engine.chunk import ChunkRunner, dealias_pytree, run_key, step_once
from repro.engine.config import RoundRecord, RunConfig, RunResult
from repro.engine.registry import make_aggregator, make_policy
from repro.fl.client import make_local_update
from repro.fl.task import FLTask
from repro.optim.schedules import exponential_decay
from repro.sim import events as ev_mod
from repro.sim import latency as lat_mod


def _resolved_profile(profile) -> lat_mod.LatencyProfile:
    if isinstance(profile, lat_mod.LatencyProfile):
        return profile
    return lat_mod.get_profile(profile)


def _init_stats(heartbeat: bool = False, redispatch: bool = False,
                agg_stats: tuple = ()) -> Dict[str, jnp.ndarray]:
    z = jnp.zeros((), jnp.float32)
    out = {
        "wall_sx": z, "wall_sx2": z, "wall_cnt": z,  # X in simulated seconds
        "ep_sx": z, "ep_sx2": z, "ep_cnt": z,  # X in decision epochs
        "stale_sum": z, "stale_cnt": z,
        "stale_max": jnp.zeros((), jnp.int32),
        "updates": z,  # successful updates aggregated
        "aggs": z,  # server versions produced
    }
    if heartbeat:
        out["hb_expired"] = z  # updates excluded by heartbeat churn
    if redispatch:
        out["redispatched"] = z  # expired dispatches re-issued
        out["rd_expired"] = z  # deadline expiries (incl. written off)
    for s in agg_stats:
        out[f"agg_{s}"] = z  # aggregator telemetry (e.g. norm_clip)
    return out


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


class AsyncEngine:
    """Asynchronous server steps: one buffer flush per step, clients train
    from (possibly stale) ring-buffered model versions."""

    def __init__(
        self,
        task: FLTask,
        cfg: RunConfig,
        policy: Optional[Policy] = None,
        aggregator: Optional[Aggregator] = None,
    ):
        if cfg.mode != "async":
            raise ValueError(f"AsyncEngine needs mode='async', got {cfg.mode!r}")
        self.task = task
        self.cfg = cfg
        self.policy = policy or make_policy(
            cfg.policy, cfg.n_clients, cfg.k, cfg.m, **dict(cfg.policy_kwargs)
        )
        self.aggregator = aggregator or make_aggregator(
            cfg.resolved_aggregator(), **dict(cfg.aggregator_kwargs)
        )
        self.profile = _resolved_profile(cfg.profile)
        self.topo = cfg.resolved_topology()
        self.fault_set = cfg.resolved_faults()
        self.defense_cfg = cfg.resolved_defense()
        if self.defense_cfg is not None:
            from repro.defense import make_defense

            self.defense = make_defense(cfg.n_clients, self.defense_cfg)
        else:
            self.defense = None
        self._init_state, core = self._build_step()
        self._chunk = ChunkRunner(
            core, aux_keys=("loss", "clock", "version", "buffer_fill"),
            data=client_rows(self.task.client_data),
        )

    def _build_step(self):
        """Step-builder hook: ``ShardedAsyncEngine`` overrides this to
        inject the mesh-sharded pop and sharding constraints."""
        return _make_async_step(
            self.task, self.cfg, self.policy, self.aggregator, self.profile,
            topo=self.topo, faults=self.fault_set, defense=self.defense,
        )

    def init(self) -> Dict:
        cfg = self.cfg
        key = run_key(cfg.seed, cfg.rng_impl)
        k_init, k_policy, k_run = jax.random.split(key, 3)
        params = self.task.init(k_init)
        sched = self.policy.init(k_policy, cfg.n_clients)
        state = self._init_state(params, sched, jax.random.fold_in(k_run, 2**31))
        state["k_run"] = k_run
        state["load_acc"] = init_selection_accum(cfg.n_clients, cfg.k)
        # donation-safe from the start: step() routes through the donated
        # chunk runner even for single steps
        return dealias_pytree(state)

    def step(self, state: Dict, r: int):
        return step_once(self._chunk, state, r)

    def run_chunk(self, state: Dict, r0: int, length: int, with_history: bool):
        return self._chunk(state, r0, length, with_history)

    def eval_params(self, state: Dict):
        return state["params"]

    def ring_snapshot(self, state: Dict):
        """Device-resident view of the retained-version ring for the
        serving tier (``repro.serve.VersionStore``): ``(hist, version,
        max_versions)``. No host pull and no copy — the leaves stay
        wherever the engine keeps them (the sharded engines replicate
        ``hist``/``version``, so the same snapshot works unchanged), and
        the serving tier reads versions without synchronizing training."""
        return state["hist"], state["version"], self.cfg.max_versions

    def evaluate(self, state: Dict) -> Dict:
        """Held-out eval on the current global params. Cohort-sharded
        engines override this to shard the eval-batch axis over the mesh
        (params stay replicated)."""
        return self.task.eval_fn(self.eval_params(state))

    def record(self, r: int, aux: Dict, ev: Dict) -> RoundRecord:
        return RoundRecord(
            round=r + 1,
            train_loss=float(aux["loss"]),
            eval_loss=float(ev["loss"]),
            accuracy=float(ev["accuracy"]),
            clock=float(aux["clock"]),
            version=int(aux["version"]),
            buffer_fill=int(aux["buffer_fill"]),
        )

    def _topo_tag(self) -> str:
        if self.topo is None or self.topo.is_star:
            return ""
        return f"/{self.topo.describe()}"

    def progress_line(self, rec: RoundRecord, elapsed: float) -> str:
        return (
            f"  [{self.policy.name}/{self.profile.name}{self._topo_tag()}] "
            f"step {rec.round:4d} t={rec.clock:9.2f}s v={rec.version:4d} "
            f"acc={rec.accuracy:.4f} loss={rec.eval_loss:.4f} ({elapsed:.1f}s)"
        )

    def finalize(self, state, records, sel_hist, wall_time_s) -> RunResult:
        st = {k: float(v) for k, v in state["stats"].items()}

        def _mv(sx, sx2, cnt):
            if cnt <= 0:
                return float("nan"), float("nan")
            mean = sx / cnt
            return mean, max(sx2 / cnt - mean * mean, 0.0)

        mean_w, var_w = _mv(st["wall_sx"], st["wall_sx2"], st["wall_cnt"])
        mean_e, var_e = _mv(st["ep_sx"], st["ep_sx2"], st["ep_cnt"])
        wall_stats = {
            "mean_X_wall": mean_w, "var_X_wall": var_w,
            "num_samples_wall": int(st["wall_cnt"]),
            "mean_X_epoch": mean_e, "var_X_epoch": var_e,
            "num_samples_epoch": int(st["ep_cnt"]),
            "mean_staleness": st["stale_sum"] / max(st["stale_cnt"], 1.0),
            "max_staleness": int(st["stale_max"]),
            "updates_applied": int(st["updates"]),
            "aggregations": int(st["aggs"]),
            "sim_time": float(state["clock"]),
        }
        if "hb_expired" in st:
            wall_stats["hb_expired"] = int(st["hb_expired"])
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
        if "redispatched" in st:
            load_stats["redispatched"] = int(st["redispatched"])
            load_stats["rd_expired"] = int(st["rd_expired"])
        for s in self.aggregator.stat_names:
            load_stats[f"agg_{s}"] = float(st[f"agg_{s}"])
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
            wall_stats=wall_stats,
            params=state["params"],
            wall_time_s=wall_time_s,
            fault_exposure=fault_exposure,
            defense=(self.defense.arrays(state["defense"])
                     if "defense" in state else None),
        )


def _make_async_step(
    task: FLTask, cfg: RunConfig, policy: Policy, agg: Aggregator,
    profile: lat_mod.LatencyProfile,
    pop=None, cohort_layout=None, constrain_state=None,
    aggregate=None, cohort_pad: int = 0, topo=None, faults=None,
    defense=None,
):
    """Builds ``(init_state, step core)`` with ``step(state, key, data) ->
    (state, aux)`` — the pure function the chunked scan body folds over
    (``ChunkRunner`` also drives single steps through a length-1 chunk);
    ``data`` is ``client_rows(task.client_data)``, passed in rather than
    closed over; the cohort's rows take ``task.client_data``'s per-client
    shapes back before local training.

    The optional hooks are the mesh-sharding seam (``repro.engine.sharded``
    supplies them; the single-device engine runs with identity defaults):

      * ``pop(ev) -> (t, idx, valid, ev')`` replaces the buffer pop;
      * ``cohort_layout(tree)`` decides the device layout of every
        cohort-sized (B,) intermediate. The bit-exact sharded engine pins
        them *replicated* so cross-device reduction order — and therefore
        bitwise results — cannot drift from the single-device engine; the
        cohort-parallel mode (``RunConfig.shard_cohort``) lays them out
        ``P(fleet)`` instead so each device trains only its slice of the
        cohort;
      * ``aggregate(params, updates, bases, w, idx) -> params`` replaces
        the inline ``init/accumulate/finalize`` chain (the cohort-parallel
        mode routes it through ``aggregators.cohort_sharded_apply``:
        shard-local accumulation merged by one psum; ``idx`` is the
        cohort -> client map, which topology-aware reductions use to
        route each slot to its tier-0 node);
      * ``cohort_pad`` appends that many zero-weight slots to the popped
        cohort so the padded axis divides the mesh (invalid slots, masked
        everywhere exactly like an under-filled buffer);
      * ``constrain_state(state)`` re-asserts the fleet sharding of the
        carry so the donated scan aliases buffers instead of resharding.

    ``topo`` (a ``repro.topo.Topology``) reshapes the aggregation: the
    default aggregate becomes the tiered reduction, every dispatch pays
    the per-hop DAG latency under a dedicated key fold, the per-tier
    load accumulators ride the state, and a non-zero
    ``heartbeat_timeout`` excludes dark clients from their tier's
    reduction. A star (or ``topo=None``) leaves every code path — state
    keys, key folds, ops — untouched, so the degenerate case is
    structurally bit-for-bit identical (pinned by ``tests/test_topo.py``).

    ``faults`` (a ``repro.faults.FaultSet``) and a non-zero
    ``cfg.redispatch_timeout`` follow the same structural-gating rule:
    armed, they add their ``(n,)`` state to the carry and draw under
    dedicated key folds (105 with sub-folds 0=dispatch/1=pop/2=corrupt;
    106/107 for re-dispatch latency); absent, no state key, no fold, no
    op exists and the engine is bit-for-bit today's
    (``tests/test_faults.py`` pins both the structural and the rate-0
    golden).

    ``defense`` (a ``repro.defense.Defense``) closes the detect ->
    quarantine -> adapt loop inside this same step under the same rule:
    armed, it adds its ``(n,)`` reputation/status state to the carry,
    draws its probation/readmit coins under dedicated fold 108, vetoes
    quarantined clients at the selection seam (``send &= ~blocked``) and
    suspect updates at the aggregation seam (``succ &= ~suspect`` — the
    exact seam heartbeat dark-clients use), and, with mtd configured,
    swaps the aggregate hook for the moving-target wrapper. Disarmed:
    no state key, no fold, no op (``tests/test_defense.py`` pins the
    structural golden and the armed-but-never-triggered bitwise one).
    """
    n = cfg.n_clients
    B = cfg.resolved_buffer_size()
    Bp = B + cohort_pad
    H = cfg.max_versions
    tiered = topo is not None and not topo.is_star
    hb_timeout = float(topo.heartbeat_timeout) if topo is not None else 0.0
    have_faults = faults is not None
    have_def = defense is not None
    rd_on = (cfg.redispatch_timeout or 0) > 0
    kill_on = have_faults and faults.has("kill")
    if have_faults and (faults.has("scale") or faults.has("noise")):
        from repro.faults.inject import corrupt_updates
    collude_on = have_faults and faults.has("collude")
    if collude_on:
        from repro.faults.inject import collude_updates
    col_on = have_def and defense.collusion
    # supervised labels for the learned detector head: only when the run
    # opted into exposure ground truth AND some fault actually pops
    sup_on = (have_def and defense.wants_labels and have_faults
              and faults.has_pop and cfg.fault_exposure)
    if sup_on:
        from repro.faults.inject import effects_hit
    if tiered:
        from repro.core.load_metric import init_tier_accum, update_tier_accum
        from repro.topo.reduce import make_hop_latency, tiered_apply

        assign_dev = jnp.asarray(topo.assign(n))
        hop_fn = make_hop_latency(topo, n)
    if hb_timeout > 0 or rd_on:
        # re-dispatch deadlines reuse the heartbeat liveness predicate:
        # "no completion for longer than the timeout" is the same signal
        from repro.topo import heartbeat as hb_mod
    if pop is None:
        def pop(ev):
            return ev_mod.pop_events(ev, B, use_kernel=cfg.use_kernel)
    if cohort_layout is None:
        cohort_layout = lambda tree: tree  # noqa: E731
    if constrain_state is None:
        constrain_state = lambda state: state  # noqa: E731
    if aggregate is None:
        if tiered:
            aggregate = tiered_apply(agg, topo, n)
        else:
            def aggregate(g, updates, bases, w, idx=None):
                acc = agg.accumulate(agg.init(g), updates, bases, w)
                return agg.finalize(g, acc), acc_stats(acc)
    mtd_on = have_def and defense.mtd
    if mtd_on:
        # config rejects mtd under tiered/cohort-sharded aggregation, so
        # the wrapped hook is always the inline (or bit-exact sharded)
        # default; level 0 routes through it untouched via lax.cond
        from repro.defense.adaptive import adaptive_aggregate

        aggregate_mtd = adaptive_aggregate(aggregate, defense.cfg.mtd_trims,
                                           families=defense.cfg.mtd_families)
    local_update = make_local_update(
        task.loss_fn, cfg.local_epochs, cfg.batch_size, task.examples_per_client
    )
    lr_fn = exponential_decay(cfg.lr0, cfg.lr_decay)

    def init_state(params, sched_state, key):
        state = {
            "params": params,
            # ring buffer of the last H global models; slot v % H = version v
            "hist": jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (H,) + p.shape), params
            ),
            "sched": sched_state,
            "ev": ev_mod.init_event_state(n),
            "speed": lat_mod.client_speed(key, n, profile),
            "clock": jnp.zeros((), jnp.float32),
            "version": jnp.zeros((), jnp.int32),
            "stats": _init_stats(heartbeat=hb_timeout > 0, redispatch=rd_on,
                                 agg_stats=agg.stat_names),
        }
        if hb_timeout > 0:
            state["hb"] = hb_mod.init_heartbeat(n)
        if tiered:
            state["tier_acc"] = init_tier_accum(n, int(topo.tier_sizes[0]))
        if have_faults:
            # fold 7 off the init key: independent of the speed draw
            state["faults"] = faults.init(jax.random.fold_in(key, 7))
        if have_def:
            state["defense"] = defense.init()  # deterministic zeros
        if rd_on:
            state["rd"] = {
                "t_disp": jnp.zeros((n,), jnp.float32),
                "retries": jnp.zeros((n,), jnp.int32),
            }
        return state

    def step(state, key, data):
        ev, sched, stats = state["ev"], state["sched"], state["stats"]
        clock, version = state["clock"], state["version"]
        # same key split as the sync round so the degenerate case is
        # bit-for-bit comparable; latency/dropout/gap keys are fresh folds
        k_sel, k_local = jax.random.split(key)
        k_lat = jax.random.fold_in(k_sel, 101)
        k_gap = jax.random.fold_in(k_sel, 103)

        with jax.named_scope("admission"):
            # --- admission control: idle+available clients consult the policy
            prev_ages = sched["ages"]
            idle = jnp.isinf(ev["t_done"])
            available = ev["next_avail"] <= clock
            want, sched = policy.step(sched, k_sel)
            send = want & idle & available
            if have_def:
                # quarantined clients are vetoed at the admission seam (they
                # still age); probation clients stay selectable so they keep
                # generating evidence for re-admission
                dstate = state["defense"]
                send = send & ~defense.blocked(dstate)
            # only actual dispatches reset the AoI clock; everyone else ages
            sched = {**sched, "ages": age_update(prev_ages, send)}
            ep_sx, ep_sx2, ep_cnt = peak_age_accumulate(
                prev_ages, send, stats["ep_sx"], stats["ep_sx2"], stats["ep_cnt"]
            )

        with jax.named_scope("dispatch"):
            # --- dispatch: sample wall-clock latencies, mark in flight.
            # zero-dropout profiles skip the dropout path entirely — the 102
            # key fold here plus the constant-folding of the zeros mask
            # (sample_dropout already skips the (n,) draw itself). No other
            # key depends on the 102 fold, so results are unchanged — pinned
            # by tests/test_cohort_engine.py
            latency = lat_mod.sample_latency(k_lat, profile, state["speed"])
            if tiered:
                # fold 104: per-hop DAG latency. Only drawn when a multi-tier
                # topology is armed, so the star key schedule is untouched
                latency = latency + hop_fn(jax.random.fold_in(k_sel, 104))
            if have_faults:
                fstate = state["faults"]
                # fold 105: the fault set's dedicated key (sub-folds:
                # 0 dispatch, 1 pop, 2 corruption noise) — armed only when
                # faults are, so the fault-free key schedule is untouched
                k_fault = jax.random.fold_in(k_sel, 105)
                if faults.has_dispatch:
                    fstate, latency = faults.on_dispatch(
                        fstate, jax.random.fold_in(k_fault, 0), send, latency
                    )
            if hb_timeout > 0:
                # dispatch is a heartbeat: the client pulled the model at
                # the current clock
                hb = hb_mod.beat(state["hb"], send, clock)
            if profile.dropout > 0:
                dropped = lat_mod.sample_dropout(
                    jax.random.fold_in(k_sel, 102), profile, n
                )
            else:
                dropped = jnp.zeros((n,), jnp.bool_)
            ev = ev_mod.schedule_completions(ev, send, clock, latency, version, dropped)

            # --- deadline-based re-dispatch of expired in-flight dispatches:
            # a dispatch the server has not heard back from within the
            # timeout is sent again at the current version with a fresh
            # latency (folds 106/107), at most redispatch_retries times —
            # then written off (t_done=inf frees the client to be selected
            # again). The original dispatch's dropout coin is preserved: a
            # retry re-attempts delivery, not the client's fate.
            if rd_on:
                rd_t = jnp.where(send, clock, state["rd"]["t_disp"])
                rd_cnt = jnp.where(send, 0, state["rd"]["retries"])
                inflight = ~jnp.isinf(ev["t_done"])
                exp = inflight & hb_mod.expired(
                    rd_t, clock, float(cfg.redispatch_timeout)
                )
                retry = exp & (rd_cnt < cfg.redispatch_retries)
                give_up = exp & ~retry
                rd_lat = lat_mod.sample_latency(
                    jax.random.fold_in(k_sel, 106), profile, state["speed"]
                )
                if tiered:
                    rd_lat = rd_lat + hop_fn(jax.random.fold_in(k_sel, 107))
                ev = {
                    **ev,
                    "t_done": jnp.where(
                        retry, clock + rd_lat,
                        jnp.where(give_up, jnp.inf, ev["t_done"]),
                    ),
                    "disp_ver": jnp.where(retry, version, ev["disp_ver"]),
                }
                rd = {
                    "t_disp": jnp.where(retry, clock, rd_t),
                    "retries": rd_cnt + retry.astype(jnp.int32),
                }
                rd_retried = retry.astype(jnp.float32).sum()
                rd_expired = exp.astype(jnp.float32).sum()

        with jax.named_scope("pop"):
            # --- pop the next B completions, advance the simulated clock
            t_ev, idx, valid, ev = pop(ev)
            if cohort_pad:
                # pad the cohort to the mesh multiple with invalid slots:
                # t=+inf/valid=False masks them out of the clock advance, the
                # weights, the telemetry, and both scatters, exactly like an
                # under-filled buffer slot
                t_ev = jnp.concatenate(
                    [t_ev, jnp.full((cohort_pad,), jnp.inf, t_ev.dtype)]
                )
                idx = jnp.concatenate([idx, jnp.zeros((cohort_pad,), idx.dtype)])
                valid = jnp.concatenate(
                    [valid, jnp.zeros((cohort_pad,), valid.dtype)]
                )
            if have_faults and faults.has_pop:
                # fold 105/1: per-slot injection coins over the popped cohort
                fstate, eff = faults.on_pop(
                    fstate, jax.random.fold_in(k_fault, 1), idx, valid
                )
                eff = cohort_layout(eff)
            new_clock = jnp.maximum(clock, jnp.max(jnp.where(valid, t_ev, -jnp.inf)))
            # an all-idle fleet inside availability gaps must not freeze the
            # clock: with nothing in flight to pop, jump to the earliest
            # window opening so availability can recover next step
            new_clock = jnp.where(
                valid.any(), new_clock,
                jnp.maximum(new_clock, jnp.min(ev["next_avail"])),
            )

        with jax.named_scope("local_train"):
            # --- local training from each client's dispatch-time model
            disp_ver = cohort_layout(ev["disp_ver"][idx])
            # versions older than the ring are trained from the oldest retained
            # model; staleness for weighting still uses the true dispatch version
            read_ver = jnp.clip(disp_ver, jnp.maximum(version - (H - 1), 0), version)
            if have_faults and faults.has("replay"):
                # stale replay: hit slots read an older retained version than
                # they were dispatched (shift 0 elsewhere is exact identity on
                # ints); the staleness *weight* below still sees the honest
                # dispatch version — precisely the attack
                read_ver = jnp.maximum(
                    read_ver - eff.replay_shift,
                    jnp.maximum(version - (H - 1), 0),
                )
            disp_params = cohort_layout(
                jax.tree.map(lambda h: h[read_ver % H], state["hist"])
            )
            shards = cohort_layout(gather_rows(data, idx, task.client_data))
            keys = jax.random.split(k_local, B)
            if cohort_pad:
                # the first B keys must stay the exact draws of the unpadded
                # engine (split(k, Bp) has a different prefix); padded slots
                # reuse the last real key — their updates carry weight 0
                keys = keys[jnp.minimum(jnp.arange(Bp), B - 1)]
            lr = lr_fn(jnp.maximum(disp_ver, 0))
            updated, losses = cohort_layout(
                jax.vmap(local_update, in_axes=(0, 0, 0, 0))(
                    disp_params, shards, keys, lr
                )
            )
            if have_faults and (faults.has("scale") or faults.has("noise")):
                # fold 105/2: corruption noise. Missed slots keep their exact
                # input buffers (per-slot where inside corrupt_updates), so a
                # rate-0 set is bitwise identity
                updated = corrupt_updates(
                    updated, disp_params, eff, jax.random.fold_in(k_fault, 2),
                    faults.has("scale"), faults.has("noise"),
                )
            if collude_on:
                # after corrupt: a coalition member's replacement is
                # authoritative over any scale/noise it also drew. Keyless —
                # the direction is a trace-time constant, the jitter rode
                # the fault's own pop fold
                updated = collude_updates(updated, disp_params, eff)

        with jax.named_scope("aggregate"):
            # --- buffered aggregation of deltas through the aggregator seam
            succ = valid & ~ev["dropped"][idx]
            if kill_on:
                # mid-round dropout: the update never arrived — excluded from
                # aggregation and from heartbeat contact below
                succ = succ & ~eff.kill
            if hb_timeout > 0:
                # an update landing more than the timeout after its client's
                # last contact looks dead to its tier coordinator: excluded
                # from the reduction exactly like a dropped slot. All valid
                # completions still count as contact (the client did return)
                dark = succ & hb_mod.expired(
                    hb["last_beat"][idx], t_ev, hb_timeout
                )
                succ = succ & ~dark
                arrived = valid & ~eff.kill if kill_on else valid
                hb = hb_mod.beat_at(hb, ev_mod.scatter_idx(idx, arrived), t_ev)
            staleness = jnp.maximum(version - disp_ver, 0)
            if have_def:
                # fold 108: the defense tier's dedicated key (sub-folds
                # 0 probation / 1 readmit coins). Every update that arrived
                # (pre-exclusion succ) is scored — including probation
                # clients — then post-transition suspects are excluded from
                # the reduction through the exact seam heartbeat dark
                # clients use, closing the detect->quarantine loop within
                # the step
                dstate, suspect, w_scale = defense.observe(
                    dstate, jax.random.fold_in(k_sel, 108),
                    updated, disp_params, idx, succ, staleness,
                    losses=losses, ages=cohort_layout(sched["ages"][idx]),
                    labels=cohort_layout(effects_hit(eff)) if sup_on else None,
                )
                succ = succ & ~cohort_layout(suspect[idx])
            w = agg.weigh(succ, staleness)
            if col_on:
                # clique members keep a (discounted) vote rather than a
                # binary exclusion: w_scale is exact 1.0 on clique-free
                # slots, so a calm armed run multiplies by ones
                w = w * w_scale
            wsum = w.sum()
            has = wsum > 0
            denom = jnp.maximum(wsum, 1e-9)
            if mtd_on:
                params, agg_tel = aggregate_mtd(
                    state["params"], updated, disp_params, w, idx,
                    dstate["level"],
                )
            else:
                params, agg_tel = aggregate(
                    state["params"], updated, disp_params, w, idx
                )
            version = version + has.astype(jnp.int32)
            hist = jax.tree.map(
                lambda h, p: h.at[version % H].set(p), state["hist"], params
            )
            # NaN, not a fake 0.0 datapoint, when nothing was aggregated
            mean_loss = jnp.where(has, jnp.sum(losses * w) / denom, jnp.nan)

        with jax.named_scope("load_metric"):
            # --- completed clients go idle; wall-clock AoI samples
            # gaps are i.i.d. — draw only the B popped clients' worth
            gaps = lat_mod.sample_avail_gap(k_gap, profile, B)
            if cohort_pad:
                gaps = jnp.concatenate(
                    [gaps, jnp.zeros((cohort_pad,), gaps.dtype)]
                )
            ev = {
                **ev,
                "next_avail": ev["next_avail"]
                .at[ev_mod.scatter_idx(idx, valid)]
                .set(new_clock + gaps, mode="drop"),
            }
            last_done = cohort_layout(ev["last_done"][idx])
            x_wall = t_ev - last_done
            wall_ok = succ & (last_done >= 0.0)
            wall_okf = wall_ok.astype(jnp.float32)
            ev = {
                **ev,
                "last_done": ev["last_done"]
                .at[ev_mod.scatter_idx(idx, succ)]
                .set(t_ev, mode="drop"),
            }

            stats = {
                "wall_sx": stats["wall_sx"] + jnp.sum(jnp.where(wall_ok, x_wall, 0.0)),
                "wall_sx2": stats["wall_sx2"]
                + jnp.sum(jnp.where(wall_ok, x_wall**2, 0.0)),
                "wall_cnt": stats["wall_cnt"] + wall_okf.sum(),
                "ep_sx": ep_sx, "ep_sx2": ep_sx2, "ep_cnt": ep_cnt,
                "stale_sum": stats["stale_sum"]
                + jnp.sum(jnp.where(succ, staleness, 0).astype(jnp.float32)),
                "stale_cnt": stats["stale_cnt"] + succ.astype(jnp.float32).sum(),
                "stale_max": jnp.maximum(
                    stats["stale_max"], jnp.max(jnp.where(succ, staleness, 0))
                ),
                "updates": stats["updates"] + succ.astype(jnp.float32).sum(),
                "aggs": stats["aggs"] + has.astype(jnp.float32),
            }
            if hb_timeout > 0:
                stats["hb_expired"] = (
                    state["stats"]["hb_expired"] + dark.astype(jnp.float32).sum()
                )
            if rd_on:
                stats["redispatched"] = state["stats"]["redispatched"] + rd_retried
                stats["rd_expired"] = state["stats"]["rd_expired"] + rd_expired
            for s in agg.stat_names:
                stats[f"agg_{s}"] = state["stats"][f"agg_{s}"] + agg_tel[s]
            new_state = {
                **state,
                "params": params, "hist": hist, "sched": sched, "ev": ev,
                "clock": new_clock, "version": version, "stats": stats,
            }
            if hb_timeout > 0:
                new_state["hb"] = hb
            if have_faults:
                new_state["faults"] = fstate
            if have_def:
                new_state["defense"] = dstate
            if rd_on:
                new_state["rd"] = rd
            if tiered:
                new_state["tier_acc"] = update_tier_accum(
                    state["tier_acc"], send, assign_dev
                )
        state = constrain_state(new_state)
        aux = {
            "send": send,
            "loss": mean_loss,
            "buffer_fill": valid.astype(jnp.int32).sum(),
            "clock": new_clock,
            "version": version,
        }
        return state, aux

    return init_state, step

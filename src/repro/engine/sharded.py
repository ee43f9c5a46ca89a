"""Mesh-sharded asynchronous engine: the fleet state split across devices.

``AsyncEngine`` holds every per-client array — the ``(n,)`` event-engine
vectors, the policy ages, persistent speeds, the selection/load
accumulators, and the client data shards — on a single device, which caps
the fleet at one device's memory. ``ShardedAsyncEngine`` is the same
engine (same step math, same RNG schedule, the identical
``_make_async_step`` body) with that state laid out over a 1-D ``fleet``
device mesh:

  * **sharded** over ``fleet``: ``ev`` (completion times, dispatch
    versions, availability, dropout, last-done), ``sched`` ages,
    ``speed``, the ``load_acc`` per-client last-selection vector, and
    ``task.client_data`` — every array with a leading client axis;
  * **replicated**: the global params, the ``max_versions`` ring buffer
    of retained models, the run key, and all scalar telemetry.

The one operation that fundamentally crosses shards is the buffer pop.
It runs through ``core.distributed.sharded_next_k_events``: each shard
extracts its local top-B earliest completions, the ``devices x B``
candidates are ``all_gather``-ed, and a single stable merge picks the
global B — O(devices * B) communication per step instead of
materializing the ``(n,)`` completion-time vector on one device. The
decentralized Markov admission step stays elementwise over the shard
(zero cross-device traffic — the paper's coordination-free property,
realized in the partitioning), while scalar statistics and the load
accumulators reduce with the all-reduces GSPMD inserts for ``jnp.sum``
over sharded arrays.

**Bit-for-bit equivalence.** Every random draw keeps the exact ``(n,)``
shape and key schedule of the single-device engine, jit results are
sharding-independent, and all cohort-sized ``(B,)`` intermediates are
pinned to a replicated layout (so floating-point reduction order over the
cohort cannot drift). The engine therefore reproduces ``AsyncEngine``
exactly — same selections, same losses, same final params — for the same
``RunConfig`` seed, pinned per-step and chunked by
``tests/test_sharded_engine.py``. The ``(n,)``-wide float sums folded
into the load accumulators are sums of integer-valued float32 and stay
exact under any partial-sum order at test scales.

Shard counts must divide ``n_clients`` so every device owns an equal
client block (``mesh_shards=0`` auto-detects: the largest divisor of the
fleet size at most the local device count). On CPU,
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` fakes an 8-device
mesh — the recipe the sharded benchmarks and CI smoke job use. The whole
sharded carry runs inside the donated ``ChunkRunner`` scan, so chunked
multi-device execution still performs one host transfer per chunk.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import distributed as dist
from repro.core.selection import Policy
from repro.engine.aggregators import Aggregator
from repro.engine.async_engine import AsyncEngine, _make_async_step
from repro.engine.cohort import cohort_layout, make_aggregate
from repro.engine.config import RunConfig
from repro.fl.task import FLTask
from repro.sim import events as ev_mod

# state entries whose leading-``n`` leaves shard over the fleet axis
# ("hb" heartbeats and the "tier_acc" per-client last-selection vector
# are (n,)-leading too; their (E,) per-tier moments stay replicated via
# the shape[0] == n check in fleet_state_sharding — same check that
# keeps the fault sets' scalar "injected" counters replicated while
# their (n,) prone masks and the re-dispatch deadline vectors shard —
# and the defense tier's scalar counters/mtd level replicated while its
# (n,) reputation/status vectors shard)
FLEET_STATE_KEYS = ("ev", "sched", "speed", "load_acc", "hb", "tier_acc",
                    "faults", "rd", "defense")


def per_device_state_bytes(state, dev) -> int:
    """Measured bytes of a state pytree resident on device ``dev`` — the
    sharded-vs-single-device footprint the benchmarks and the engine's
    accounting report. Typed PRNG key arrays hide their buffer (their
    ``nbytes`` is not exposed); they are probed for explicitly and
    counted as 0, which is negligible — any other failure to read a
    shard's size is a real bug and raises."""
    total = 0
    for leaf in jax.tree.leaves(state):
        dt = getattr(leaf, "dtype", None)
        if dt is not None and jax.dtypes.issubdtype(dt, jax.dtypes.prng_key):
            continue
        for shard in getattr(leaf, "addressable_shards", []):
            if shard.device == dev:
                total += shard.data.nbytes
    return total


def fleet_state_sharding(mesh: Mesh, n: int, state: Dict, axis: str) -> Dict:
    """A matching tree of ``NamedSharding``s for an engine state pytree:
    leaves with a leading client axis under the per-client entries get
    ``P(axis)``, everything else (params, ring buffer, scalars, the run
    key) is replicated."""
    fleet = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())

    def leaf_spec(is_fleet):
        def spec(x):
            if is_fleet and getattr(x, "ndim", 0) >= 1 and x.shape[0] == n:
                return fleet
            return rep

        return spec

    return {
        key: jax.tree.map(leaf_spec(key in FLEET_STATE_KEYS), sub)
        for key, sub in state.items()
    }


def require_cohort_mesh(shards: int, what: str) -> None:
    """``shard_cohort=True`` on a 1-device mesh would be a silent no-op
    (the "sharded" cohort is the whole cohort) — reject it loudly."""
    if shards < 2:
        raise ValueError(
            f"shard_cohort=True but {what} resolves to a {shards}-device "
            "mesh — cohort-parallel execution needs >= 2 devices. On CPU, "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 fakes an "
            "8-device mesh; otherwise drop shard_cohort."
        )


def make_sharded_eval(task: FLTask, mesh: Mesh, axis: str):
    """Eval with the held-out batch axis sharded over ``axis`` (params
    replicated): each device scores ``1/devices`` of the eval set and the
    metric reductions all-reduce. Returns None — caller falls back to the
    replicated ``task.eval_fn`` — when the task lacks the batched-eval
    interface (``eval_data``/``eval_batch_fn``) or the eval prefix does
    not divide the mesh. Metrics are allclose to, not bitwise identical
    with, the replicated eval (reduction order differs)."""
    if task.eval_data is None or task.eval_batch_fn is None:
        return None
    leaves = jax.tree.leaves(task.eval_data)
    n_eval = leaves[0].shape[0]
    devices = mesh.shape[axis]
    if n_eval % devices or any(
        getattr(a, "ndim", 0) < 1 or a.shape[0] != n_eval for a in leaves
    ):
        return None
    data = jax.device_put(
        task.eval_data, NamedSharding(mesh, P(axis))
    )
    fn = jax.jit(task.eval_batch_fn)
    return lambda params: fn(params, data)


class ShardedAsyncEngine(AsyncEngine):
    """``AsyncEngine`` with the fleet state sharded over a device mesh.

    Drop-in behind the ``Engine`` protocol: ``make_engine`` routes here
    whenever ``RunConfig.mesh_shards`` is set (0 = auto-detect devices).
    An explicit ``mesh`` overrides the config-driven one (its single axis
    size must divide ``n_clients``).
    """

    def __init__(
        self,
        task: FLTask,
        cfg: RunConfig,
        policy: Optional[Policy] = None,
        aggregator: Optional[Aggregator] = None,
        mesh: Optional[Mesh] = None,
    ):
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    f"ShardedAsyncEngine needs a 1-D mesh, got axes "
                    f"{mesh.axis_names}"
                )
            self.fleet_axis = mesh.axis_names[0]
            shards = mesh.shape[self.fleet_axis]
            if cfg.n_clients % shards:
                raise ValueError(
                    f"mesh has {shards} devices but n_clients="
                    f"{cfg.n_clients} is not divisible by it"
                )
            self.mesh = mesh
        else:
            shards = dist.resolve_fleet_shards(
                cfg.n_clients, cfg.mesh_shards or 0, len(jax.devices())
            )
            self.fleet_axis = dist.FLEET_AXIS
            self.mesh = dist.fleet_mesh(shards, self.fleet_axis)
        self.mesh_shards = shards
        if cfg.shard_cohort:
            require_cohort_mesh(shards, f"mesh_shards={cfg.mesh_shards}")
        # client data is per-client state too — shard its leading axis
        data_spec = jax.tree.map(
            lambda a: NamedSharding(
                self.mesh,
                P(self.fleet_axis)
                if a.shape[:1] == (cfg.n_clients,)
                else P(),
            ),
            task.client_data,
        )
        task = dataclasses.replace(
            task, client_data=jax.device_put(task.client_data, data_spec)
        )
        self._sharded_eval = (
            make_sharded_eval(task, self.mesh, self.fleet_axis)
            if cfg.shard_cohort else None
        )
        super().__init__(task, cfg, policy=policy, aggregator=aggregator)

    def evaluate(self, state: Dict) -> Dict:
        if self._sharded_eval is not None:
            return self._sharded_eval(self.eval_params(state))
        return super().evaluate(state)

    def _build_step(self):
        cfg = self.cfg
        next_k = dist.sharded_next_k_events(
            self.mesh, cfg.n_clients, cfg.resolved_buffer_size(),
            axis=self.fleet_axis,
        )

        def pop(ev):
            t, idx = next_k(ev["t_done"])
            return ev_mod.apply_pop(ev, t, idx)

        def constrain_state(state):
            return jax.tree.map(
                jax.lax.with_sharding_constraint,
                state,
                fleet_state_sharding(
                    self.mesh, cfg.n_clients, state, self.fleet_axis
                ),
            )

        # cohort-parallel: (B,) intermediates lay out over the mesh — each
        # device gathers, trains, and accumulates only its B/devices cohort
        # slice, and the aggregate merges by one psum (allclose, not
        # bitwise, to the replicated layout: cross-device reduction order
        # differs). Otherwise they stay replicated, bit-exact to one device
        axis = self.fleet_axis if cfg.shard_cohort else None
        return _make_async_step(
            self.task, cfg, self.policy, self.aggregator, self.profile,
            pop=pop, cohort_layout=cohort_layout(self.mesh, axis),
            constrain_state=constrain_state,
            aggregate=make_aggregate(
                self.aggregator, self.topo, cfg.n_clients,
                self.mesh if axis else None, axis,
            ),
            cohort_pad=dist.cohort_padding(
                cfg.resolved_buffer_size(), self.mesh_shards
            ) if axis else 0,
            topo=self.topo, faults=self.fault_set, defense=self.defense,
        )

    def init(self) -> Dict:
        state = super().init()
        return jax.device_put(
            state,
            fleet_state_sharding(
                self.mesh, self.cfg.n_clients, state, self.fleet_axis
            ),
        )

    def per_device_state_bytes(self, state: Dict) -> int:
        """Measured bytes of the engine state resident on one device —
        the sharded-vs-single-device memory comparison the benchmarks
        report."""
        return per_device_state_bytes(state, self.mesh.devices.flat[0])

    def progress_line(self, rec, elapsed: float) -> str:
        return (
            f"  [{self.policy.name}/{self.profile.name}"
            f"/x{self.mesh_shards}] "
            f"step {rec.round:4d} t={rec.clock:9.2f}s v={rec.version:4d} "
            f"acc={rec.accuracy:.4f} loss={rec.eval_loss:.4f} ({elapsed:.1f}s)"
        )

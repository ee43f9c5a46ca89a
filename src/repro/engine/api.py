"""The ``Engine`` protocol and the one run loop both engines share.

An engine is anything with ``init/step/run_chunk/finalize`` (plus the
small ``eval_params/record/progress_line`` hooks the loop uses);
``run_engine`` drives it for ``cfg.rounds`` steps in jitted, donated
``lax.scan`` chunks of ``cfg.resolved_steps_per_chunk()`` steps per host
dispatch, collects the selection history (when configured) and eval
records on the configured cadence, and returns a typed ``RunResult`` —
identical schema for sync and async.

The hot loop performs **one host transfer per chunk**: per-step aux
scalars (and, when history is kept, the chunk's stacked selection rows)
come back as one device pytree. Load statistics never require the
materialized history — both engines fold device-resident sufficient
statistics (``core.load_metric``) inside the scan body, so Var[X] is
available even for fleet-scale runs where the ``(rounds, n)`` matrix
could never be stored. Chunked execution is bit-for-bit identical to
per-step execution (``tests/test_engine_chunked.py``), and chunks never
straddle an eval step, so records land on exactly the legacy cadence.

    cfg = RunConfig(mode="async", policy="markov", aggregator="fedbuff")
    result = run_engine(make_engine(task, cfg), progress=True)
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Protocol, Tuple, runtime_checkable

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.engine.config import RoundRecord, RunConfig, RunResult, chunk_plan

# collect the full (steps, n) selection matrix only below this cell count
HISTORY_CELL_CAP = 4_000_000


@runtime_checkable
class Engine(Protocol):
    """The contract ``run_engine`` drives."""

    task: object
    cfg: RunConfig

    def init(self) -> Dict: ...

    def step(self, state: Dict, r: int) -> Tuple[Dict, Dict]: ...

    def run_chunk(
        self, state: Dict, r0: int, length: int, with_history: bool
    ) -> Tuple[Dict, Dict]: ...

    def eval_params(self, state: Dict): ...

    def evaluate(self, state: Dict) -> Dict: ...

    def record(self, r: int, aux: Dict, ev: Dict) -> RoundRecord: ...

    def progress_line(self, rec: RoundRecord, elapsed: float) -> str: ...

    def finalize(self, state, records, sel_hist, wall_time_s) -> RunResult: ...


def make_engine(task, cfg: RunConfig, policy=None, aggregator=None) -> Engine:
    """Instantiate the engine matching ``cfg.mode`` (and, for async runs
    with ``mesh_shards`` set, the fleet-sharded variant)."""
    if cfg.mode == "sync":
        from repro.engine.sync import SyncEngine

        return SyncEngine(task, cfg, policy=policy, aggregator=aggregator)
    if cfg.mesh_shards is not None:
        from repro.engine.sharded import ShardedAsyncEngine

        return ShardedAsyncEngine(task, cfg, policy=policy, aggregator=aggregator)
    from repro.engine.async_engine import AsyncEngine

    return AsyncEngine(task, cfg, policy=policy, aggregator=aggregator)


def keep_history(cfg: RunConfig) -> bool:
    """Whether a run materializes the (rounds, n) selection matrix.

    ``cfg.collect_history`` wins when set; the legacy heuristic otherwise
    (sync runs always kept it, async fleets cap at ``HISTORY_CELL_CAP``
    cells). Load statistics no longer depend on it — the device
    accumulators cover runs of any size.
    """
    if cfg.collect_history is not None:
        return cfg.collect_history
    return cfg.mode == "sync" or cfg.rounds * cfg.n_clients <= HISTORY_CELL_CAP


def run_engine(engine: Engine, progress: bool = False) -> RunResult:
    """Drive an engine for ``cfg.rounds`` steps and package the result.

    Each phase of the loop is a host span in the profiler's trace
    (``jax.profiler.TraceAnnotation``, about a microsecond each when no
    profiler runs): ``run_engine.init`` and ``run_engine.finalize`` around
    the loop, and inside each ``run_engine.chunk`` step span
    ``run_engine.dispatch`` (the chunk's enqueue), ``run_engine.pull``
    (its one host transfer), ``run_engine.history``, and on eval steps
    ``run_engine.evaluate`` and ``run_engine.record``."""
    from repro.engine.chunk import dealias_pytree

    cfg = engine.cfg
    steps = cfg.rounds
    with TraceAnnotation("run_engine.init"):
        state = dealias_pytree(engine.init())
        keep_hist = keep_history(cfg)
        sel_hist: Optional[np.ndarray] = (
            np.zeros((steps, cfg.n_clients), dtype=bool) if keep_hist else None
        )
        records = []
    t0 = time.perf_counter()
    for r0, length, do_eval in chunk_plan(
        steps, cfg.eval_every, cfg.resolved_steps_per_chunk()
    ):
        with StepTraceAnnotation("run_engine.chunk", step_num=r0):
            with TraceAnnotation("run_engine.dispatch"):
                state, aux = engine.run_chunk(state, r0, length, keep_hist)
            with TraceAnnotation("run_engine.pull"):
                # the chunk's one device -> host transfer
                aux = jax.device_get(aux)
            if keep_hist:
                with TraceAnnotation("run_engine.history"):
                    sel_hist[r0:r0 + length] = aux.pop("send")
            if do_eval:
                # engines own their eval: cohort-sharded engines score the
                # held-out set with the eval-batch axis sharded over the mesh
                with TraceAnnotation("run_engine.evaluate"):
                    ev = engine.evaluate(state)
                with TraceAnnotation("run_engine.record"):
                    r = r0 + length - 1
                    rec = engine.record(r, {k: v[-1] for k, v in aux.items()},
                                        ev)
                    records.append(rec)
                    if progress:
                        print(engine.progress_line(
                            rec, time.perf_counter() - t0), flush=True)
    wall_time_s = time.perf_counter() - t0
    with TraceAnnotation("run_engine.finalize"):
        return engine.finalize(state, records, sel_hist, wall_time_s)

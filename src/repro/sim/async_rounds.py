"""Back-compat wrappers over the unified engine (``repro.engine``).

The FedBuff-style buffered asynchronous loop that used to live here is
now ``AsyncEngine`` in ``repro.engine.async_engine``, driven through the
one ``RunConfig``/``RunResult`` contract shared with the sync engine, with
the staleness-discounted delta aggregation factored out into the
``fedbuff`` aggregator. ``run_async_training`` keeps the legacy signature
and returns the legacy history dict, reproducing the pre-refactor loop
bit-for-bit on fixed seeds (pinned by ``tests/test_engine_equivalence.py``).

With the degenerate ``uniform`` latency profile (zero spread, always
available, no dropout) and ``buffer_size = k`` every dispatch completes
inside its own step with staleness 0, and the loop reproduces the
synchronous FedAvg round exactly — the equivalence
``tests/test_async_rounds.py`` pins down.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Union

from repro.core.selection import Policy
from repro.engine.aggregators import staleness_weight  # noqa: F401  (back-compat)
from repro.fl.config import FLConfig
from repro.fl.task import FLTask
from repro.sim import latency as lat_mod

# collect the full (steps, n) selection matrix only below this cell count
# (re-exported for back-compat; the engine's run loop owns the cap now)
HISTORY_CELL_CAP = 4_000_000


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    buffer_size: Optional[int] = None  # aggregation buffer; default fl.k
    staleness_mode: str = "poly"  # poly | const
    staleness_exp: float = 0.5  # weight = (1+s)^(-exp) for mode=poly
    max_versions: int = 8  # ring of retained global models
    profile: Union[str, lat_mod.LatencyProfile] = "lognormal"
    use_kernel: Optional[bool] = None  # None: kernel when fleet is large

    def resolved_profile(self) -> lat_mod.LatencyProfile:
        if isinstance(self.profile, lat_mod.LatencyProfile):
            return self.profile
        return lat_mod.get_profile(self.profile)


def make_async_step(
    task: FLTask, fl: FLConfig, acfg: AsyncConfig, policy: Policy
):
    """Builds (init_state, jitted step) for one async server step (legacy
    helper); ``step(state, key)`` reads ``task.client_data``, as rows
    (``client_rows``), as an argument of the compiled step."""
    import functools

    import jax

    from repro.engine.async_engine import _make_async_step, client_rows
    from repro.engine.config import run_config_from_legacy
    from repro.engine.registry import make_aggregator

    cfg = run_config_from_legacy(fl, acfg)
    agg = make_aggregator(
        "fedbuff", staleness_mode=acfg.staleness_mode,
        staleness_exp=acfg.staleness_exp,
    )
    init_state, step = _make_async_step(
        task, cfg, policy, agg, acfg.resolved_profile()
    )
    return init_state, functools.partial(jax.jit(step),
                                         data=client_rows(task.client_data))


def run_async_training(
    task: FLTask,
    fl: FLConfig,
    acfg: Optional[AsyncConfig] = None,
    policy: Optional[Policy] = None,
    progress: bool = False,
) -> Dict:
    """Full asynchronous FL run. ``fl.rounds`` counts *server steps* (one
    buffer flush each). Returns history + load stats on both clocks."""
    from repro.engine.api import run_engine
    from repro.engine.async_engine import AsyncEngine
    from repro.engine.config import run_config_from_legacy

    acfg = acfg or AsyncConfig()
    cfg = run_config_from_legacy(fl, acfg)
    res = run_engine(AsyncEngine(task, cfg, policy=policy), progress=progress)
    return {
        "history": res.history(),
        "selection": res.selection,
        "wall_stats": res.wall_stats,
        "params": res.params,
        "wall_time_s": res.wall_time_s,
    }

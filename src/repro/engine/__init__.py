"""Unified federated engine API.

One contract for every run, sync or async:

  * **Registries** — ``@register_policy`` / ``@register_aggregator`` plus
    ``make_policy`` / ``make_aggregator`` dispatch: a new scheduling
    policy or aggregation rule is a registry entry, not a fork of a round
    loop.
  * **Protocols** — ``Policy`` (explicit state pytree, ``init/step``),
    ``Aggregator`` (pure ``weigh/init/accumulate/finalize``), ``Engine``
    (``init/step/finalize``).
  * **Contract** — ``RunConfig`` in, ``RunResult``/``RoundRecord`` out,
    with one JSON-safe serializer (``to_jsonable``/``dump_json``).

The paper's policies live in ``repro.core.selection`` and register
themselves on import; ``fedavg``/``fedbuff``/``fedprox`` aggregators in
``repro.engine.aggregators``. ``make_engine`` / ``run_engine`` are the
one way to build and start a run.
"""
from repro.engine.registry import (  # noqa: F401
    aggregator_names,
    make_aggregator,
    make_policy,
    policy_names,
    register_aggregator,
    register_policy,
)
from repro.engine.serialize import dump_json, to_jsonable  # noqa: F401
from repro.engine.aggregators import Aggregator, staleness_weight  # noqa: F401
from repro.engine import robust  # noqa: F401  (registers robust aggregators)
from repro.engine.config import (  # noqa: F401
    RoundRecord,
    RunConfig,
    RunResult,
    rounds_to_target,
)
from repro.engine.api import (  # noqa: F401
    HISTORY_CELL_CAP,
    Engine,
    make_engine,
    run_engine,
)
from repro.engine.sync import SyncEngine  # noqa: F401
from repro.engine.async_engine import AsyncEngine  # noqa: F401
from repro.engine.sharded import ShardedAsyncEngine  # noqa: F401
from repro.core.selection import Policy  # noqa: F401  (registers built-ins)

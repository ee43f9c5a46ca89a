"""Device self time a step of the fleet scheduler in the engine's chunk
program: the scopes ``admission``, ``dispatch``, ``pop`` and
``load_metric`` (``bench/scopes.py``)."""
from bench.scopes import readings


def read(obs):
    return readings(obs.trace, obs.steps).get("sched_ms")

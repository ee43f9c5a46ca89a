"""Device self time a step of the aggregation and the version ring (scope
``aggregate``) in the engine's chunk program."""
from bench.scopes import readings


def read(obs):
    return readings(obs.trace, obs.steps).get("agg_ms")

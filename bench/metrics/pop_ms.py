"""Device self time a step of the event pop (scope ``pop``: the
``event_topk`` kernel and the event state's update) in the engine's chunk program;
nothing where the engine pops no events (sync rounds)."""
from bench.scopes import readings


def read(obs):
    return readings(obs.trace, obs.steps).get("pop_ms")

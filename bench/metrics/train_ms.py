"""Device self time a step of the cohort's local training (scope
``local_train``, with its gathers of the cohort's data) in the engine's
chunk program."""
from bench.scopes import readings


def read(obs):
    return readings(obs.trace, obs.steps).get("train_ms")

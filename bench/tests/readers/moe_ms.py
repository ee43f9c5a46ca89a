"""Device self time a step under a model's own ``moe`` scope, nested in
the engine's ``local_train``: the reader a configuration brings as one new
file."""
from bench.scopes import under_ms


def read(obs):
    return under_ms(obs.trace, "moe", obs.steps)

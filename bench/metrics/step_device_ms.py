"""Device time of the engine's chunk programs (``jit_chunk``) per step."""

PROGRAM = "jit_chunk"


def read(obs):
    s = obs.trace.program_s.get(PROGRAM)
    return None if not s else 1e3 * s / obs.steps

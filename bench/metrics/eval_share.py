"""Share of the traced window the device spent in the eval program
(``jit_eval_scan``)."""

PROGRAM = "jit_eval_scan"


def read(obs):
    s = obs.trace.program_s.get(PROGRAM)
    return None if not s else 100.0 * s / obs.trace.window_s

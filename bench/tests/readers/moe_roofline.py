"""The ``moe`` scope's share of its roofline: the work its expert matrix
products need (forward and backward of a ``d`` x ``f`` product for every
token the window's aggregated clients trained on, the weights and their
gradient read or written once a step), from the configuration's widths
and the window's counts, over the device time under the scope."""
from bench.metrics import roofline_share


def read(obs):
    w = obs.config["widths"]
    tokens = obs.local_steps * obs.local_batch
    flops = 3 * 2 * tokens * w["d"] * w["f"]
    bytes_ = obs.local_steps * 3 * 4 * w["d"] * w["f"]
    got = roofline_share(obs, obs.trace.under_s.get("jit_chunk", {}).get("moe"),
                         flops, bytes_)
    return None if got is None else got[0]

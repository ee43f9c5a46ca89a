"""The whole step's share of the chips' bf16 peak: the CNN FLOPs the
window required (forward and backward of every example trained by a
client whose update was aggregated, forward of every eval example), per
second of the traced window, over chips x peak. Padded cohort slots and
recomputation do not count. The bf16 peak is the ceiling because float32
matrix products run at the default precision, one bf16 pass on a TPU."""


def read(obs):
    peak = obs.chips * obs.peak["bf16_flops_per_s"]
    return 100.0 * obs.flops / obs.trace.window_s / peak

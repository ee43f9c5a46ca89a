"""Per-layer metrics: one reader per metric, ``bench/metrics/<name>.py``.

Each reader is ``read(obs: Observed) -> float | None``: the metric from
the reduced trace of the traced window and the run's counts, or None where
the window holds nothing for it to read (the harness then leaves the
metric out of the line).

A reader of a model's own scope takes its time with
``bench.scopes.under_ms``; a kernel's roofline share comes from
``roofline_share``, with the operations and bytes the algorithm needs
computed from shapes by the model module, from ``obs.config`` and the
window's counts.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Dict, Optional, Tuple

from bench.trace import Reduced

METRICS_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Observed:
    trace: Reduced
    steps: int  # server steps (or rounds) in the traced window
    flops: float  # FLOPs the window's training and evals required
    chips: int
    peak: dict  # one chip's peaks (bench/peaks.json)
    n_clients: int
    buffer: Optional[int]  # async buffer size; None for sync rounds
    config: Dict  # the configuration file, as the model module takes it
    # local SGD steps the window's aggregated clients ran, and the
    # examples in each
    local_steps: float
    local_batch: int
    eval_examples: int  # examples the window's evals ran forward


def roofline_share(obs: Observed, seconds, flops: float, bytes_: float
                   ) -> Optional[Tuple[float, str]]:
    """A kernel's share of its roofline, in %, and what bounds it
    (``"compute"`` or ``"memory"``): the least time the chips could take
    for ``flops`` operations and ``bytes_`` of HBM traffic, the larger of
    flops over the chips' bf16 peak (float32 products at the default
    precision run as one bf16 pass) and bytes over their HBM bandwidth,
    over the ``seconds`` the kernel took (a mean over chips, as the
    trace's times are). ``flops`` and ``bytes_`` are what the
    algorithm needs on all the chips used, computed from shapes, so the
    share reads the same whatever implements the kernel. None where the
    kernel did not run or needs no work: a share is never 0."""
    if not seconds or not (flops > 0 or bytes_ > 0):
        return None
    compute = flops / (obs.chips * obs.peak["bf16_flops_per_s"])
    memory = bytes_ / (obs.chips * obs.peak["hbm_bytes_per_s"])
    return (100.0 * max(compute, memory) / seconds,
            "compute" if compute >= memory else "memory")


def read(name: str, obs: Observed) -> Optional[float]:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", METRICS_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)

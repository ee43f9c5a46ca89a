"""Per-layer metrics: one reader per metric, ``bench/metrics/<name>.py``.

Each reader is ``read(obs: Observed) -> float | None``: the metric from
the reduced trace of the traced window and the run's counts, or None where
the window holds nothing for it to read (the harness then leaves the
metric out of the line).
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Optional

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


def read(name: str, obs: Observed) -> Optional[float]:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", METRICS_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)

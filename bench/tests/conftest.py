"""Tiny versions of the benchmark's cells, for runs on the CPU.

A tiny cell is a real cell of ``BENCHMARK.json`` with its fleet, data and
periods cut so a whole run (set-up, window, reference, check) takes
seconds; widths, the policy, the aggregator and the limits are the real
cell's.
"""
import copy
import dataclasses
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# CPU programs stay out of the checkout's compile cache, which the chip's
# runs read
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

TINY = {
    # 60 examples in batches of 5: each client takes the cell's 60 local
    # SGD steps a round
    "sync-paper": {"dataset": {"examples_per_client": 60, "test": 500},
                   "run": {"n_clients": 10, "k": 2, "batch_size": 5}},
}


def tiny_cell(name: str):
    from bench.cell import load_cell

    cell = load_cell(name)
    cut = TINY[name]
    config = copy.deepcopy(cell.config)
    config["dataset"].update(cut["dataset"])
    config["run"].update(cut["run"])
    traffic = copy.deepcopy(cell.traffic)
    traffic["run"].update(cut.get("traffic", {}))
    # compare one chunk of the cut traffic
    check = {**cell.check, "compare_steps": traffic["run"]["steps_per_chunk"]}
    return dataclasses.replace(cell, config=config, traffic=traffic,
                               check=check)


@pytest.fixture
def tiny():
    return tiny_cell

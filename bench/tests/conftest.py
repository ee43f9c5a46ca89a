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
    # the cell's 4 examples a client and buffer of 10, over 512 clients;
    # 8 steps a dispatch and an eval after each
    "fleet-b10": {"dataset": {"test": 500},
                  "run": {"n_clients": 512, "k": 77},
                  "traffic": {"steps_per_chunk": 8, "eval_every": 8}},
}
# The bf16 control loses the updates that fall under half a bfloat16 ulp of
# a weight, which shows at a cell's own local work: sync-paper's control is
# cut to 3 clients that each keep 600 examples in batches of 50.
CONTROL = {
    "sync-paper": {"dataset": {"examples_per_client": 600, "test": 500},
                   "run": {"n_clients": 3, "k": 1, "batch_size": 50}},
}


def tiny_cell(name: str, control: bool = False):
    from bench.cell import load_cell

    cell = load_cell(name)
    cut = CONTROL.get(name, TINY[name]) if control else TINY[name]
    config = copy.deepcopy(cell.config)
    config["dataset"].update(cut["dataset"])
    config["run"].update(cut["run"])
    traffic = copy.deepcopy(cell.traffic)
    traffic["run"].update(cut.get("traffic", {}))
    # compare one chunk of the cut traffic; a fixed window is one period,
    # whose work the cut fleet sets
    check = {**cell.check, "compare_steps": traffic["run"]["steps_per_chunk"]}
    check.pop("window_work", None)
    if "window_steps" in check:
        check["window_steps"] = traffic["run"]["eval_every"]
    return dataclasses.replace(cell, config=config, traffic=traffic,
                               check=check)


@pytest.fixture
def tiny():
    return tiny_cell


# McMahan et al.'s 2NN has 200 units a hidden layer
MLP_WIDTHS = {"image_size": 28, "channels": 1, "num_classes": 10,
              "hidden": 200}


@pytest.fixture
def mlp_cell(tiny, monkeypatch):
    """``mlp_cell(name)``: the tiny cell with the two-layer MLP module
    (``toy_mlp.py``) in the CNN's place."""
    import toy_mlp  # bench/tests/toy_mlp.py

    monkeypatch.setitem(sys.modules, "bench.models.toy_mlp", toy_mlp)

    def make(name):
        cell = tiny(name)
        config = {**cell.config, "model": "toy_mlp", "widths": MLP_WIDTHS}
        return dataclasses.replace(cell, config=config)

    return make

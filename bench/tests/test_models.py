"""A configuration brings its own model through ``bench/models``: a second
model module (``toy_mlp.py``, a two-layer MLP) runs a whole run of each
tiny cell, and the plain reference, with no edit to a file of the
harness."""
import jax.numpy as jnp
import pytest

from bench import oracle
from bench import run as bench_run
from bench.reference import Reference
import toy_mlp  # bench/tests/toy_mlp.py

SEED = 2**33 + 29


@pytest.mark.parametrize("name", ["sync-paper", "fleet-b10"])
def test_a_second_model_runs_through_the_unedited_harness(mlp_cell, name):
    cell = mlp_cell(name)
    out = bench_run.run(cell, SEED, 0.2, False, require_chip=False)
    assert out["correct"], out["rows"]
    assert out["window"]["compiles"] == 0
    # the program trained the MLP: its params are the MLP's, moved
    assert out["readings"]["update_gap"] < 1e-3


def test_the_reference_takes_the_model_from_its_module(mlp_cell):
    cell = mlp_cell("sync-paper")
    data, model, _, engine, run_seed = bench_run.build(cell, SEED)
    assert model is toy_mlp
    steps, chunk = engine.cfg.rounds, engine.cfg.resolved_steps_per_chunk()
    ref = Reference(model, cell.config, cell.traffic, data, run_seed).follow(
        steps, chunk)
    assert set(ref["params0"]) == {"fc1", "fc2"}
    assert ref["params0"]["fc1"]["w"].shape == (784, 200)
    low = Reference(model, cell.config, cell.traffic, data, run_seed,
                    jnp.bfloat16).follow(steps, chunk)
    readings = oracle.compare(low, ref)
    assert readings["sel_diff"] == 0
    assert readings["update_gap"] > 0

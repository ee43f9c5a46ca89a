"""The reference trains as many clients together as the chip holds copies
of the model's parameters for (``client_block``): a model at a language
model's widths trains one client at a time, only in the blocks that hold a
selected client, and reads what the whole cohort in one block reads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench import run as bench_run
from bench.reference import Reference, client_block, cohort_width

SEED = 2**33 + 29


def _params(*sizes, dtype=jnp.float32):
    return {f"w{i}": jax.ShapeDtypeStruct((n,), dtype)
            for i, n in enumerate(sizes)}


@pytest.mark.parametrize("sizes,dtype,block", [
    ((1_663_370,), jnp.float32, 125),  # the paper's CNN
    ((1_663_370,), jnp.bfloat16, 125),  # its control
    ((80_000_000, 20_000_000), jnp.float32, 2),
    ((268_435_456,), jnp.float32, 1),  # four copies fill the budget
    ((534_600_000,), jnp.float32, 1),  # DeepSeek-V2-Lite, cut
])
def test_the_block_follows_the_parameters_bytes(sizes, dtype, block):
    assert client_block(_params(*sizes, dtype=dtype)) == block


def _run(cell):
    return bench_run.run(cell, SEED, 0.2, False, require_chip=False)


@pytest.mark.parametrize("name", ["sync-paper", "fleet-b10"])
def test_a_block_of_one_reads_what_the_default_block_reads(mlp_cell, name,
                                                           monkeypatch):
    whole = _run(mlp_cell(name))
    monkeypatch.setattr(reference, "CLIENT_BLOCK", 1)
    one = _run(mlp_cell(name))
    assert one["correct"], one["rows"]
    assert whole["correct"], whole["rows"]
    a, b = one["readings"], whole["readings"]
    assert a["sel_diff"] == b["sel_diff"]
    # a block of one sums the losses in another order: a reading of 0
    # can become a float32 rounding of the loss (9.3e-8 on fleet-b10)
    for key in ("update_gap", "update_dist", "loss_gap"):
        assert a[key] == pytest.approx(b[key], rel=1e-5, abs=1e-6), key


class Counted:
    """A model module whose ``loss`` counts the examples it trains on."""

    def __init__(self, model):
        self._model = model
        self.examples = 0

    def __getattr__(self, name):
        return getattr(self._model, name)

    def _seen(self, y):
        self.examples += int(np.size(y))

    def loss(self, p, x, y):
        jax.debug.callback(self._seen, y)
        return self._model.loss(p, x, y)


@pytest.mark.parametrize("block", [1, 3, None])
def test_a_small_block_trains_only_the_blocks_of_selected_slots(mlp_cell,
                                                                 block,
                                                                 monkeypatch):
    if block is not None:
        monkeypatch.setattr(reference, "CLIENT_BLOCK", block)
    cell = mlp_cell("sync-paper")
    data, model, _, engine, run_seed = bench_run.build(cell, SEED)
    steps, chunk = engine.cfg.rounds, engine.cfg.resolved_steps_per_chunk()
    counted = Counted(model)
    ref = Reference(counted, cell.config, cell.traffic, data,
                    run_seed).follow(steps, chunk)
    jax.effects_barrier()
    run = {**cell.config["run"], **cell.traffic["run"]}
    width = cohort_width(run["n_clients"], run["k"])
    examples = int(data.x.shape[1])
    batch = min(run["batch_size"], examples)
    a_client = run["local_epochs"] * (examples // batch) * batch
    selected = np.minimum(np.asarray(ref["sel"]).sum(1), width)
    assert 0 < selected.sum() < steps * width  # idle slots to skip
    blk = min(width, block or reference.CLIENT_BLOCK)
    assert counted.examples == a_client * (blk * -(-selected // blk)).sum()

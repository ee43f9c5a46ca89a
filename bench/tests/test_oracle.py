"""The correctness check passes a sound run and fails each fault a cell
can have, and its control.

Every test drives a whole run of a tiny cell (set-up, window, reference,
check) on the CPU, skipping only the harness's look for a chip. A fault
is planted in the program underneath, before the engine is built."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import oracle
from bench import run as bench_run
from bench.reference import Reference

SEED = 2**33 + 17  # wider than 32 bits: the harness takes any whole number
CELLS = ["sync-paper", "fleet-b10"]


def _run(cell):
    return bench_run.run(cell, SEED, 0.2, False, require_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(tiny, name):
    out = _run(tiny(name))
    assert out["correct"], out["rows"]
    assert out["window"]["compiles"] == 0


def _state_unchanged(monkeypatch):
    from repro.engine import chunk

    call = chunk.ChunkRunner.__call__

    def frozen(self, state, r0, length, with_history):
        keep = jax.tree.map(jnp.copy, state)
        _, aux = call(self, state, r0, length, with_history)
        return keep, aux

    monkeypatch.setattr(chunk.ChunkRunner, "__call__", frozen)


def _half_batch(monkeypatch):
    from bench.models import paper_cnn

    build = paper_cnn.build_task

    def halved(config, data):
        task = build(config, data)
        loss = task.loss_fn

        def first_half(params, batch):
            k = max(batch["y"].shape[0] // 2, 1)
            return loss(params, jax.tree.map(lambda a: a[:k], batch))

        return dataclasses.replace(task, loss_fn=first_half)

    monkeypatch.setattr(paper_cnn, "build_task", halved)


def _answer_altered(monkeypatch):
    """The pop hands back the next client after each one it popped (async);
    the cohort trains the next client after each one selected (sync)."""
    from repro.engine import sync
    from repro.sim import events

    apply_pop = events.apply_pop
    monkeypatch.setattr(events, "apply_pop", lambda ev, t, idx: apply_pop(
        ev, t, (idx + 1) % ev["t_done"].shape[0]))
    cohort = sync.cohort_indices

    def shifted(selected, width):
        idx, w = cohort(selected, width)
        return (idx + 1) % selected.shape[0], w

    monkeypatch.setattr(sync, "cohort_indices", shifted)


def _selection_altered(monkeypatch):
    """The Markov policy selects the next client after each one it drew,
    where the selection is made (both engines)."""
    from repro.core import selection
    from repro.engine import registry

    markov = registry._POLICIES["markov"]

    def shifted(*a, **kw):
        policy = markov(*a, **kw)

        def step(state, key):
            sel, _ = policy.step(state, key)
            sel = jnp.roll(sel, 1)
            return sel, selection._advance(state, sel)

        return dataclasses.replace(policy, step=step)

    monkeypatch.setitem(registry._POLICIES, "markov", shifted)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _selection_altered])
def test_fault_is_not_correct(tiny, monkeypatch, name, fault):
    fault(monkeypatch)
    out = _run(tiny(name))
    assert not out["correct"], out["rows"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(tiny, name):
    """The reference in bfloat16, in the program's place, fails a limit
    at the cell's own learning rate, epochs and batch."""
    cell = tiny(name, control=True)
    data, model, _, engine, run_seed = bench_run.build(cell, SEED)
    steps, chunk = engine.cfg.rounds, engine.cfg.resolved_steps_per_chunk()

    def follow(dtype):
        return Reference(model, cell.config, cell.traffic, data, run_seed,
                         dtype).follow(steps, chunk)

    ref, low = follow(jnp.float32), follow(jnp.bfloat16)
    correct, rows = oracle.judge(oracle.compare(low, ref),
                                 cell.check["limits"])
    assert not correct, rows


def test_judge_holds_each_limited_number_to_its_limit():
    assert oracle.judge({"a": 0.0, "b": 5.0}, {"a": 0.0})[0]
    assert not oracle.judge({"a": 0.0}, {"a": 0.0, "b": 1.0})[0]
    assert not oracle.judge({"a": float("nan")}, {"a": 1.0})[0]
    assert not oracle.judge({"a": 1.5}, {"a": 1.0})[0]

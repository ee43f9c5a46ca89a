"""Golden equivalence for the chunked hot loop: driving an engine in
jitted, donated ``lax.scan`` chunks must be *bit-for-bit* identical to
per-step execution — same selection history, same per-round losses, same
final params — for both engines. The per-step key schedule
``fold_in(k_run, r)`` makes the scan body a pure function of the global
step index, so any numeric drift (op reordering, dtype, key handling) is
a bug, and these tests fail on exact comparison.

Also pins the empty-cohort loss convention: a round/step that aggregates
nothing reports ``train_loss = NaN`` (not a fake near-zero datapoint) in
both engines.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import MNIST_CNN
from repro.core.selection import Policy
from repro.data.synthetic import make_image_dataset
from repro.engine import AsyncEngine, RunConfig, SyncEngine, run_engine
from repro.engine.async_engine import client_rows
from repro.engine.config import chunk_plan

SMALL_CNN = dataclasses.replace(
    MNIST_CNN, name="paper-cnn-mnist-small", image_size=16,
    conv_channels=(8, 16), fc_width=64,
)


@pytest.fixture(scope="module")
def small_task():
    from repro.fl import make_cnn_task

    train, test = make_image_dataset(
        "mnist-small", 10, 16, 1, 600, 500, seed=0, difficulty=0.8
    )
    return make_cnn_task(SMALL_CNN, train, test, n_clients=20)


def _cfg(**kw):
    base = dict(
        n_clients=20, k=4, m=6, policy="markov", rounds=7,
        local_epochs=1, batch_size=10, eval_every=3,
    )
    base.update(kw)
    return RunConfig(**base)


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _per_step_reference(engine, rounds, n):
    """The pre-chunking hot loop: one dispatch + one (n,) host pull per
    step, eval cadence inline."""
    state = engine.init()
    sel = np.zeros((rounds, n), dtype=bool)
    losses = []
    for r in range(rounds):
        state, aux = engine.step(state, r)
        sel[r] = np.asarray(aux["send"])
        losses.append(float(aux["loss"]))
    return state, sel, losses


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_chunked_matches_per_step_bit_for_bit(small_task, mode):
    kw = dict(profile="lognormal", buffer_size=3) if mode == "async" else {}
    cfg = _cfg(mode=mode, **kw)
    make = SyncEngine if mode == "sync" else AsyncEngine

    ref_state, ref_sel, ref_losses = _per_step_reference(
        make(small_task, cfg), cfg.rounds, cfg.n_clients
    )

    # steps_per_chunk=2 against eval_every=3 exercises both chunk lengths
    # (full chunks and eval-boundary remainders) plus the compiled-chunk
    # cache; steps_per_chunk=64 collapses each eval segment to one chunk
    for spc in (1, 2, 64):
        res = run_engine(make(small_task, dataclasses.replace(
            cfg, steps_per_chunk=spc
        )))
        np.testing.assert_array_equal(res.selection, ref_sel, err_msg=f"spc={spc}")
        eval_rounds = [r0 + ln for r0, ln, ev in
                       chunk_plan(cfg.rounds, cfg.eval_every, spc) if ev]
        assert [rec.round for rec in res.records] == eval_rounds
        np.testing.assert_array_equal(
            [rec.train_loss for rec in res.records],
            [ref_losses[r - 1] for r in eval_rounds],
            err_msg=f"spc={spc}",
        )
        _assert_trees_equal(res.params, ref_state["params"])


def test_eval_cadence_identical_to_per_step_rule():
    # the chunk plan's eval chunks must land exactly on the legacy rule:
    # (r + 1) % eval_every == 0 or r == rounds - 1
    for rounds, every, spc in [(7, 3, 2), (10, 4, 64), (5, 1, 2), (6, 10, 4)]:
        legacy = [r for r in range(rounds)
                  if (r + 1) % every == 0 or r == rounds - 1]
        plan = chunk_plan(rounds, every, spc)
        assert sum(ln for _, ln, _ in plan) == rounds
        assert [r0 + ln - 1 for r0, ln, ev in plan if ev] == legacy
        assert all(ln <= spc for _, ln, _ in plan)


def test_collect_history_off_matches_history_run(small_task):
    cfg = _cfg(rounds=6, eval_every=2)
    with_hist = run_engine(SyncEngine(small_task, cfg))
    no_hist = run_engine(SyncEngine(
        small_task, dataclasses.replace(cfg, collect_history=False)
    ))
    assert with_hist.selection is not None and no_hist.selection is None
    np.testing.assert_array_equal(
        [r.train_loss for r in with_hist.records],
        [r.train_loss for r in no_hist.records],
    )
    _assert_trees_equal(with_hist.params, no_hist.params)
    # device accumulators reproduce the history-derived load statistics
    for key, val in with_hist.load_stats.items():
        np.testing.assert_allclose(
            no_hist.load_stats[key], val, rtol=1e-5, err_msg=key
        )


def _never_send_policy(n):
    def init(key, n_=n):
        return {"ages": jnp.zeros((n_,), jnp.int32),
                "round": jnp.zeros((), jnp.int32)}

    def step(state, key):
        return jnp.zeros((n,), jnp.bool_), {**state, "round": state["round"] + 1}

    return Policy("never_send", init, step, exact_k=False)


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_empty_cohort_reports_nan_loss(small_task, mode):
    kw = dict(profile="lognormal", buffer_size=3) if mode == "async" else {}
    cfg = _cfg(mode=mode, rounds=2, eval_every=1, **kw)
    make = SyncEngine if mode == "sync" else AsyncEngine
    res = run_engine(make(small_task, cfg, policy=_never_send_policy(20)))
    assert all(np.isnan(rec.train_loss) for rec in res.records)
    assert not res.selection.any()


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_client_data_is_a_chunk_argument(small_task, mode):
    """Client data reaches the compiled chunk as an argument. Closed
    over, it would be an XLA constant of every chunk program: gigabytes
    at fleet scale, minutes of compile, too large for the compile cache.
    The async engine passes it as rows (``client_rows``)."""
    kw = dict(profile="lognormal", buffer_size=3) if mode == "async" else {}
    make = SyncEngine if mode == "sync" else AsyncEngine
    engine = make(small_task, _cfg(mode=mode, **kw))
    lowered = engine._chunk.lower(engine.init(), 0, 2, False)
    data_args = jax.tree.leaves(lowered.args_info[0][1])
    given = (client_rows(small_task.client_data) if mode == "async"
             else small_task.client_data)
    data = jax.tree.leaves(given)
    assert [a.shape for a in data_args] == [d.shape for d in data]
    x_types = ["tensor<" + "x".join(map(str, x.shape)) + "xf32>"
               for x in (given["x"], small_task.client_data["x"])]
    assert not [line for line in lowered.as_text().splitlines()
                if "stablehlo.constant" in line
                and any(t in line for t in x_types)]

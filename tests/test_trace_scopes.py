"""The engine names its own phases in the profiler's trace.

Device side: each engine step lowers its phases under ``jax.named_scope``
blocks (``admission``, ``dispatch``, ``pop``, ``local_train``,
``aggregate``, ``load_metric``), so every op of the compiled chunk carries
its phase in its name path. Host side: ``run_engine`` wraps each phase of
its loop in a ``jax.profiler`` span. Neither changes what is computed: a
run under the profiler returns the same bits as one without it.
"""
import dataclasses
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs.paper_cnn import MNIST_CNN
from repro.data.synthetic import make_image_dataset
from repro.engine import AsyncEngine, RunConfig, SyncEngine, run_engine
from repro.engine.api import keep_history
from repro.engine.config import chunk_plan

TINY_CNN = dataclasses.replace(
    MNIST_CNN, name="paper-cnn-mnist-tiny", image_size=8,
    conv_channels=(4, 8), fc_width=16,
)
SYNC_SCOPES = ("admission", "local_train", "aggregate", "load_metric")
ASYNC_SCOPES = ("admission", "dispatch", "pop", "local_train", "aggregate",
                "load_metric")


@pytest.fixture(scope="module")
def tiny_task():
    from repro.fl import make_cnn_task

    train, test = make_image_dataset(
        "mnist-tiny", 10, 8, 1, 240, 100, seed=0, difficulty=0.8
    )
    return make_cnn_task(TINY_CNN, train, test, n_clients=12)


def _cfg(mode: str, **kw) -> RunConfig:
    base = dict(n_clients=12, k=3, m=5, policy="markov", rounds=5,
                local_epochs=1, batch_size=10, eval_every=2,
                steps_per_chunk=2, mode=mode)
    if mode == "async":
        base.update(profile="lognormal", buffer_size=3)
    base.update(kw)
    return RunConfig(**base)


def _engine(task, mode: str, **kw):
    make = SyncEngine if mode == "sync" else AsyncEngine
    return make(task, _cfg(mode, **kw))


@pytest.fixture(scope="module")
def lowered_text(tiny_task):
    texts = {}

    def text(mode):
        if mode not in texts:
            engine = _engine(tiny_task, mode)
            lowered = engine._chunk.lower(engine.init(), 0, 2, False)
            texts[mode] = lowered.as_text(debug_info=True)
        return texts[mode]

    return text


@pytest.mark.parametrize("mode,scope", [("sync", s) for s in SYNC_SCOPES]
                         + [("async", s) for s in ASYNC_SCOPES])
def test_chunk_ops_carry_their_phase_scope(lowered_text, mode, scope):
    # an op's location is its name path: "admission/add", ".../pop/..."
    assert re.search(rf'["/]{scope}/', lowered_text(mode))


def _host_spans(log_dir: str):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names += [ev.name for ev in line.events
                          if ev.name.startswith("run_engine.")]
    return names


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_run_engine_spans_each_phase_and_changes_no_bit(tiny_task, tmp_path,
                                                        mode):
    plain = run_engine(_engine(tiny_task, mode))
    engine = _engine(tiny_task, mode)
    with jax.profiler.trace(str(tmp_path)):
        traced = run_engine(engine)
    names = _host_spans(str(tmp_path))
    plan = chunk_plan(engine.cfg.rounds, engine.cfg.eval_every,
                      engine.cfg.resolved_steps_per_chunk())
    evals = sum(1 for _, _, do_eval in plan if do_eval)
    counts = {n: names.count(f"run_engine.{n}") for n in
              ("init", "chunk", "dispatch", "pull", "history", "evaluate",
               "record", "finalize")}
    assert counts == {"init": 1, "chunk": len(plan), "dispatch": len(plan),
                      "pull": len(plan),
                      "history": len(plan) if keep_history(engine.cfg) else 0,
                      "evaluate": evals, "record": evals, "finalize": 1}
    for a, b in zip(jax.tree.leaves(plain.params),
                    jax.tree.leaves(traced.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [r.eval_loss for r in plain.records] == [
        r.eval_loss for r in traced.records]

"""FL integration: end-to-end FedAvg rounds with each policy, aggregation
semantics, empirical load stats, checkpoint round-trip of server state."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import MNIST_CNN
from repro.core import load_metric, make_policy
from repro.data.synthetic import make_image_dataset
from repro.engine import RunConfig, make_engine, run_engine
from repro.fl import make_cnn_task, make_lm_task
from repro.fl.server import broadcast_to_cohort, cohort_indices, fedavg_aggregate


import dataclasses

SMALL_CNN = dataclasses.replace(
    MNIST_CNN, name="paper-cnn-mnist-small", image_size=16, conv_channels=(8, 16),
    fc_width=64,
)


@pytest.fixture(scope="module")
def small_task():
    train, test = make_image_dataset(
        "mnist-small", 10, 16, 1, 600, 500, seed=0, difficulty=0.8
    )
    return make_cnn_task(SMALL_CNN, train, test, n_clients=20)


def _cfg(policy, rounds=8, **kw):
    base = dict(
        n_clients=20, k=4, m=6, policy=policy, rounds=rounds,
        local_epochs=2, batch_size=10, eval_every=rounds,
    )
    base.update(kw)
    return RunConfig(**base)


def _run(task, cfg):
    return run_engine(make_engine(task, cfg))


def test_cohort_indices_padding():
    sel = jnp.array([False, True, False, True, True, False])
    idx, w = cohort_indices(sel, 5)
    assert idx.shape == (5,)
    assert w.sum() == 3
    assert set(np.asarray(idx)[np.asarray(w) > 0].tolist()) == {1, 3, 4}


def test_fedavg_aggregate_weighted_mean():
    g = {"w": jnp.zeros((3,))}
    cohort = {"w": jnp.stack([jnp.ones(3), 3 * jnp.ones(3), 100 * jnp.ones(3)])}
    out = fedavg_aggregate(g, cohort, jnp.array([1.0, 1.0, 0.0]))
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0 * np.ones(3))


def test_fedavg_aggregate_empty_cohort_keeps_params():
    g = {"w": 7 * jnp.ones((3,))}
    cohort = {"w": jnp.zeros((2, 3))}
    out = fedavg_aggregate(g, cohort, jnp.zeros(2))
    np.testing.assert_allclose(np.asarray(out["w"]), 7.0)


def test_fedavg_aggregate_kernel_path_matches():
    key = jax.random.PRNGKey(0)
    g = {"w": jnp.zeros((4, 5))}
    cohort = {"w": jax.random.normal(key, (3, 4, 5))}
    w = jnp.array([1.0, 1.0, 1.0])
    a = fedavg_aggregate(g, cohort, w, use_kernel=False)
    b = fedavg_aggregate(g, cohort, w, use_kernel=True)
    np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]), atol=1e-6)


@pytest.mark.parametrize("policy", ["random", "markov"])
def test_training_improves_accuracy(small_task, policy):
    out = _run(small_task, _cfg(policy)).history()
    accs = out["accuracy"]
    assert accs[-1] > 0.2  # 10-class synthetic after 8 rounds
    assert np.isfinite(out["train_loss"]).all()


@pytest.mark.parametrize("policy", ["oldest_age", "round_robin"])
def test_other_policies_run(small_task, policy):
    out = _run(small_task, _cfg(policy, rounds=3))
    assert np.isfinite(out.history()["train_loss"]).all()


def test_markov_load_stats_in_training(small_task):
    out = _run(small_task, _cfg("markov", rounds=60, local_epochs=1))
    stats = out.load_stats
    # n/k = 5 exactly and m=6 >= 5: the optimal policy is deterministic —
    # every client selected every 5th round, Var*[X] = 0 (Theorem 2)
    v_opt = load_metric.optimal_var(20, 4, 6)
    assert v_opt == pytest.approx(0.0, abs=1e-12)
    assert stats["mean_X"] == pytest.approx(5.0, rel=0.05)
    assert stats["var_X"] == pytest.approx(0.0, abs=0.3)


def test_lm_task_federated():
    """A reduced assigned architecture as the federated workload."""
    from repro.configs import get_arch

    cfg = get_arch("tinyllama-1.1b").reduced()
    task = make_lm_task(cfg, n_clients=8, seq_len=32, docs_per_client=4)
    cfg = RunConfig(n_clients=8, k=2, m=4, policy="markov", rounds=3,
                    local_epochs=1, batch_size=2, lr0=0.05, eval_every=3)
    out = _run(task, cfg)
    assert np.isfinite(out.history()["eval_loss"]).all()


def test_server_state_checkpoint_roundtrip(tmp_path, small_task):
    from repro.checkpoint import load_checkpoint, save_checkpoint

    pol = make_policy("markov", 20, 4, 6)
    key = jax.random.PRNGKey(0)
    params = small_task.init(key)
    sched = pol.init(key, 20)
    state = {"params": params, "sched": sched}
    save_checkpoint(str(tmp_path / "ckpt"), state, step=17)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored, step = load_checkpoint(str(tmp_path / "ckpt"), like)
    assert step == 17
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

"""Quickstart: the paper in 60 seconds.

1. Build the optimal age-dependent Markov policy (Theorem 2).
2. Verify its load-metric variance against theory and random selection.
3. Run a few federated rounds on a synthetic MNIST with both policies.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.configs.paper_cnn import MNIST_CNN
from repro.core import (
    empirical_load_stats,
    load_metric as lm,
    make_policy,
    simulate,
)
from repro.data.synthetic import load_dataset
from repro.engine import RunConfig, make_engine, run_engine
from repro.fl import make_cnn_task

N, K, M = 100, 15, 10  # the paper's simulation setting

# --- 1. the optimal policy --------------------------------------------------
probs = lm.optimal_probs(N, K, M)
print(f"optimal send-probabilities p*_0..p*_{M}: {probs.round(4).tolist()}")
print(f"theory: E[X]={N / K:.3f}, Var*[X]={lm.optimal_var(N, K, M):.4f}, "
      f"random Var[X]={lm.random_selection_var(N, K):.2f}")

# --- 2. Monte-Carlo check ---------------------------------------------------
key = jax.random.PRNGKey(0)
for name in ("random", "markov"):
    hist = simulate(make_policy(name, N, K, M), key, N, 3000)
    s = empirical_load_stats(hist)
    print(f"{name:8s}: E[X]={s['mean_X']:.3f} Var[X]={s['var_X']:.3f} "
          f"cohort {s['mean_cohort']:.1f}±{s['std_cohort']:.1f}")

# --- 3. federated training --------------------------------------------------
train, test = load_dataset("mnist", scale=0.1)
task = make_cnn_task(MNIST_CNN, train, test, N)
for policy in ("random", "markov"):
    cfg = RunConfig(n_clients=N, k=K, m=M, policy=policy, rounds=8,
                    local_epochs=2, batch_size=12, eval_every=4)
    res = run_engine(make_engine(task, cfg), progress=True)
    print(f"{policy}: final acc {res.records[-1].accuracy:.3f}, "
          f"Var[X]={res.load_stats['var_X']:.3f}")

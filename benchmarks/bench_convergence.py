"""Paper Figs. 2-4: FedAvg convergence under random vs Markov selection.

Synthetic stand-ins for MNIST/CIFAR (offline container) with the paper's
setting n=100, k=15, m=10, SGD(lr 0.1, decay 0.998), E=5, B=50. Default
runs are CPU-budget-scaled (fewer rounds, reduced data); --paper-scale
restores the full protocol.
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np

from repro.configs.paper_cnn import CNN_CONFIGS
from repro.core import load_metric as lm
from repro.data.synthetic import load_dataset
from repro.engine import RunConfig, SyncEngine, rounds_to_target, run_engine
from repro.fl import make_cnn_task

# (dataset, noniid, target_acc, paper figure, cpu-budget scale multiplier)
EXPERIMENTS = [
    ("cifar10", False, 0.50, "Fig.2", 0.6),
    ("cifar100", False, 0.15, "Fig.3", 0.6),
    ("mnist", False, 0.60, "Fig.4 top", 1.0),
    ("mnist", True, 0.55, "Fig.4 bottom", 1.0),
]


def run_one(dataset: str, noniid: bool, policy: str, rounds: int, scale: float,
            seed: int = 0, batch_size: int = 50, local_epochs: int = 5,
            cnn_width: float = 1.0):
    import dataclasses

    train, test = load_dataset(dataset, seed=seed, scale=scale)
    cnn = CNN_CONFIGS[f"paper-cnn-{dataset}"]
    if cnn_width != 1.0:
        c1, c2 = cnn.conv_channels
        cnn = dataclasses.replace(
            cnn, conv_channels=(int(c1 * cnn_width), int(c2 * cnn_width)),
            fc_width=int(cnn.fc_width * cnn_width),
        )
    task = make_cnn_task(
        cnn, train, test, 100,
        noniid_alpha=0.6 if noniid else None, seed=seed,
    )
    cfg = RunConfig(
        n_clients=100, k=15, m=10, policy=policy, rounds=rounds,
        local_epochs=local_epochs, batch_size=batch_size,
        eval_every=max(rounds // 20, 1), seed=seed,
    )
    return run_engine(SyncEngine(task, cfg))


def run_one_mini(dataset: str, noniid: bool, policy: str, rounds: int, seed: int = 0):
    """CPU-budget mini protocol: 16x16 images, (8,16)-channel CNN, fc 64,
    n=100, k=15, m=10, SGD lr 0.1 x 0.998^t, E=2, B=10 — the paper's
    *structure* at a scale one CPU core can run in minutes."""
    import dataclasses

    from repro.data.synthetic import make_image_dataset

    base = CNN_CONFIGS[f"paper-cnn-{dataset}"]
    cnn = dataclasses.replace(
        base, name=base.name + "-mini", image_size=16, conv_channels=(8, 16),
        fc_width=64,
    )
    train, test = make_image_dataset(
        dataset + "-mini", base.num_classes, 16, base.channels,
        2000, 1000, seed=seed, difficulty=0.9,
    )
    task = make_cnn_task(cnn, train, test, 100,
                         noniid_alpha=0.6 if noniid else None, seed=seed)
    cfg = RunConfig(n_clients=100, k=15, m=10, policy=policy, rounds=rounds,
                    local_epochs=2, batch_size=10,
                    eval_every=max(rounds // 20, 1), seed=seed)
    return run_engine(SyncEngine(task, cfg))


def run(csv_rows, rounds: int = 14, scale: float = 0.05, paper_scale: bool = False):
    if paper_scale:
        rounds, scale = 300, 1.0
    print(f"\n== convergence: random vs markov "
          f"({'paper protocol' if paper_scale else 'CPU-budget mini protocol; --paper-scale for the full one'}, "
          f"rounds={rounds}) ==")
    for dataset, noniid, target, fig, mult in EXPERIMENTS:
        row = {}
        for policy in ("random", "markov"):
            t0 = time.time()
            if paper_scale:
                out = run_one(dataset, noniid, policy, rounds, scale)
            else:
                out = run_one_mini(dataset, noniid, policy,
                                   max(int(rounds * mult), 6))
            dt = time.time() - t0
            h = out.history()
            r2t = rounds_to_target(h, target)
            row[policy] = (h["accuracy"][-1], r2t, out.load_stats["var_X"], dt)
        tag = f"{dataset}{'-noniid' if noniid else ''}"
        acc_r, r2t_r, var_r, dt_r = row["random"]
        acc_m, r2t_m, var_m, dt_m = row["markov"]
        speedup = ""
        if r2t_r and r2t_m:
            speedup = f" speedup {100 * (r2t_r - r2t_m) / r2t_r:+.1f}%"
        print(f"{fig:12s} {tag:16s} acc@end rand={acc_r:.3f} mkv={acc_m:.3f} | "
              f"rounds->{target:.0%}: rand={r2t_r} mkv={r2t_m}{speedup} | "
              f"VarX {var_r:.1f} vs {var_m:.2f}")
        csv_rows.append(
            (f"convergence_{tag}", (dt_r + dt_m) / 2 * 1e6 / rounds,
             f"acc_random={acc_r:.4f};acc_markov={acc_m:.4f};"
             f"r2t_random={r2t_r};r2t_markov={r2t_m};varX_random={var_r:.2f};"
             f"varX_markov={var_m:.3f}")
        )

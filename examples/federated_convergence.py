"""End-to-end driver: the paper's Fig. 4 experiment — FedAvg on (synthetic)
MNIST, IID and non-IID Dirichlet(0.6), random vs Markov selection, with
rounds-to-target-accuracy reporting. Scaled for CPU by default; pass
--paper-scale for the full n=100/k=15/E=5/B=50/300-round protocol.

  PYTHONPATH=src python examples/federated_convergence.py [--paper-scale]
"""
import argparse
import os
import sys

# the shared experiment helpers live in benchmarks/, next to examples/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_convergence import run_one
from repro.core import load_metric as lm
from repro.engine import rounds_to_target

ap = argparse.ArgumentParser()
ap.add_argument("--paper-scale", action="store_true")
ap.add_argument("--rounds", type=int, default=16)
args = ap.parse_args()
rounds = 300 if args.paper_scale else args.rounds
scale = 1.0 if args.paper_scale else 0.08

print(f"n=100 k=15 m=10 rounds={rounds} (Var theory: random "
      f"{lm.random_selection_var(100, 15):.1f}, markov {lm.optimal_var(100, 15, 10):.3f})")
for noniid in (False, True):
    tag = "non-IID Dir(0.6)" if noniid else "IID"
    print(f"\n== MNIST {tag} ==")
    results = {}
    for policy in ("random", "markov"):
        out = run_one("mnist", noniid, policy, rounds, scale)
        h = out.history()
        results[policy] = h
        print(f"  {policy:7s}: acc " +
              " ".join(f"{a:.2f}" for a in h["accuracy"][-6:]) +
              f" | Var[X]={out.load_stats['var_X']:.2f}")
    for target in (0.5, 0.6, 0.7):
        rr = rounds_to_target(results["random"], target)
        rm = rounds_to_target(results["markov"], target)
        if rr or rm:
            print(f"  rounds to {target:.0%}: random={rr} markov={rm}")

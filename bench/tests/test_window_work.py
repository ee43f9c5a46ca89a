"""A cell that fixes its window's work draws, for every seed, a run seed
whose window trains the same number of cohort slots; the admission that
predicts it is the reference's, and the program's window trains exactly
that many."""
import dataclasses

import numpy as np

from bench import run as bench_run
from bench.cell import load_cell
from bench.reference import Reference, cohort_sizes

SEED = 2**33 + 41


def _slots(sizes, group):
    return (group * -(-np.asarray(sizes) // group)).sum(-1)


def test_cohort_sizes_are_the_reference_cohorts(tiny):
    cell = tiny("sync-paper")
    data, model, _, engine, run_seed = bench_run.build(cell, SEED)
    steps, chunk = 4, engine.cfg.resolved_steps_per_chunk()
    ref = Reference(model, cell.config, cell.traffic, data, run_seed).follow(
        steps, chunk)
    width = engine.cfg.cohort_width()
    want = np.minimum(np.asarray(ref["sel"]).sum(1), width)
    got = cohort_sizes(cell.config, cell.traffic, [run_seed], steps)
    assert got.shape == (1, steps)
    np.testing.assert_array_equal(got[0], want)


def test_every_seed_of_sync_paper_draws_the_cells_work():
    cell = load_cell("sync-paper")
    work, steps = cell.window_work, cell.window_steps
    drawn = [bench_run.equal_work_seed(cell, s)
             for s in (0, 1, 2**31 + 5, 5000000011, 9 * 10**9)]
    assert len(set(drawn)) == len(drawn)
    assert bench_run.equal_work_seed(cell, 5000000011) == drawn[3]
    sizes = cohort_sizes(cell.config, cell.traffic, drawn, steps)
    assert (_slots(sizes, work["group"]) == work["slots"]).all()
    # the target is common: a seed runs out of candidates about never
    cands = [bench_run._hash31(f"7:{j}") for j in range(bench_run.CANDIDATES)]
    hits = _slots(cohort_sizes(cell.config, cell.traffic, cands, steps),
                  work["group"]) == work["slots"]
    assert hits.mean() > 0.1


def test_the_programs_window_trains_the_drawn_work(tiny):
    cell = tiny("sync-paper")
    steps = 2 * cell.period
    # the work of the seed's third candidate: the draw stops there or before
    third = bench_run._hash31(f"{SEED}:2")
    target = int(_slots(cohort_sizes(cell.config, cell.traffic, [third],
                                     steps), 5)[0])
    check = {**cell.check, "window_steps": steps,
             "window_work": {"group": 5, "slots": target}}
    cell = dataclasses.replace(cell, check=check)
    out = bench_run.run(cell, SEED, 0.2, False, require_chip=False)
    assert out["correct"], out["rows"]
    assert out["window"]["steps"] == steps
    assert out["window"]["slots"] == target

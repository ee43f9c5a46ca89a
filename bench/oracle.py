"""What decides ``correct``: the program's first steps against the plain
reference (``bench/reference.py``), number by number.

The harness builds one engine and drives it through ``run_engine`` from
the seed for the cell's ``compare_steps`` steps during set-up (whole
chunks, ending in one eval), with the window's own chunk program, eval
program and data. ``Capture``
wraps the engine's hooks from outside to copy out, after every chunk, the
global params and the per-step outputs, and at the end the fleet state.
The reference then follows the same steps and ``compare`` reads:

- ``sel_diff``: clients whose selection differs in any step (sync), whose
  age or step of last selection differs at the end, plus 1 for each load
  accumulator (gap count, sum, sum of squares) that differs;
- ``pop_diff`` (async): steps whose buffer fill, version or simulated
  clock differ, plus clients whose completion time, dispatch version or
  last completion differ at the end;
- ``loss_gap``: the widest gap of a step's train loss, over the mean of
  the reference's losses (a client's loss on its own two examples can
  near 0, where a relative gap means nothing);
- ``update_gap``: the global params' change over the first chunk, by the
  worst leaf: |‖Δ_prog‖ - ‖Δ_ref‖| over the larger of ‖Δ_ref‖ and the
  median leaf's ‖Δ_ref‖. Leaves whose reference change is under a
  thousandth of the median leaf's are left out (none of the CNN's are);
- ``update_dist``: the same change, by the worst leaf, ‖Δ_prog - Δ_ref‖
  over the same scale: where the norms agree, as when the cohort trained
  other clients' i.i.d. examples, the direction can still differ;
- ``param_gap``: the params after the compared steps, by the worst leaf:
  ‖p_prog - p_ref‖ over the larger of ‖p_ref‖ and the median leaf's
  ‖p_ref‖. Parameters held in bfloat16 read the rounding of every
  weight here, also where it is small against the updates;
- ``eval_gap``: the relative gap of the eval loss after the last step.

Counts compare floats to a relative 1e-6 (the load sums to 1e-5, being
float32 sums over millions of clients).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np

REL = 1e-6
REL_SUM = 1e-5


class Capture:
    """Copies out what ``compare`` reads while ``run_engine`` drives the
    engine, without changing what runs. ``restore()`` unwraps."""

    HOOKS = ("init", "run_chunk", "finalize")

    def __init__(self, engine):
        self.engine = engine
        self.out: Dict = {"params": [], "aux": []}
        for name in self.HOOKS:
            setattr(engine, name, getattr(self, "_" + name))

    def restore(self):
        for name in self.HOOKS:
            delattr(self.engine, name)

    def _init(self):
        state = type(self.engine).init(self.engine)
        self.out["params0"] = jax.device_get(state["params"])
        return state

    def _run_chunk(self, state, r0, length, with_history):
        state, aux = type(self.engine).run_chunk(self.engine, state, r0,
                                                 length, with_history)
        self.out["params"].append(jax.device_get(state["params"]))
        self.out["aux"].append(jax.device_get(aux))
        return state, aux

    def _finalize(self, state, records, sel_hist, wall):
        final = {"ages": state["sched"]["ages"],
                 "acc": {k: state["load_acc"][k] for k in
                         ("last_sel", "gap_sum", "gap_sumsq", "gap_cnt")}}
        if "ev" in state:
            final.update({k: state["ev"][k] for k in
                          ("t_done", "disp_ver", "last_done")})
        self.out["final"] = jax.device_get(final)
        result = type(self.engine).finalize(self.engine, state, records,
                                            sel_hist, wall)
        self.out["eval"] = [r.eval_loss for r in records]
        self.out["sel"] = sel_hist
        return result

    def outputs(self) -> Dict:
        """The program's outputs in the reference's layout."""
        out = dict(self.out)
        aux = out.pop("aux")
        for key, name in (("loss", "loss"), ("clock", "clock"),
                          ("version", "version"), ("buffer_fill", "fill")):
            if key in aux[0]:
                out[name] = np.concatenate([a[key] for a in aux])
        if out["sel"] is None:
            out.pop("sel")
        return out


def _rel(a, b) -> np.ndarray:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    same = (a == b) | (np.isnan(a) & np.isnan(b))
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
    return np.where(same, 0.0, np.nan_to_num(gap, nan=np.inf))


def _count(a, b, rel=REL) -> int:
    return int(np.sum(_rel(a, b) > rel))


def _norms(leaves) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(x, np.float64))
                     for x in leaves])


def _worst(gaps: np.ndarray, nr: np.ndarray) -> float:
    """The largest of ``gaps`` over the larger of each leaf's reference norm
    ``nr`` and the median leaf's, leaving out leaves under a thousandth of
    the median."""
    med = float(np.median(nr))
    keep = nr >= 1e-3 * med
    if not keep.any():
        return float("inf")
    return float(np.max(gaps[keep] / np.maximum(nr, med)[keep]))


def leaf_gap(dp: List[np.ndarray], dr: List[np.ndarray]) -> float:
    """Worst leaf's gap of norms (see the module docstring)."""
    nr = _norms(dr)
    return _worst(np.abs(_norms(dp) - nr), nr)


def leaf_dist(dp: List[np.ndarray], dr: List[np.ndarray]) -> float:
    """Worst leaf's distance (see the module docstring)."""
    return _worst(_norms([a - b for a, b in zip(dp, dr)]), _norms(dr))


def param_gap(pp, pr) -> float:
    """Worst leaf's distance of the program's params from the reference's,
    over the larger of the reference leaf's norm and the median leaf's."""
    pp = [np.asarray(x, np.float64) for x in jax.tree.leaves(pp)]
    pr = [np.asarray(x, np.float64) for x in jax.tree.leaves(pr)]
    nr = np.array([np.linalg.norm(x) for x in pr])
    dist = np.array([np.linalg.norm(a - b) for a, b in zip(pp, pr)])
    return float(np.max(dist / np.maximum(nr, np.median(nr))))


def change(after, before) -> List[np.ndarray]:
    return [np.asarray(a, np.float64) - np.asarray(b, np.float64)
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))]


def _loss_gap(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    gap = np.where(np.isnan(got) & np.isnan(ref), 0.0, np.abs(got - ref))
    with np.errstate(invalid="ignore"):
        scale = np.nanmean(np.abs(ref)) if not np.isnan(ref).all() else np.nan
        return float(np.nan_to_num(np.max(gap) / scale, nan=np.inf))


def compare(got: Dict, ref: Dict) -> Dict[str, float]:
    """The readings of ``got`` (the program's or the control's outputs)
    against the reference's."""
    fg, fr = got["final"], ref["final"]
    sel = (_count(fg["ages"], fr["ages"], 0)
           + _count(fg["acc"]["last_sel"], fr["acc"]["last_sel"], 0)
           + _count(fg["acc"]["gap_cnt"], fr["acc"]["gap_cnt"], 0)
           + _count(fg["acc"]["gap_sum"], fr["acc"]["gap_sum"], REL_SUM)
           + _count(fg["acc"]["gap_sumsq"], fr["acc"]["gap_sumsq"], REL_SUM))
    if "sel" in ref:
        sel += int(np.sum(np.asarray(got["sel"]) != np.asarray(ref["sel"])))
    out = {"sel_diff": float(sel)}
    if "clock" in ref:
        out["pop_diff"] = float(
            _count(got["fill"], ref["fill"], 0)
            + _count(got["version"], ref["version"], 0)
            + _count(got["clock"], ref["clock"])
            + _count(fg["t_done"], fr["t_done"])
            + _count(fg["disp_ver"], fr["disp_ver"], 0)
            + _count(fg["last_done"], fr["last_done"]))
    dp = change(got["params"][0], got["params0"])
    dr = change(ref["params"][0], ref["params0"])
    out.update({
        "loss_gap": _loss_gap(got["loss"], ref["loss"]),
        "update_gap": leaf_gap(dp, dr),
        "update_dist": leaf_dist(dp, dr),
        "param_gap": param_gap(got["params"][-1], ref["params"][-1]),
        "eval_gap": float(np.max(_rel(got["eval"], ref["eval"]))),
    })
    return out


def judge(readings: Dict[str, float], limits: Dict[str, float]):
    """``(correct, [(name, reading, limit)])`` over the numbers the cell
    holds to a limit; a limit with no reading, or a reading that is not
    a number, fails. Readings with no limit (numbers whose control and
    faults read no higher than sound runs) are not compared."""
    rows = [(n, readings.get(n, float("inf")), limits[n])
            for n in sorted(limits)]
    return all(val <= lim for _, val, lim in rows), rows

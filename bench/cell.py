"""One cell of ``BENCHMARK.json`` and the files it is made of.

A cell names a configuration and a traffic mix. Each is a JSON file of its
own, found by name:

- ``configs[].file``: the deployment (model widths, data, fleet, the
  ``RunConfig`` fields of the run) and the name of its model module under
  ``bench/models/``;
- ``bench/traffic/<traffic>.json``: the ``RunConfig`` fields that shape the
  traffic (buffer, eval cadence, steps per host dispatch);
- ``bench/cells/<cell>.json``: what the correctness check compares over
  (how many steps), the limit of every number it compares, and, where
  the cell fixes them, the window's length in steps and its work.

Nothing here knows a cell by name, so a later cell adds files and edits
none.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _read(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    check: Dict  # bench/cells/<name>.json
    end_to_end: List[Dict]  # BENCHMARK.json metrics this cell reports
    per_layer: List[Dict]

    @property
    def period(self) -> int:
        """Steps per eval period: the window is a whole number of them."""
        return int(self.traffic["run"]["eval_every"])

    @property
    def compare_steps(self) -> int:
        """Steps the correctness check compares: whole chunks of the
        window's chunk program, within one eval period, so the compared
        run ends in the one eval it makes and compiles nothing the window
        does not use."""
        steps = int(self.check["compare_steps"])
        chunk = int(self.traffic["run"]["steps_per_chunk"])
        if steps <= 0 or steps % chunk or steps > self.period:
            raise ValueError(f"cell {self.name}: compare_steps {steps} is not "
                             f"a whole number of {chunk}-step chunks within "
                             f"one {self.period}-step eval period")
        return steps

    @property
    def window_steps(self):
        """The window's length in steps where the cell fixes it (whole eval
        periods), so every run of a seed runs the same steps; None where
        the window is sized from ``--seconds``."""
        steps = self.check.get("window_steps")
        if steps is None:
            return None
        if steps <= 0 or steps % self.period:
            raise ValueError(f"cell {self.name}: window_steps {steps} is not "
                             f"a whole number of {self.period}-step eval "
                             "periods")
        return int(steps)

    @property
    def window_work(self):
        """Where the cell fixes the window's work, ``{"group": g, "slots":
        s}``: every run seed it draws trains ``s`` cohort slots over the
        window's sync rounds, each round's cohort counted in whole groups
        of ``g``; else None."""
        work = self.check.get("window_work")
        if work is None:
            return None
        if self.window_steps is None or self.config["run"]["mode"] != "sync":
            raise ValueError(f"cell {self.name}: window_work needs sync "
                             "rounds and window_steps")
        return {"group": int(work["group"]), "slots": int(work["slots"])}

    def run_config(self, seed: int, rounds: int):
        from repro.engine import RunConfig

        fields = {**self.config["run"], **self.traffic["run"]}
        return RunConfig(seed=seed, rounds=rounds, **fields)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``; raises KeyError for
    a name that is not there."""
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {', '.join(sorted(cells))}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read(root / configs[w["config"]]["file"]),
        traffic=_read(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        check=_read(root / "bench" / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )

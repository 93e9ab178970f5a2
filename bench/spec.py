"""One cell's files, found by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from bench.traffic import load_mix

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    mix: dict             # traffic/<traffic>.json
    cell: dict            # cells/<name>.json: slots, max_seq, rate, check
    end_to_end: tuple     # metric entries this cell reports
    per_layer: tuple


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = load_mix(BENCH / "traffic" / f"{w['traffic']}.json")
    cell = json.loads((BENCH / "cells" / f"{name}.json").read_text())
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    per_layer = tuple(m for m in bench["per_layer"] if _reports(m, name))
    return Cell(name, w["chips"], config, mix, cell, e2e, per_layer)

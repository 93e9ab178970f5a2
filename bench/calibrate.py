#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in this one process: one run of the cell as
``bench/run.py`` makes it, then the served tokens' widest logit gap
against the reference (the number ``correct`` compares) and the same
gap for the tokens the float8 control puts first at the same positions,
with the verdict of the cell's limits on each.  One JSON line per seed.
Exits 1 where the program's run is not correct or the control is.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    devs = run.devices_for(spec.chips)[:spec.chips]
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(spec, seed, args.seconds, False, devs=devs,
                           t_start=time.perf_counter(), control=True,
                           log=lambda s, **kw: print(s, file=sys.stderr,
                                                     **kw))
        if not out["correct"] or out["control_correct"]:
            rc = 1
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "control_correct": out["control_correct"],
                          **out["readings"],
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()}}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

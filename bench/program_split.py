#!/usr/bin/env python3
"""Where a traced run's time went, by the program's own spans.

    python3 bench/program_split.py --workload <cell> --seed <n> --seconds <s>

One traced run of the cell as ``bench/run.py --trace 1`` makes it, and
then, from the engine's recorder and the run's trace
(``bench/programspans.py``):

* ``counts``: the window's counter increments, with the page-out check
  (rows = decode rows + prefill rows, and bytes per row);
* ``spans``: per program span name, how many and their host seconds;
* ``idle``: the traced window's device idle time by (innermost program
  span, harness span), in seconds, and ``covered``, the share of it that
  lies in a program span or in the harness's ``wait``;
* ``recorder``: the recorder's cost with the profiler off, timed here
  after the run: microseconds per span in the engine's pattern, times
  the window's spans per decode iteration, against the window's seconds
  per decode iteration.

Prints ``bench/run.py``'s result line, then one JSON line of the above;
``--out`` also writes both to a file.  The benchmark's own runs never
run this.
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

from bench import programspans, run  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def summary(rec) -> dict | None:
    """What the run record ``rec`` says by the program's spans; ``None``
    where the window has no recorder or lost spans."""
    w = programspans.window(rec)
    if w is None:
        return None
    c = w.counts
    spans = {}
    for s in w.spans:
        n, t = spans.get(s.name, (0, 0.0))
        spans[s.name] = (n + 1, t + s.end - s.start)
    steps = c["decode_steps"]
    out = {"window_s": rec.t1 - rec.t0,
           "counts": dict(c),
           "rows_match": c["page_out_rows"] == c["active_slot_steps"]
           + c["admitted"],
           "bytes_per_row": c["page_out_bytes"] / max(1, c["page_out_rows"]),
           "spans": {k: {"n": n, "s": t} for k, (n, t) in spans.items()},
           "spans_per_decode": len(w.spans) / max(1, steps),
           "s_per_decode": (rec.t1 - rec.t0) / max(1, steps)}
    sp = programspans.traced_split(rec)
    if sp is not None:
        idle = sp["idle_s"]
        covered = sum(v for (prog, harn), v in sp["split"].items()
                      if prog != "-" or harn == "wait")
        out["idle"] = {
            "window_s": sp["window_s"], "idle_s": idle,
            "covered": covered / idle if idle else None,
            "split": sorted(([prog, harn, v] for (prog, harn), v
                             in sp["split"].items()), key=lambda x: -x[2])}
    return out


def recorder_cost(rows: int, iterations: int = 2000,
                  repeats: int = 5) -> dict:
    """Microseconds per span of a fresh recorder (profiler off) in the
    engine's pattern: an ``admit``, and a ``decode`` holding
    ``decode.step``, a counted ``page_out`` and a ``pool_write`` per row,
    and ``retire``.  The least of ``repeats`` timings."""
    from repro.exec.trace import Recorder

    def iteration(r):
        with r.span("admit"):
            pass
        with r.span("decode"):
            with r.span("decode.step"):
                r.count("decode_steps")
                r.count("active_slot_steps", rows)
            for i in range(rows):
                with r.span("page_out", rid=i):
                    r.count("page_out_rows")
                    r.count("page_out_bytes", 1)
                with r.span("pool_write", rid=i):
                    pass
            with r.span("retire"):
                pass

    per = []
    for _ in range(repeats):
        r = Recorder()
        t = time.perf_counter()
        for _ in range(iterations):
            iteration(r)
        per.append((time.perf_counter() - t) / r.closed)
    return {"rows": rows, "us_per_span": 1e6 * min(per),
            "us_per_span_max": 1e6 * max(per)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    spec = load_cell(args.workload)
    devs = run.devices_for(spec.chips)[:spec.chips]
    seen = []
    read_metric = run.read_metric

    def keep(name, rec):          # the one place run_cell hands out its record
        if not seen:
            seen.append(summary(rec))
        return read_metric(name, rec)

    run.read_metric = keep
    try:
        out = run.run_cell(spec, args.seed, args.seconds, True, devs=devs,
                           t_start=t_start,
                           log=lambda s, **kw: print(s, file=sys.stderr,
                                                     **kw))
    finally:
        run.read_metric = read_metric
    split = seen[0] if seen else None
    if split is not None:
        c = split["counts"]
        cost = recorder_cost(round(c["active_slot_steps"]
                                   / max(1, c["decode_steps"])))
        per_decode = cost["us_per_span"] * split["spans_per_decode"]
        cost["us_per_decode"] = per_decode
        cost["share_of_decode"] = per_decode * 1e-6 / split["s_per_decode"]
        split["recorder"] = cost
    print(json.dumps(out), flush=True)
    print(json.dumps(split), flush=True)
    if args.out:
        Path(args.out).write_text(json.dumps({"result": out,
                                              "program": split}, indent=1))
    return 0 if split is not None else 1


if __name__ == "__main__":
    sys.exit(main())

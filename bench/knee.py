#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest Poisson rate at which
the queue does not grow over a window.

    python3 bench/knee.py --workload <cell> --rates 0.5,1,2 --seconds <s> --seed <n>

One process; a fresh engine per rate (as ``bench/run.py`` builds it), the
cell's traffic at each rate in turn.  One JSON line per rate: requests due
and admitted, the queue (due, not admitted) at the middle and the end of
the window, the mean wait of requests due in each half, and the cell's
end-to-end numbers.  The cell's file holds the rate chosen from this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run, serve, stats  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def queue_at(reqs, t: float) -> int:
    return sum(1 for r in reqs if r.due_at <= t
               and (r.admitted_at is None or r.admitted_at > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    run.devices_for(spec.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = serve.program_config(spec.config)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = dict(spec.cell, rate_per_s=rate)
        engine = serve.build_engine(cfg, cell, args.seed % 2**32)
        engine.warmup()
        serve.warm_up(engine, cfg.vocab)
        hooks = serve.drive(engine, spec.mix, cell, args.seed, args.seconds,
                            cfg.vocab)
        t0, t1 = hooks.t0, hooks.t1
        reqs = list(hooks.requests.values())
        mid = (t0 + t1) / 2

        def wait(lo, hi):
            w = [min(r.admitted_at or t1, t1) - r.due_at for r in reqs
                 if lo <= r.due_at < hi]
            return sum(w) / len(w) if w else None
        row = {"rate": rate,
               "due": sum(1 for r in reqs if r.due_at <= t1),
               "admitted": sum(1 for r in reqs if r.admitted_at is not None),
               "queue_mid": queue_at(reqs, mid), "queue_end": queue_at(reqs, t1),
               "wait_first_half_s": wait(t0, mid),
               "wait_second_half_s": wait(mid, t1),
               "mean_active": (sum(len(c.live) for c in hooks.decode_calls)
                               / max(1, len(hooks.decode_calls))),
               "ttft_p90_s": stats.end_to_end("ttft_p90_s", reqs, t0, t1),
               "tokens_per_s": stats.end_to_end("tokens_per_s", reqs, t0, t1)}
        print(json.dumps(row), flush=True)
        del engine
        hooks.engine = None
        run.free_device_memory()
    return 0


if __name__ == "__main__":
    sys.exit(main())

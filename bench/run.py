#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``, from the start of this process): the
program's engine is built for the cell, with its weights made on the
device from the seed, and every program and shape the window uses is run
once.  Then the window: ``--seconds`` of the cell's traffic through
``ServingEngine.run``.  Then, outside every number: the peak memory is
read, the program's state freed, and a sample of the served tokens is
compared with the plain float32 reference, which decides ``correct``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs a
profiler over part of the window and reports its per-layer metrics.  The
last line of standard output is one JSON object.  Off an accelerator, or
with fewer chips than the cell needs, the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import check, counts, stats  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402
from bench.spec import BENCH, load_cell  # noqa: E402

#: Where a traced run keeps its trace (inside the checkout; one at a time).
TRACE_DIR = BENCH / ".trace"
#: The longest profiler window of a traced run.
TRACE_SECONDS = 8.0


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell needs."""


def devices_for(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("no accelerator: JAX sees only the CPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


class RunRecord:
    """What the per-layer readers (``bench/metrics/<name>.py``) read."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)

    def decode_calls_in(self, lo: float, hi: float) -> list:
        return [c for c in self.hooks.decode_calls
                if lo <= c.start and c.end <= hi]

    def spans_in(self, label: str, lo: float, hi: float) -> list:
        return [(a, b) for lab, a, b in self.hooks.spans
                if lab == label and lo <= a and b <= hi]


def read_metric(name: str, rec: RunRecord):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def param_bytes_per_chip(params) -> int:
    """Bytes of parameters the fullest chip holds, as the program holds
    them (dtype and shard)."""
    import jax
    per = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            per[sh.device] = per.get(sh.device, 0) + sh.data.nbytes
    return max(per.values())


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def free_device_memory() -> None:
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def run_cell(spec, seed: int, seconds: float, trace: bool, *,
             devs=None, t_start: float = T_START, break_path=None,
             control: bool = False, log=print) -> dict:
    """One run of one cell; returns the result object.  ``break_path``
    (tests only) gets the built engine and may break the timed path.
    ``control`` (``bench/calibrate.py``) also reads the float8 control,
    returns every reading under ``readings``, and the verdict of the
    cell's limits on the control's gap as ``control_correct``."""
    from repro.launch.compile_cache import enable_compile_cache

    from bench import serve
    from bench.hooks import Tracer

    import jax

    enable_compile_cache()
    devs = devs if devs is not None else jax.devices()[:spec.chips]
    cell, config = spec.cell, spec.config
    cfg = serve.program_config(config, cell.get("program_overrides"))
    param_seed = seed % 2**32
    engine = serve.build_engine(cfg, cell, param_seed)
    engine.params = serve.with_free_params(engine.params, param_seed, config)
    engine.warmup()
    serve.warm_up(engine, cfg.vocab)
    if break_path is not None:
        break_path(engine)
    setup_parts = dict(engine.setup_s)
    pbytes = param_bytes_per_chip(engine.params)

    tracer = None
    if trace:
        TRACE_DIR.mkdir(parents=True, exist_ok=True)
        for old in TRACE_DIR.rglob("*.xplane.pb"):
            old.unlink()
        # The last seconds of the window: writing the trace out, which
        # stalls the host for seconds, comes after the window.
        length = min(TRACE_SECONDS, seconds / 2)
        tracer = Tracer(str(TRACE_DIR), seconds - length, length + 60.0)
    compiles = []          # backend compiles from here on: should be none
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if "backend_compile" in name else None)
    hooks = serve.drive(engine, spec.mix, cell, seed, seconds, cfg.vocab,
                        tracer=tracer)
    t0, t1 = hooks.t0, hooks.t1
    in_window = len(compiles)
    setup_s = t0 - t_start

    reqs = list(hooks.requests.values())
    e2e = {}
    for m in spec.end_to_end:
        v = setup_s if m["name"] == "setup_s" else \
            stats.end_to_end(m["name"], reqs, t0, t1)
        if v is None:
            raise RuntimeError(f"end-to-end metric {m['name']} has nothing "
                               "to read in the window")
        e2e[m["name"]] = {"value": v, "unit": m["unit"]}
    peak = memory_peak(devs)
    backlog = spec.mix["arrivals"] == "backlog"
    attempted = sum(1 for r in reqs if r.state is not None
                    or (not backlog and r.due_at <= t1))
    served = [(r.rid, r.prompt, r.tokens) for r in reqs]
    short = sum(1 for r in reqs if r.finished and len(r.tokens) != r.max_new)
    vocab_bad = sum(1 for _, _, toks in served for t in toks
                    if not 0 <= t < cfg.vocab)
    n_active = [len(c.live) for c in hooks.decode_calls if t0 <= c.start < t1]

    trace_red = None
    if tracer is not None and tracer.started_at is not None:
        from bench import tracefile
        files = sorted(TRACE_DIR.rglob("*.xplane.pb"))
        if files:
            tr = tracefile.load(files[-1])
            off = tracefile.host_offset_ns(tr, hooks.spans, tracer.started_at)
            if off is None:
                lo, hi = 0.0, (tracer.stopped_at - tracer.started_at) * 1e9
            else:
                lo = max(0.0, tracer.started_at * 1e9 + off)
                hi = tracer.stopped_at * 1e9 + off
            trace_red = tracefile.reduce(tr, lo, hi)
            del tr
    rec = RunRecord(hooks=hooks, t0=t0, t1=t1, setup=setup_parts,
                    config=config, cell=cell, chips=spec.chips,
                    peaks=peaks_for(devs[0].device_kind) if trace else None,
                    param_bytes=pbytes, trace=trace_red, tracer=tracer,
                    counts=counts, stats=stats)
    per_layer = {}
    if trace:
        for m in spec.per_layer:
            v = read_metric(m["name"], rec)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}

    # The program's state goes before the reference runs.
    del engine, rec
    hooks.engine = None
    free_device_memory()
    chk = cell["check"]
    picked = check.sample(served, seed, chk["tokens"], chk["rows"])
    readings = check.compare(config, param_seed, picked, cell["max_seq"],
                             chk["rows"], control=control) if picked else {}
    readings["finished_short"] = short
    readings["out_of_vocab"] = vocab_bad
    correct, checks = check.verdict(readings, chk["limits"])
    if control:
        # The control in the program's place, judged by the same limits.
        control_correct, _ = check.verdict(
            dict(readings, logit_gap=readings.get("control_gap")),
            chk["limits"])

    log(f"[bench] {spec.name} seed {seed}: setup {setup_s:.3f} s "
        f"({', '.join(f'{k} {v:.3f}' for k, v in setup_parts.items())}), "
        f"window {seconds} s, {len(reqs)} requests offered, "
        f"{stats.tokens_in(reqs, t0, t1)} tokens in the window, "
        f"{len(n_active)} decode steps (mean active "
        f"{sum(n_active) / max(1, len(n_active)):.2f}), "
        f"{in_window} compiles in the window, "
        + (f"profiler start {tracer.started_at - tracer.requested_at:.3f} s, "
           if tracer is not None and tracer.started_at else "")
        + f"{readings.get('tokens_compared', 0)} tokens compared over "
        f"{readings.get('requests_compared', 0)} requests", flush=True)
    dev = devs[0]
    out = {"correct": correct, "attempted": attempted,
           "failed": 0,
           "metrics": per_layer if trace else e2e,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devs), "memory_peak_bytes": peak}}
    if trace:
        if trace_red is None or not trace_red.get("busy_s"):
            raise RuntimeError("the traced window holds no device operation")
        out["device"]["busy_s"] = trace_red["busy_s"]
        out["device"]["window_s"] = trace_red["window_s"]
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace_red["device_ops"]],
            "idle_gaps": sorted(([f"idle in {k}", v] for k, v in
                                 trace_red["idle_by_span"].items()),
                                key=lambda kv: -kv[1])[:10]}
    out["compiles_in_window"] = in_window
    if control:
        out["readings"] = readings
        out["control_correct"] = control_correct
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    try:
        devs = devices_for(spec.chips)
        peaks_for(devs[0].device_kind)
    except (NoChip, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   devs=devs[:spec.chips],
                   log=lambda s, **kw: print(s, file=sys.stderr, **kw))
    for name, c in out["checks"].items():
        kind = "max" if "max" in c else "min"
        print(f"check {name} {c['value']!r} {kind} {c[kind]!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns one ``.xplane.pb`` into plain records; the rest works on
those records alone, so ``tests/bench`` checks it on a slice of a chip
trace kept as JSON.  What a v5e trace holds (read by hand first):

* device planes ``/device:TPU:<n>``; their ``XLA Modules`` line has one
  event per program run, named ``jit_<fn>(<fingerprint>)``, and their
  ``XLA Ops`` line one event per operation, named by its HLO text
  (``%fusion.3 = bf16[...] fusion(...)``), a ``while`` op spanning the ops
  of its body.  Ops carry no program stat: an op belongs to the program
  run whose interval holds it;
* the harness's spans are host events named ``bench.<span>``;
* times are nanoseconds from the start of the profiling session, one
  clock for host and devices.
"""
from __future__ import annotations

import bisect
import collections
import re

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
#: ``%name = <shape> opcode(`` of an op's HLO text, or its short form.
HLO = re.compile(r"^(%[^ ]+) = (?:\([^=]*?\)|\S+) ([a-z][\w-]*)\(")
SHORT = re.compile(r"^(%\S+) ([a-z][\w-]*)$")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
CONTAINERS = ("while", "conditional", "call")


def load(path: str) -> dict:
    """Plain records of one trace file: per device, ``ops`` (named
    ``%name opcode``) and ``modules`` as ``[name, start_ns, duration_ns]``;
    host ``spans`` as ``[label, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "spans": []}
    for plane in data.planes:
        m = DEVICE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key == "ops":
                    dev[key] = [[op_label(e.name)[0], e.start_ns,
                                 e.duration_ns] for e in line.events]
                elif key == "modules":
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            out["devices"][int(m.group(1))] = dev
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    out["spans"].append([e.name[len("bench."):],
                                         e.start_ns, e.duration_ns])
    return out


def op_label(name: str) -> tuple[str, str]:
    """(short name, opcode) of an op's HLO text."""
    m = HLO.match(name) or SHORT.match(name)
    if m:
        return f"{m.group(1)} {m.group(2)}", m.group(2)
    return name[:80], ""


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, sorted."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy(ops: list, lo: float, hi: float) -> list[tuple[float, float]]:
    """Busy intervals of one device, clipped to ``[lo, hi]``."""
    clipped = [(max(s, lo), min(s + d, hi)) for _, s, d in ops]
    return union([(a, b) for a, b in clipped if b > a])


def idle_by_span(ivs: list, spans: list, lo: float, hi: float) -> dict:
    """Idle time of one device (the complement of its busy intervals
    ``ivs`` in ``[lo, hi]``), split by the host span it fell in (``-``: in
    none).  ``spans`` sorted by start, not overlapping."""
    edges = [lo] + [x for iv in ivs for x in iv] + [hi]
    out = collections.Counter()
    i = 0
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        while i < len(spans) and spans[i][1] + spans[i][2] <= a:
            i += 1
        covered, j = 0.0, i
        while j < len(spans) and spans[j][1] < b:
            s0, s1 = max(a, spans[j][1]), min(b, spans[j][1] + spans[j][2])
            if s1 > s0:
                out[spans[j][0]] += s1 - s0
                covered += s1 - s0
            j += 1
        if b - a > covered:
            out["-"] += b - a - covered
    return out


def span_at(spans: list, starts: list, t: float) -> str:
    """The harness span the host was in at ``t`` (``-`` if none)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < spans[i][1] + spans[i][2]:
        return spans[i][0]
    return "-"


def programs(modules: list, spans: list, lo: float, hi: float) -> dict:
    """Program name -> its runs inside ``[lo, hi]`` as (start, duration,
    host span at the run's middle)."""
    starts = [s for _, s, _ in spans]
    out = collections.defaultdict(list)
    for name, s, d in modules:
        if lo <= s and s + d <= hi:
            out[name].append((s, d, span_at(spans, starts, s + d / 2)))
    return out


def find_program(progs: dict, labels: tuple, exclude=()) -> str | None:
    """The program with the most device time in runs that fall in host
    spans ``labels`` (by time, not count: the engine's small eager ops run
    as often)."""
    best, most = None, 0.0
    for name, runs in progs.items():
        if name in exclude:
            continue
        t = sum(d for _, d, lab in runs if lab in labels)
        if t > most:
            best, most = name, t
    return best


def ops_in(ops: list, runs: list) -> list:
    """The ops that start inside one of ``runs`` (sorted, disjoint)."""
    starts = [s for s, _, _ in runs]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] < runs[i][0] + runs[i][1]:
            out.append(op)
    return out


def host_offset_ns(trace: dict, host_spans: list, since: float) -> float | None:
    """Trace time minus host clock (ns), from the first harness span the
    trace holds and the same span in the harness's own record (host
    seconds, ``since`` the profiler started)."""
    if not trace["spans"]:
        return None
    first = min(trace["spans"], key=lambda s: s[1])
    later = [s for s in host_spans if s[0] == first[0] and s[1] >= since]
    if not later:
        return None
    return first[1] - min(s[1] for s in later) * 1e9


def reduce(trace: dict, lo: float, hi: float) -> dict:
    """Device numbers of the traced window ``[lo, hi]`` (ns).

    ``busy_s``/``window_s``: union of operation intervals over the window,
    averaged over devices.  ``decode_ms``/``prefill_ms``: device time per
    run of the decode and prefill programs, told apart by the host span
    their runs fall in (``decode``; ``prefill`` or ``after_prefill``),
    averaged over devices.  ``collective_ms``: time of collective ops
    inside decode runs per run, averaged over devices.  ``idle_by_span``:
    the first device's idle time by host span.  ``device_ops``: its ten
    costliest ops (control-flow containers left out)."""
    spans = sorted(trace["spans"], key=lambda s: s[1])
    devs = trace["devices"]
    if not devs:
        return {}
    per_dev = collections.defaultdict(list)
    idle, op_time = collections.Counter(), collections.Counter()
    for dev_id in sorted(devs):
        dev = devs[dev_id]
        ivs = busy(dev["ops"], lo, hi)
        per_dev["busy_ns"].append(sum(b - a for a, b in ivs))
        if dev_id == min(devs):
            idle.update(idle_by_span(ivs, spans, lo, hi))
            for name, s, d in dev["ops"]:
                if lo <= s < hi:
                    label, opcode = op_label(name)
                    if opcode not in CONTAINERS:
                        op_time[label] += d
        progs = programs(dev["modules"], spans, lo, hi)
        decode = find_program(progs, ("decode",))
        prefill = find_program(progs, ("prefill", "after_prefill"),
                               exclude=(decode,))
        for name, tag in ((decode, "decode"), (prefill, "prefill")):
            if name is not None:
                runs = progs[name]
                per_dev[f"{tag}_ns"].append(sum(d for _, d, _ in runs)
                                            / len(runs))
                per_dev[f"{tag}_runs"].append(len(runs))
        if decode is not None:
            runs = sorted(progs[decode])
            coll = sum(d for name, s, d in ops_in(dev["ops"], runs)
                       if op_label(name)[1].startswith(COLLECTIVES))
            per_dev["collective_ns"].append(coll / len(runs))

    def mean(key):
        xs = per_dev.get(key)
        return sum(xs) / len(xs) if xs else None

    out = {"devices": len(devs), "window_s": (hi - lo) * 1e-9,
           "busy_s": mean("busy_ns") * 1e-9,
           "idle_by_span": {k: v * 1e-9 for k, v in idle.items()},
           "device_ops": [(k, v * 1e-9) for k, v in op_time.most_common(10)]}
    for key in ("decode", "prefill", "collective"):
        v = mean(f"{key}_ns")
        if v is not None:
            out[f"{key}_ms"] = v * 1e-6
    for key in ("decode", "prefill"):
        v = mean(f"{key}_runs")
        if v is not None:
            out[f"{key}_runs"] = v
    return out

"""The program's own spans and counters, for the per-layer metrics.

The serving engine records spans and counters (``engine.recorder``, a
``repro.exec.trace.Recorder``) on the host clock the harness uses, and
opens a ``serve.<name>`` profiler annotation for each span.  This module

* selects the spans and counters of the measured window: whole top-level
  spans (``decode``, ``admit``, a ``retire`` between them) that lie in
  ``[run.t0, run.t1]``, with every span under them and the counter
  increments made inside them;
* for a traced run, loads the ``serve.*`` and ``bench.*`` host events and
  the device op intervals from the run's ``.xplane.pb``;
* splits the device's idle time in the traced window by the innermost
  program span the host was in, and, where the host was in none, by the
  harness span.  Host events and device ops share the profiler's clock.

A program without a recorder (or a trace without ``serve.*`` events)
gives ``None`` everywhere, and the readers report nothing.
"""
from __future__ import annotations

import collections
import dataclasses
from pathlib import Path

from bench import tracefile


@dataclasses.dataclass(frozen=True)
class Window:
    spans: list                  # the recorder's Span records, closing order
    counts: collections.Counter  # counter increments inside them

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


def window(run) -> Window | None:
    """The recorder's spans of the window ``[run.t0, run.t1]``; ``None``
    without a recorder, or where spans that may lie in the window were
    evicted from its ring."""
    rec = getattr(getattr(run.hooks, "engine", None), "recorder", None)
    if rec is None:
        return None
    spans = rec.spans()
    # Spans close in order: an evicted one ended before the oldest kept.
    if rec.dropped and (not spans or spans[0].end >= run.t0):
        return None
    parent = {s.id: s.parent for s in spans}
    roots = {s.id for s in spans if s.parent is None
             and run.t0 <= s.start and s.end <= run.t1}

    def root(sid):
        while parent.get(sid) is not None:
            sid = parent[sid]
        return sid

    kept = [s for s in spans if root(s.id) in roots]
    counts = collections.Counter()
    for s in kept:
        if s.counts:
            counts.update(s.counts)
    return Window(kept, counts)


def load(path) -> dict:
    """Plain records of one trace file: per device its op intervals
    (``[None, start_ns, duration_ns]``), and the host's ``program``
    (``serve.*``) and ``harness`` (``bench.*``) events as ``[label,
    start_ns, duration_ns]`` with the prefix taken off."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "program": [], "harness": []}
    for plane in data.planes:
        m = tracefile.DEVICE.match(plane.name)
        if m:
            out["devices"][int(m.group(1))] = [
                [None, e.start_ns, e.duration_ns]
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]
            continue
        for line in plane.lines:
            for e in line.events:
                for key, prefix in (("program", "serve."),
                                    ("harness", "bench.")):
                    if e.name.startswith(prefix):
                        out[key].append([e.name[len(prefix):], e.start_ns,
                                         e.duration_ns])
    return out


def innermost(spans: list) -> list:
    """Properly nested ``[label, start, duration]`` spans as disjoint
    pieces labelled by the innermost span over them, sorted."""
    out = []

    def emit(label, a, b):
        if b > a:
            out.append([label, a, b - a])

    stack, t = [], 0.0
    for label, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            emit(top, t, end)
            t = end
        if stack:
            emit(stack[-1][0], t, s)
        stack.append((label, s + d))
        t = s
    while stack:
        top, end = stack.pop()
        emit(top, t, end)
        t = end
    return out


def idle_split(busy_ivs: list, program: list, harness: list, lo: float,
               hi: float) -> collections.Counter:
    """Idle time of one device in ``[lo, hi]`` (the complement of
    ``busy_ivs``) by ``(program span, harness span)`` the host was in:
    the innermost ``program`` span, and the ``harness`` span (``-``:
    none)."""
    prog = innermost(program)
    harn = sorted(harness, key=lambda s: s[1])
    cuts = {lo, hi}
    for spans in (prog, harn):
        for _, s, d in spans:
            cuts.update(x for x in (s, s + d) if lo < x < hi)
    cuts = sorted(cuts)
    starts = {id(spans): [s for _, s, _ in spans] for spans in (prog, harn)}
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        key = tuple(tracefile.span_at(spans, starts[id(spans)], mid)
                    for spans in (prog, harn))
        if pieces and pieces[-1][0] == key and pieces[-1][1] + \
                pieces[-1][2] == a:
            pieces[-1][2] += b - a
        else:
            pieces.append([key, a, b - a])
    out = tracefile.idle_by_span(busy_ivs, pieces, lo, hi)
    if "-" in out:                      # rounding at the pieces' edges
        out[("-", "-")] += out.pop("-")
    return out


def traced_split(run) -> dict | None:
    """The traced window's idle time of the first device by (program
    span, harness span), in seconds, with ``window_s`` and ``idle_s``;
    ``None`` off a chip, or where the trace holds no program span."""
    tracer = getattr(run, "tracer", None)
    if tracer is None or tracer.started_at is None or not run.trace:
        return None
    files = sorted(Path(tracer.log_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    tr = load(files[-1])
    if not tr["devices"] or not tr["program"]:
        return None
    # The window bench/run.py reduces: the profiler's session, put on the
    # trace's clock by the first harness span.
    off = tracefile.host_offset_ns({"spans": tr["harness"]}, run.hooks.spans,
                                   tracer.started_at)
    if off is None:
        lo, hi = 0.0, (tracer.stopped_at - tracer.started_at) * 1e9
    else:
        lo = max(0.0, tracer.started_at * 1e9 + off)
        hi = tracer.stopped_at * 1e9 + off
    ivs = tracefile.busy(tr["devices"][min(tr["devices"])], lo, hi)
    split = idle_split(ivs, tr["program"], tr["harness"], lo, hi)
    return {"window_s": (hi - lo) * 1e-9,
            "idle_s": sum(split.values()) * 1e-9,
            "split": {k: v * 1e-9 for k, v in split.items()}}

"""Spans and counters of the serving engine, on the host clock.

A :class:`Recorder` keeps what the engine did as spans (name, start, end,
its id, its parent's id, and the request it belongs to, if one) and as
integer counters bumped at the same boundaries.  The clock is
``time.perf_counter``; this module sits in ``exec/``, outside the
determinism lint's ``wall-clock`` scope, for the same reason as
:mod:`repro.exec.timing`: it is the one place the serving path reads the
clock.  Nothing here goes into a persisted artifact.

Every span also opens a ``jax.profiler.TraceAnnotation("serve.<name>")``,
so that with the profiler on the spans land in its trace on the device
ops' clock; with the profiler off an annotation costs about a
microsecond.  The request id stays in the ring: the annotation would pay
to format it.  Recording is always on.  Spans go into a ring of
:data:`CAPACITY`; the oldest are evicted first and counted in
:attr:`Recorder.dropped`, so a reader can refuse a window whose spans are
gone.  A reader measures a stretch of serving by the spans that start in
it and by the difference of two copies of :attr:`Recorder.counters`.
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import NamedTuple, Optional

#: Spans the ring holds.  A 50-s window of the serving benchmark's
#: busiest cell closes about 10k spans.
CAPACITY = 1 << 16


class Span(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    rid: Optional[str]
    start: float                  # host clock, seconds
    end: float
    counts: Optional[dict]        # counter increments made while innermost


class _Open:
    """One span while it is open (the context manager ``span`` returns)."""

    __slots__ = ("rec", "name", "rid", "id", "parent", "start", "counts",
                 "ann")

    def __init__(self, rec: "Recorder", name: str, rid) -> None:
        self.rec, self.name, self.rid = rec, name, rid
        self.counts = None

    def __enter__(self) -> "_Open":
        rec = self.rec
        self.ann = rec._annotation("serve." + self.name)
        self.ann.__enter__()
        stack = rec._open
        self.parent = stack[-1].id if stack else None
        self.id = next(rec._ids)
        stack.append(self)
        self.start = rec.clock()
        return self

    def __exit__(self, *exc) -> None:
        rec = self.rec
        end = rec.clock()
        rec._open.pop()
        rec._ring.append((self.id, self.parent, self.name, self.rid,
                          self.start, end, self.counts))
        rec.closed += 1
        self.ann.__exit__(*exc)


class Recorder:
    """Spans in a ring and counters; one per engine."""

    def __init__(self, clock=time.perf_counter) -> None:
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.clock = clock
        self.counters: dict[str, int] = {}
        self.closed = 0               # spans recorded, evicted ones included
        self._ring: collections.deque = collections.deque(maxlen=CAPACITY)
        self._open: list[_Open] = []
        self._ids = itertools.count()

    def span(self, name: str, rid: Optional[str] = None) -> _Open:
        """``with recorder.span(name):``; the innermost open span is the
        parent of the next."""
        return _Open(self, name, rid)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to counter ``name``, and to the innermost open span's
        own ``counts``."""
        self.counters[name] = self.counters.get(name, 0) + n
        if self._open:
            top = self._open[-1]
            if top.counts is None:
                top.counts = {name: n}
            else:
                top.counts[name] = top.counts.get(name, 0) + n

    @property
    def dropped(self) -> int:
        """Spans evicted from the ring."""
        return self.closed - len(self._ring)

    def spans(self) -> list[Span]:
        """The spans the ring holds, in closing order (children before
        their parent)."""
        return list(map(Span._make, self._ring))

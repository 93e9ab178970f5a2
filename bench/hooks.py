"""Timestamps and open-loop injection from wrappers on one engine.

The harness does not copy the engine's loop.  It wraps three callables of
one ``ServingEngine`` instance, where they are found:

* ``engine.sched.admit``: submits every request that has fallen due, then
  admits as the scheduler would;
* ``engine.prefill_step.fn``: times the first token of a request when the
  last chunk of its prompt has its logits;
* ``engine.step.fn``: times every decode token when it is on the host.

Each wrapper entry is also where the window is closed (``WindowClosed``)
and where the profiler is started and stopped in a traced run.  The host
time between wrappers is kept as spans (``after_decode`` is the rest of a
decode iteration: page-out and retire), and, in a traced run, written into
the profiler's trace as ``bench.<span>`` annotations.  A wrapper whose
attribute is gone is not installed: its per-layer metrics find nothing,
and tokens it would have timed are timed late, at the next wrapper entry.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Optional

import numpy as np


class WindowClosed(Exception):
    """Raised from a wrapper once the measured window has ended."""


@dataclasses.dataclass
class Served:
    """What the harness knows of one request."""

    rid: str
    prompt: tuple
    max_new: int
    due_at: float                       # host clock
    admitted_at: Optional[float] = None
    state: object = None                # the engine's RequestState
    token_times: list = dataclasses.field(default_factory=list)

    @property
    def tokens(self) -> list:
        return list(self.state.generated) if self.state is not None else []

    @property
    def finished(self) -> bool:
        return self.state is not None and self.state.finish_time is not None


@dataclasses.dataclass(frozen=True)
class DecodeCall:
    start: float
    end: float                          # next tokens on the host
    live: tuple                         # positions attended, per active slot


class Tracer:
    """Starts and stops the JAX profiler ``offset`` seconds into the
    window, for ``length`` seconds."""

    def __init__(self, log_dir: str, offset: float, length: float) -> None:
        self.log_dir, self.offset, self.length = log_dir, offset, length
        self.start = self.stop = float("inf")
        self.requested_at = self.started_at = self.stopped_at = None

    def arm(self, t0: float) -> None:
        self.start = t0 + self.offset
        self.stop = self.start + self.length

    @property
    def on(self) -> bool:
        return self.started_at is not None and self.stopped_at is None

    def tick(self, now: float, clock) -> None:
        import jax
        if self.started_at is None and now >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # host spans are the harness's
            self.requested_at = now
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.started_at = clock()
        elif self.on and now >= self.stop:
            self.close(clock)

    def close(self, clock) -> None:
        import jax
        if self.on:
            self.stopped_at = clock()      # writing the trace comes after
            jax.profiler.stop_trace()


class Hooks:
    def __init__(self, engine, clock=time.perf_counter,
                 tracer: Optional[Tracer] = None) -> None:
        self.engine = engine
        self.clock = clock
        self.tracer = tracer
        self.requests: dict[str, Served] = {}
        self.pending: collections.deque = collections.deque()  # not submitted
        self.t0: Optional[float] = None          # window
        self.t1: Optional[float] = None
        self.seconds: Optional[float] = None
        self.spans: list[tuple[str, float, float]] = []
        self.decode_calls: list[DecodeCall] = []
        self.prefill_calls: list[tuple[float, float]] = []
        self.installed: set[str] = set()
        self.admitted: list[Served] = []
        self._prefill_fifo: collections.deque = collections.deque()
        self._gap: Optional[tuple[str, float, object]] = None

    # ------------------------------------------------------------------ #
    def open_window(self, t0: float, seconds: float) -> None:
        self.t0, self.seconds, self.t1 = t0, seconds, t0 + seconds
        if self.tracer is not None:
            self.tracer.arm(t0)

    def add(self, served: Served, submitted: bool) -> None:
        self.requests[served.rid] = served
        if not submitted:
            self.pending.append(served)

    def install(self, request_of) -> None:
        """Wrap what the engine has.  ``request_of(served)`` makes the
        engine's ``Request`` for a request that falls due."""
        eng = self.engine
        sched = getattr(eng, "sched", None)
        if callable(getattr(sched, "admit", None)):
            sched.admit = self._wrap_admit(sched.admit, request_of)
            self.installed.add("admit")
        pre = getattr(eng, "prefill_step", None)
        if callable(getattr(pre, "fn", None)):
            pre.fn = self._wrap_prefill(pre.fn)
            self.installed.add("prefill")
        step = getattr(eng, "step", None)
        if callable(getattr(step, "fn", None)):
            step.fn = self._wrap_decode(step.fn)
            self.installed.add("decode")

    # ------------------------------------------------------------------ #
    def _annotation(self, label: str):
        if self.tracer is None or not self.tracer.on:
            return None
        import jax
        ann = jax.profiler.TraceAnnotation(f"bench.{label}")
        ann.__enter__()
        return ann

    def begin_gap(self, label: str, t: float) -> None:
        self._gap = (label, t, self._annotation(label))

    def end_gap(self, t: float) -> None:
        if self._gap is not None:
            label, t0, ann = self._gap
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.append((label, t0, t))
            self._gap = None

    def enter(self, label: str) -> float:
        """Entry of a wrapper (or of the harness's wait): closes the open
        gap, starts or stops the profiler, times late tokens, and closes
        the window when it has ended."""
        t = self.clock()
        self.end_gap(t)
        if self.tracer is not None:
            self.tracer.tick(t, self.clock)
        self.sweep(t)
        if self.t1 is not None and t >= self.t1:
            raise WindowClosed
        return t

    def leave(self, label: str, t0: float, t1: float) -> None:
        self.spans.append((label, t0, t1))
        self.begin_gap(f"after_{label}", t1)

    def sweep(self, t: float) -> None:
        """Time tokens no wrapper timed (a wrapper that is gone)."""
        for r in self.admitted:
            missing = len(r.state.generated) - len(r.token_times)
            r.token_times.extend([t] * max(0, missing))

    def submit_due(self, t: float) -> list:
        due = []
        while self.pending and self.pending[0].due_at <= t:
            due.append(self.pending.popleft())
        return due

    # ------------------------------------------------------------------ #
    def _wrap_admit(self, admit, request_of):
        def wrapped(now=0.0):
            t = self.enter("admit")
            for r in self.submit_due(t):
                self.engine.sched.submit(request_of(r))
            states = admit(now)
            t_end = self.clock()
            chunk = getattr(self.engine, "prefill_chunk", None)
            for st in states:
                r = self.requests[st.req.rid]
                r.state, r.admitted_at = st, t_end
                self.admitted.append(r)
                if chunk:
                    self._prefill_fifo.append(
                        [r, math.ceil(st.req.prompt_len / chunk)])
            self.leave("admit", t, t_end)
            return states
        return wrapped

    def _wrap_prefill(self, fn):
        import jax

        def wrapped(params, batch, cache):
            t = self.enter("prefill")
            ann = self._annotation("prefill")
            out = fn(params, batch, cache)
            if self._prefill_fifo:
                head = self._prefill_fifo[0]
                head[1] -= 1
                if head[1] == 0:           # the prompt's last chunk
                    jax.block_until_ready(out[0])
                    head[0].token_times.append(self.clock())
                    self._prefill_fifo.popleft()
            if ann is not None:
                ann.__exit__(None, None, None)
            t_end = self.clock()
            self.prefill_calls.append((t, t_end))
            self.leave("prefill", t, t_end)
            return out
        return wrapped

    def _wrap_decode(self, fn):
        def wrapped(params, batch, cache):
            t = self.enter("decode")
            active = list(self.engine.sched.active.values())
            if self.t0 is None and self.seconds is not None and (
                    len(active) == self.engine.slots
                    or not len(self.engine.sched.queue)):
                self.open_window(t, self.seconds)    # a backlog's window
            ann = self._annotation("decode")
            nxt, cache = fn(params, batch, cache)
            nxt = np.asarray(nxt)
            t_host = self.clock()
            if ann is not None:
                ann.__exit__(None, None, None)
            for st in active:
                self.requests[st.req.rid].token_times.append(t_host)
            self.decode_calls.append(
                DecodeCall(t, t_host, tuple(st.pos for st in active)))
            self.leave("decode", t, t_host)
            return nxt, cache
        return wrapped

"""End-to-end metrics from the host times the hooks recorded.

``percentile`` is a copy of ``repro.serve.metrics.percentile`` (nearest
rank), pinned against it by ``tests/bench``.
"""
from __future__ import annotations

import math


def percentile(xs: list[float], p: float) -> float:
    """Nearest-rank percentile (deterministic, no interpolation)."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[rank - 1]


def ttft_samples(requests, t0: float, t1: float) -> list[float]:
    """Time to first token of every request due inside ``[t0, t1]``, from
    its due time; one with no first token by ``t1`` counts with its age
    at ``t1``."""
    out = []
    for r in requests:
        if not t0 <= r.due_at <= t1:
            continue
        first = r.token_times[0] if r.token_times else math.inf
        out.append(min(first, t1) - r.due_at)
    return out


def itl_samples(requests, t0: float, t1: float) -> list[float]:
    """Every gap between consecutive tokens of one request with both
    tokens on the host inside ``[t0, t1]``, in seconds."""
    return [b - a for r in requests
            for a, b in zip(r.token_times, r.token_times[1:])
            if t0 <= a and b <= t1]


def tokens_in(requests, t0: float, t1: float) -> int:
    return sum(t0 <= t <= t1 for r in requests for t in r.token_times)


def end_to_end(name: str, requests, t0: float, t1: float):
    """The value of end-to-end metric ``name`` (other than ``setup_s``),
    or None where the window holds nothing to compute it from."""
    if name == "ttft_p90_s":
        xs = ttft_samples(requests, t0, t1)
        return percentile(xs, 90) if xs else None
    if name == "itl_p99_ms":
        xs = itl_samples(requests, t0, t1)
        return 1e3 * percentile(xs, 99) if xs else None
    if name == "tokens_per_s":
        n = tokens_in(requests, t0, t1)
        return n / (t1 - t0) if n else None
    raise KeyError(f"no end-to-end metric {name!r}")

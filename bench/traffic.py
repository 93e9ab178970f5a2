"""Seeded traffic for one cell, from a mix file of parameters.

The length and gap laws are copies of ``repro.serve.traffic``'s
(``parse_length_dist``, ``poisson_arrivals``), so that no later change to
the program can change the yardstick; ``tests/bench`` pins that both give
the same draws.

A mix fixes its sizes and gaps, in order: they are drawn once from the
mix's own ``shape_seed``, one realization of its laws.  The run's
``--seed`` draws the prompt tokens (and, in the harness, the weights).  So
every seed offers the same work at the same times, and the spread between
runs is the system's, not the draw's: on qwen2-1.5b.chat-p80 the same
sizes and gaps in an order drawn from the seed moved the p90 of time to
first token by 26-28% (IQR over six seeds), where one seed run twice
repeated within 0.2% in five pairs of six.
"""
from __future__ import annotations

import dataclasses
import json
import math
import random
from pathlib import Path

#: Token ids below this are left out of prompts (special tokens).
FIRST_TOKEN = 3


def parse_length_dist(spec: str):
    """A ``rng -> int`` sampler from a distribution spec string."""
    parts = spec.split(":")
    kind = parts[0]
    if kind == "fixed":
        n = int(parts[1])
        return lambda rng: n
    if kind == "uniform":
        lo, hi = int(parts[1]), int(parts[2])
        if lo > hi:
            raise ValueError(f"uniform bounds reversed in {spec!r}")
        return lambda rng: rng.randint(lo, hi)
    if kind == "lognormal":
        median, sigma, cap = float(parts[1]), float(parts[2]), int(parts[3])
        mu = math.log(median)
        return lambda rng: max(1, min(cap,
                                      round(rng.lognormvariate(mu, sigma))))
    raise ValueError(f"unknown length distribution {spec!r} "
                     "(fixed:N | uniform:LO:HI | lognormal:MED:SIGMA:MAX)")


def poisson_arrivals(qps: float, n: int, rng: random.Random) -> list[float]:
    """``n`` cumulative arrival times at rate ``qps`` (exponential gaps);
    ``qps <= 0`` means everything arrives at t=0 (offline batch)."""
    if qps <= 0:
        return [0.0] * n
    t, out = 0.0, []
    for _ in range(n):
        t += rng.expovariate(qps)
        out.append(t)
    return out


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of the traffic: its tokens, its output length, and when
    it is due, in seconds after the window opens (0 for a backlog)."""

    rid: str
    prompt: tuple
    max_new: int
    due: float


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix["arrivals"] not in ("poisson", "backlog"):
        raise ValueError(f"{path}: arrivals {mix['arrivals']!r} is neither "
                         "'poisson' nor 'backlog'")
    return mix


def _sizes(mix: dict, n: int) -> list[tuple[int, int]]:
    rng = random.Random(mix["shape_seed"])
    prompts = parse_length_dist(mix["prompt"])
    outputs = parse_length_dist(mix["output"])
    return [(prompts(rng), outputs(rng)) for _ in range(n)]


def _gaps(rate: float, seconds: float, shape_seed: int) -> list[float]:
    """Gaps of the mix's fixed Poisson draw, as many as fit in
    ``seconds`` in total (so every order of them does)."""
    n = math.ceil(2 * rate * seconds) + 64
    times = poisson_arrivals(rate, n, random.Random(shape_seed))
    if times[-1] <= seconds:
        raise ValueError(f"{n} arrivals at {rate}/s end before {seconds} s")
    times = [t for t in times if t <= seconds]
    return [b - a for a, b in zip([0.0] + times[:-1], times)]


def make_traffic(mix: dict, cell: dict, seed: int, vocab: int,
                 seconds: float) -> list[Item]:
    """The requests of one run, in due order.

    Open loop (``poisson``): the gaps of a Poisson process at the cell's
    ``rate_per_s`` that fit in the window.  Offline (``backlog``): the
    cell's ``backlog`` requests, all due when the window opens.  ``seed``
    draws only the prompt tokens."""
    if mix["arrivals"] == "poisson":
        gaps = _gaps(cell["rate_per_s"], seconds, mix["shape_seed"])
        n = len(gaps)
    else:
        n = cell["backlog"]
        gaps = [0.0] * n
    sizes = _sizes(mix, n)
    rng = random.Random(seed)
    out, due = [], 0.0
    for i, (gap, (plen, max_new)) in enumerate(zip(gaps, sizes)):
        due += gap
        prompt = tuple(rng.randrange(FIRST_TOKEN, vocab) for _ in range(plen))
        out.append(Item(f"q{i:04d}", prompt, max_new, due))
    return out

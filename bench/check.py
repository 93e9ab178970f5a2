"""What decides ``correct``: the served tokens against the reference.

Once the window has closed, a sample drawn from the seed of the requests
that were served, with the one that got the most tokens in it, is run
through the plain float32 reference (``bench.reference``) over each
prompt with its served tokens.  The number compared is the widest gap by
which a served token's reference logit lies below the reference's best
at that position.  Greedy serving at the program's own bfloat16 leaves
only near ties; a wrong token lies far below.
"""
from __future__ import annotations

import random

import numpy as np

from bench import reference


def sample(served: list, seed: int, tokens: int, rows: int) -> list:
    """Requests to compare: the one with the most served tokens, then
    others drawn from the seed until ``tokens`` served tokens or ``rows``
    requests.  ``served``: ``(rid, prompt, tokens)`` with tokens."""
    pool = sorted((s for s in served if s[2]), key=lambda s: s[0])
    if not pool:
        return []
    longest = max(pool, key=lambda s: len(s[2]))
    rest = [s for s in pool if s is not longest]
    random.Random(seed).shuffle(rest)
    out, n = [longest], len(longest[2])
    for s in rest:
        if n >= tokens or len(out) >= rows:
            break
        out.append(s)
        n += len(s[2])
    return out


def compare(config: dict, param_seed: int, picked: list, t: int, rows: int,
            control: bool = False) -> dict:
    """Readings over ``picked``: the widest served gap, the tokens
    compared, and with ``control`` the widest gap of the float8 forward's
    first choices at the same positions."""
    out = reference.gaps(config, param_seed, [(p, s) for _, p, s in picked],
                         t, rows, control=control)
    served = np.concatenate(out["served"]) if out["served"] else np.zeros(0)
    res = {"logit_gap": float(served.max()) if served.size else None,
           "tokens_compared": int(served.size),
           "requests_compared": len(picked)}
    if control:
        res["control_gap"] = float(np.concatenate(out["control"]).max())
    return res


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, checks).  ``limits``: name -> ``{"max": x}`` or
    ``{"min": x}``.  A reading that is missing fails."""
    checks, ok = {}, True
    for name, lim in limits.items():
        (kind, limit), = lim.items()
        value = readings.get(name)
        passed = value is not None and (
            value <= limit if kind == "max" else value >= limit)
        checks[name] = {"value": value, kind: limit}
        ok = ok and passed
    return ok, checks

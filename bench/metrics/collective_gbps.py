"""Collectives: the bytes each device puts into the decode program's
collectives (the program's ``psum_bytes`` counter, charged under
``decode.step``) over their device time per run (``collective_ms``), in
GB/s (1e9 B/s): the row-parallel psums' achieved rate.  ``bench/peaks.py``
holds no peak of the chip-to-chip links, so it gives no share.  Moves
``itl_p99_ms``."""
from bench import programspans


def read(run):
    ms = (run.trace or {}).get("collective_ms")
    w = programspans.window(run)
    if not ms or w is None:
        return None
    steps = [s.counts["psum_bytes"] for s in w.named("decode.step")
             if s.counts and "psum_bytes" in s.counts]
    if not steps:
        return None
    return sum(steps) / len(steps) / (ms * 1e-3) / 1e9

"""Decode program as the kernel layer: the least time a decode step needs
on one chip (the larger of its FLOPs over peak and its bytes over HBM
bandwidth; ``bench/counts.py``: parameters once as held, K/V at the live
positions of the active slots) over its device time in the trace, in %.
Moves ``itl_p99_ms``."""


def read(run):
    tr = run.trace or {}
    if "decode_ms" not in tr or run.tracer is None:
        return None
    calls = run.decode_calls_in(run.tracer.started_at, run.tracer.stopped_at)
    if not calls:
        return None
    c, cfg = run.counts, run.config
    least = [c.least_time(c.decode_flops(cfg, k.live),
                          c.decode_bytes(cfg, k.live, run.param_bytes,
                                         run.chips),
                          run.peaks, run.chips)[0] for k in calls]
    return 100.0 * (sum(least) / len(least)) / (tr["decode_ms"] * 1e-3)

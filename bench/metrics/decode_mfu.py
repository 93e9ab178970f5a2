"""Model step: model FLOPs of the decode iterations in the window (one
token per active slot, attention at live positions) over their summed wall
time (decode step plus the rest of the iteration, host clock) times chips
times peak bf16 FLOP/s, in %.  Moves ``itl_p99_ms``."""


def read(run):
    calls = run.decode_calls_in(run.t0, run.t1)
    rest = {a: b for a, b in run.spans_in("after_decode", run.t0, run.t1)}
    if not calls:
        return None
    flops = sum(run.counts.decode_flops(run.config, c.live) for c in calls)
    wall = sum(rest.get(c.end, c.end) - c.start for c in calls)
    return 100.0 * flops / (wall * run.chips * run.peaks["bf16_flops"])

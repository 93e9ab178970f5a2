"""Collectives: device time of the collective ops (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) inside runs of
the decode program, per run, averaged over devices, from the trace
(``bench/tracefile.py``'s ``collective_ms``): at TP, the row-parallel
psums.  Moves ``itl_p99_ms``."""


def read(run):
    return (run.trace or {}).get("collective_ms")

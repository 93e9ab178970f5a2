"""Decode step: device time per run of the decode program, from the trace,
averaged over devices.  Moves ``itl_p99_ms``."""


def read(run):
    return (run.trace or {}).get("decode_ms")

"""Prefill: device time per run of the prefill program (one chunk), from
the trace, averaged over devices.  Moves ``ttft_p90_s``."""


def read(run):
    return (run.trace or {}).get("prefill_ms")

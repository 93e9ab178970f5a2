"""Page-out and seat: share of the traced window in which the device is
idle while the host is inside the program's ``page_out`` span (the
``serve.page_out`` annotations and the device ops of the trace, on one
clock), first device, in %.  Moves ``itl_p99_ms``."""
from bench import programspans


def read(run):
    sp = programspans.traced_split(run)
    if sp is None:
        return None
    idle = sum(v for (prog, _), v in sp["split"].items() if prog == "page_out")
    return 100.0 * idle / sp["window_s"]

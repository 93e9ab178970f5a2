"""Page-out and seat: bytes copied from the device to the host (the
program's ``page_out_bytes`` counter: decode and prefill rows) per decode
step (``decode_steps``), over the window, in MB (1e6 B).  A count: it
repeats on every seed where the rows paged out do.  Moves
``itl_p99_ms``."""
from bench import programspans


def read(run):
    w = programspans.window(run)
    if w is None or not w.counts["decode_steps"]:
        return None
    return w.counts["page_out_bytes"] / w.counts["decode_steps"] / 1e6

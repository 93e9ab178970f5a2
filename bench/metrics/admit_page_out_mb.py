"""Page-out and seat: bytes copied from the device to the host under the
program's ``prefill`` spans (its ``page_out_bytes`` counter: the prompt's
cache positions) per admitted request, over the window, in MB (1e6 B).
Moves ``itl_p99_ms``."""
from bench import programspans


def read(run):
    w = programspans.window(run)
    if w is None:
        return None
    prefills = {s.id for s in w.named("prefill")}
    if not prefills:
        return None
    paged = sum(s.counts.get("page_out_bytes", 0) for s in w.spans
                if s.parent in prefills and s.counts)
    return paged / len(prefills) / 1e6

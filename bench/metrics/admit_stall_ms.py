"""Scheduler and prefill step: mean of the program's ``admit`` spans that
admitted at least one request (their ``admitted`` count), over the
window: the time every active request waits for an admission's chunked
prefill, page-out and seat.  Moves ``itl_p99_ms``."""
from bench import programspans


def read(run):
    w = programspans.window(run)
    if w is None:
        return None
    spans = [s for s in w.named("admit")
             if (s.counts or {}).get("admitted")]
    if not spans:
        return None
    return 1e3 * sum(s.end - s.start for s in spans) / len(spans)

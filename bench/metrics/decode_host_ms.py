"""Page-out / seat: host time per decode iteration outside the step calls
(from the decode step's result on the host to the next admission: the
row copies to the host pool, and retire), harness spans on the host clock,
over the window.  Moves ``itl_p99_ms``."""


def read(run):
    spans = run.spans_in("after_decode", run.t0, run.t1)
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)

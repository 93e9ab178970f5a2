"""Scheduler: 90th percentile of the wait from a request's due time to its
admission, over requests due inside the window (one never admitted counts
with its age at the window's end).  Host clock; moves ``ttft_p90_s``."""


def read(run):
    hooks = run.hooks
    if "admit" not in hooks.installed:
        return None
    waits = [min(r.admitted_at if r.admitted_at is not None else run.t1,
                 run.t1) - r.due_at
             for r in hooks.requests.values() if run.t0 <= r.due_at <= run.t1]
    return run.stats.percentile(waits, 90) if waits else None

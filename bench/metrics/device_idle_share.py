"""Device: share of the traced window in which no operation ran (1 minus
the union of operation intervals over the window), averaged over devices,
in %.  Moves ``tokens_per_s``."""


def read(run):
    tr = run.trace or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

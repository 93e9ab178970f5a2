"""Page-out and seat: host time per decode iteration in the program's
``page_out`` spans under ``decode`` (the device-to-host copy of each
active slot's cache row), from the engine's recorder, over the window.
Moves ``itl_p99_ms``."""
from bench import programspans


def read(run):
    w = programspans.window(run)
    if w is None:
        return None
    decodes = {s.id for s in w.named("decode")}
    if not decodes:
        return None
    t = sum(s.end - s.start for s in w.named("page_out")
            if s.parent in decodes)
    return 1e3 * t / len(decodes)

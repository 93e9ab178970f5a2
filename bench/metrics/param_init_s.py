"""Set-up: seconds the engine took to make its weights on the device
(``ServingEngine.setup_s["params"]``: tracing, compile or cache load, and
the run of the jitted init).  Moves ``setup_s``."""


def read(run):
    return run.setup.get("params")

"""Programs the engine compiled inside the window; has to read 0."""


def read(run):
    return run.counters.get("recompiles")

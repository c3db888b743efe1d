"""Events in the window's telemetry log over requests completed in it: what
the tracing itself emits."""

from benchmark import phase_readers


def read(run):
    return phase_readers.events_per_request(run)

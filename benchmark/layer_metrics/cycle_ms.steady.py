"""Median duration of the ``serving.cycle`` spans that launched and ended
before the profiler started, in ms: one turn of the engine's loop."""

from benchmark import phase_readers


def read(run):
    return phase_readers.cycle_ms(run)

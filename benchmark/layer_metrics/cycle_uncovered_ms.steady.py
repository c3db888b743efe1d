"""Median over those cycles of the cycle less the union of its child spans,
in ms: the loop's own time, which no phase names."""

from benchmark import phase_readers


def read(run):
    return phase_readers.cycle_uncovered_ms(run)

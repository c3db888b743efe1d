"""Median ``serving.state_restore`` span (the dispatch that copies a
snapshot's lightning states into the admitted row), in ms."""

from benchmark import phase_readers


def read(run):
    return phase_readers.span_median_ms(run, "serving.state_restore")

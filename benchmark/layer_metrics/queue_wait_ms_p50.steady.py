"""Median wait from submit to admission (``RequestTrace.breakdown``), in ms."""

from benchmark import readers


def read(run):
    return readers.median_of(run, "queue_wait_ms")

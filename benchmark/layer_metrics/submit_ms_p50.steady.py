"""Median ``serving.submit`` span (the caller's thread: ``queue.submit``
under the trace context) before the profiler started, in ms."""

from benchmark import phase_readers


def read(run):
    return phase_readers.span_median_ms(run, "serving.submit")

"""Median duration of the ``serve_decode_paged`` spans in the window, in ms."""

from benchmark import readers


def read(run):
    return readers.median_of(run, "launch_ms")

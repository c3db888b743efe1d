"""Median distance from a ``serve_decode_paged`` span's end to the next one's
start, in ms."""

from benchmark import readers


def read(run):
    return readers.median_of(run, "launch_gap_ms")

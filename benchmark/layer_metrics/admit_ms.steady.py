"""Median over those cycles of their time under ``serving.admit`` (take,
prefill dispatch, page bookkeeping), in ms."""

from benchmark import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "serving.admit")

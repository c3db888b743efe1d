"""Median ``train.data_wait`` span of the window's steps, in ms: the time the
loop waited for its loader."""

from benchmark import phase_readers


def read(run):
    return phase_readers.loader_wait_ms(run)

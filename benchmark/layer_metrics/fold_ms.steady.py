"""Median ``serving.launch.fold`` of those cycles, in ms: the rows x steps
loop over the launch's emitted tokens."""

from benchmark import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "serving.launch.fold")

"""Median ``serving.launch.wait`` of those cycles, in ms: the host blocked on
the chip until every output of the launch is on the host."""

from benchmark import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "serving.launch.wait")

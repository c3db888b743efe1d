"""All-reduce time a step with no compute running on that chip, in ms."""

from benchmark import readers


def read(run):
    return readers.collective_exposed_ms_per_step(run)

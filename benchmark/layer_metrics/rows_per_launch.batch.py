"""Mean ``rows`` of the ``serving.batch`` spans in the window."""

from benchmark import readers


def read(run):
    return readers.mean_of(run, "rows_per_launch")

"""Roofline share of the step's flash-attention forward kernels, in percent."""

from benchmark import readers


def read(run):
    return readers.flash_forward_roofline_percent(run)

"""Median run of the ``jit_paged_launch`` program on the chip, in ms."""

from benchmark import phase_readers


def read(run):
    return phase_readers.launch_device_ms(run)

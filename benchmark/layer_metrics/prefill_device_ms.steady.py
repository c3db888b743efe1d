"""Device time of the ``jit_paged_prefill_c*`` programs from one launch's
start to the next's, median, in ms."""

from benchmark import phase_readers


def read(run):
    return phase_readers.prefill_device_ms(run)

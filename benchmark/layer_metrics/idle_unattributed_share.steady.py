"""Of the chip's idle seconds over the whole cycles of the trace, the share
under none of the engine thread's phase annotations, in percent."""

from benchmark import phase_readers


def read(run):
    return phase_readers.idle_unattributed_percent(run)

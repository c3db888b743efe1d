"""The whole step's share of the chips' bf16 peak over the window, in percent."""

from benchmark import readers


def read(run):
    return readers.train_mfu_percent(run)

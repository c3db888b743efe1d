"""Prefill and decode operations of the requests finished in the window over
the chip's bf16 peak, in percent."""

from benchmark import readers


def read(run):
    return readers.window_mfu_percent(run, "window_flops")

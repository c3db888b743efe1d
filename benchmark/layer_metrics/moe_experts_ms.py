"""Device time a whole step under ``lm.moe.experts`` (the grouped products
over the held experts, forward and backward), in ms."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.moe.experts")

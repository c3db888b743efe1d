"""Device time a whole launch spends under ``lm.moe.experts`` (the grouped
products over the held experts), every expert layer and step, in ms."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.moe.experts")

"""Device time a whole launch spends under ``lm.moe.route`` (the router's
product, the grouped sigmoid selection, the sort of the assignments and the
scatter back), every expert layer and step, in ms."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.moe.route")

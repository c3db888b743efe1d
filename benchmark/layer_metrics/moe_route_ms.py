"""Device time a whole step under ``lm.moe.route`` (router, top-k, sort,
gather and weighted scatter-add), in ms."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.moe.route")

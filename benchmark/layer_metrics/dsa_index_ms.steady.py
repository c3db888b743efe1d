"""Device time a whole launch spends under ``lm.dsa.index``: the index
queries, keys and weights, the index keys' writes, the scan of every cached
position and the top-k selection, every layer and step, in ms."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.dsa.index")

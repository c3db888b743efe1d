"""Device time a whole launch spends under ``lm.sparse_attn.select``: the
gather of the unit means through the block tables, the block scores and the
top-k (a part of ``sparse_attn_ms.steady``), in ms."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.sparse_attn.select")

"""Device time a whole launch (``jit_paged_launch``) spends under
``lm.sparse_attn``: the sparse layers' page writes, selection and attention
over the selected blocks, every step of the launch, in ms."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.sparse_attn")

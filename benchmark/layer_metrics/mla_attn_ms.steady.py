"""Device time a whole launch (``jit_paged_launch``) spends under ``lm.mla``:
the latent rows' writes, the gather of the selected rows and the absorbed
attention over them, every layer and step of the launch, in ms."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.mla")

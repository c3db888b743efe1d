"""Device time a whole launch spends in the lightning layers, in ms: the
state update and output product under ``lm.lightning``, every step of the
launch, and the copies that carry the state planes between HBM and fast
memory around them, which no scope names (``kinds/serve_lm.py`` tells them by
the planes' shape)."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.lightning")

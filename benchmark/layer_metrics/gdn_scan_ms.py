"""Device time a whole step under ``lm.gdn_scan`` (the chunked gated delta
rule of every linear-attention layer, forward and backward), in ms."""

from benchmark import lm_readers


def read(run):
    return lm_readers.scope_ms(run, "lm.gdn_scan")

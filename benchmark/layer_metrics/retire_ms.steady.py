"""Median ``serving.retire`` of those cycles, in ms: page release,
detokenising, ``set_result`` and the ledger's calls a finished request."""

from benchmark import phase_readers


def read(run):
    return phase_readers.phase_ms(run, "serving.retire")

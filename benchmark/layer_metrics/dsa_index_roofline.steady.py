"""Roofline share of the token indexer in a launch, in percent: the needed
operations and bytes (``flops_dsa_lm.index_step_cost``: each occupied row's
context of index keys read once a layer a step, 64 x 128 x 2 operations a
position) over the device time under ``lm.dsa.index``."""

from benchmark import lm_readers


def read(run):
    return lm_readers.roofline_percent(
        run, "index_cost_per_launch", lm_readers.scope_ms(run, "lm.dsa.index")
    )

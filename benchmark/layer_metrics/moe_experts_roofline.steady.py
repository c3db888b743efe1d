"""Roofline share of the held experts' grouped products in a launch, in
percent: one read of each held expert with an assignment (its three
matrices, 88 MB in bfloat16) and 6 x 7168 x 2048 operations an assignment,
from the launch's own counters (``flops_dsa_lm.experts_cost``), over the
device time under ``lm.moe.experts``."""

from benchmark import lm_readers


def read(run):
    return lm_readers.roofline_percent(
        run, "experts_cost_per_launch", lm_readers.scope_ms(run, "lm.moe.experts")
    )

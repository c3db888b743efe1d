"""Roofline share of the routed experts' grouped products, in percent: the
local assignments' operations and one read of the held experts' weights a
direction (``flops_hybrid_lm.experts_cost_per_step``) over the device time
under ``lm.moe.experts``."""

from benchmark import lm_readers


def read(run):
    return lm_readers.roofline_percent(
        run, "experts_cost_per_step", lm_readers.scope_ms(run, "lm.moe.experts")
    )

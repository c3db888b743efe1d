"""Roofline share of the full-attention layer's flash kernels, forward and
both backward kernels, in percent: their needed operations and bytes
(``flops_hybrid_lm.flash_cost_per_step``) over the device time of the step's
``flash_*`` Mosaic calls."""

from benchmark import lm_readers


def read(run):
    return lm_readers.roofline_percent(
        run, "lm_flash_cost_per_step", lm_readers.flash_ms_per_step(run)
    )

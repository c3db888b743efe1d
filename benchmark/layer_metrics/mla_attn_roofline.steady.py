"""Roofline share of the latent attention in a launch, in percent: the
needed operations and bytes (``flops_dsa_lm.mla_step_cost``: each occupied
row's ``min(index_topk, context + 1)`` latent rows read once a layer a step,
scores over 576 values and outputs over 512 for each of 128 heads) over the
device time under ``lm.mla``."""

from benchmark import lm_readers


def read(run):
    return lm_readers.roofline_percent(
        run, "mla_cost_per_launch", lm_readers.scope_ms(run, "lm.mla")
    )

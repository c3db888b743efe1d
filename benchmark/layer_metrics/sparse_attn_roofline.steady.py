"""Roofline share of the sparse attention in a launch, in percent: the
needed operations and bytes (``flops_sala_lm.sparse_launch_cost``: the context's unit
means and the selected blocks' K and V a row a KV head, from the rows' real
context lengths) over the device time under ``lm.sparse_attn``."""

from benchmark import lm_readers


def read(run):
    return lm_readers.roofline_percent(
        run, "sparse_attn_cost_per_launch",
        lm_readers.scope_ms(run, "lm.sparse_attn"),
    )

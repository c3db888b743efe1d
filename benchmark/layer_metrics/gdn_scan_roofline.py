"""Roofline share of the gated-delta scan, in percent: the recurrence's
needed operations and bytes (``flops_hybrid_lm.scan_cost_per_step``) over the
device time under ``lm.gdn_scan``."""

from benchmark import lm_readers


def read(run):
    return lm_readers.roofline_percent(
        run, "scan_cost_per_step", lm_readers.scope_ms(run, "lm.gdn_scan")
    )

"""What the language-model cell's per-layer readers share. As in
``readers.py``, a reader returns a number or None where it finds nothing to
read (an untraced run, a rehearsal off the chip, a program without the
scope): never 0 for a share of a peak."""

from __future__ import annotations

from benchmark import readers, trace_reduce

# The flash kernels' Mosaic calls by the HLO name the ``pallas_call(name=)``
# gives them (``%flash_fwd.2``, ``%flash_bwd_dq.1``, ``%flash_bwd_dkv.1``).
# The custom-call target alone does not tell them here: XLA lowers the
# experts' grouped products (``ragged_dot``) to Mosaic calls of its own
# (``%ragged-dot-none.N``; read in the step compiled for a described v5e).
FLASH_CALL = r'^%?flash_(fwd|bwd_dq|bwd_dkv)[.\d]* = .*custom_call_target="tpu_custom_call"'


def scope_ms(run, scope: str):
    """Device ms a whole step under one of the program's ``lm.*`` scopes
    (``kinds/train_lm.py`` reads them out of the trace)."""
    value = (run.counters.get("scope_ms") or {}).get(scope)
    return value if value else None


def roofline_percent(run, cost_counter: str, ms):
    """The least time the chip could take for ``(FLOPs, bytes)`` (the larger
    of FLOPs / peak and bytes / HBM bandwidth) over ``ms``, in percent."""
    table = readers.chip_peaks(run)
    cost = run.counters.get(cost_counter)
    if table is None or not cost or not ms:
        return None
    f, b = cost
    rows_share = 1.0 / run.chips  # a chip sees its shard of the rows
    compute, memory = f / table["bf16_flops_per_s"], b / table["hbm_bytes_per_s"]
    least = max(compute, memory) * rows_share
    run.note(f"{cost_counter}: {ms:.3f} ms a step a chip, least "
             f"{least * 1e3:.3f} ms, bound by "
             f"{'compute' if compute >= memory else 'memory'}")
    return 100.0 * least * 1e3 / ms


def flash_ms_per_step(run):
    """Device ms a whole step in the flash-attention kernels, a chip."""
    if run.trace_data is None:
        return None
    calls = trace_reduce.ops_matching(run.trace_data, FLASH_CALL)
    seconds, steps = 0.0, 0
    for chip, spans in readers.whole_train_steps(run).items():
        events = readers._inside(calls.get(chip, []), spans)
        seconds += sum(e.dur for e in events)
        steps += len(spans)
    if not steps or not seconds:
        return None
    return seconds / steps * 1e3

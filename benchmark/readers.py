"""What the per-layer readers share. A reader takes the run and returns a
number, or None where it finds nothing to read (the harness then leaves the
metric out of the line; it never reports 0 for a share of a peak)."""

from __future__ import annotations

import statistics

from benchmark import peaks, trace_reduce

# A Mosaic (Pallas) kernel in a trace: its event's HLO text names the custom
# call's target. The call's own name is the Pallas function's
# (``%flash_attention.3``) where it is launched directly and ``%shard_map.49``
# where ``fit(mesh=)`` launches it per shard (read from the step compiled for
# a described 2x2 v5e, PR 23), so the name does not tell a kernel; in a train
# step at these lengths the flash forwards are the only Mosaic calls, and the
# reader below holds their number to the count it expects.
MOSAIC_CALL = r'custom-call\(.*custom_call_target="tpu_custom_call"'
TRAIN_STEP_MODULE = r"^jit_step\("


def median_of(run, counter: str):
    values = run.counters.get(counter) or []
    return statistics.median(values) if values else None


def mean_of(run, counter: str):
    values = run.counters.get(counter) or []
    return sum(values) / len(values) if values else None


def chip_peaks(run) -> dict | None:
    """The chip's peaks; None off a TPU (a rehearsal has no peak to take a
    share of, so its line leaves those metrics out). A TPU that is not in
    the table is an error."""
    import jax

    device = jax.local_devices()[0]
    if device.platform != "tpu":
        return None
    return peaks.peaks_for(device.device_kind)


def window_mfu_percent(run, flops_counter: str, times: int = 1):
    """The window's required operations (``times`` the counter) over its
    elapsed clock, over the chips' peak, in percent."""
    total = times * (run.counters.get(flops_counter) or 0)
    table = chip_peaks(run)
    if not total or not run.window_s or table is None:
        return None
    return 100.0 * total / run.window_s / run.chips / table["bf16_flops_per_s"]


def train_mfu_percent(run):
    return window_mfu_percent(
        run, "step_flops", times=run.counters.get("steps", 0)
    )


def whole_train_steps(run) -> dict[int, list]:
    """Per chip, the runs of the train step's program that lie whole inside
    the trace: a trace that starts while the host is ahead of the device
    cuts the first one short, so a run much shorter than the median run is
    left out."""
    if run.trace_data is None:
        return {}
    out = {}
    for chip, runs in trace_reduce.module_runs(
        run.trace_data, TRAIN_STEP_MODULE
    ).items():
        if runs:
            typical = statistics.median(r.dur for r in runs)
            out[chip] = [r for r in runs if r.dur >= 0.9 * typical]
    return out


def _inside(events, spans):
    return [
        e for e in events
        if any(s.start <= e.start and e.end <= s.end + 1e-9 for s in spans)
    ]


def flash_forward_roofline_percent(run):
    """Least time the chip could take for the step's flash forwards (the
    larger of FLOPs / peak and bytes / HBM bandwidth, from the benchmark's
    own count) over the device time of the Mosaic calls named
    Mosaic calls inside whole steps of the trace. None unless every
    whole step holds exactly the forwards' number of calls (a backward
    kernel among them would make the share a guess); the count found is
    then noted on an earlier line."""
    table = chip_peaks(run)
    if (
        run.trace_data is None or table is None
        or "flash_cost_per_step" not in run.counters
    ):
        return None
    calls = trace_reduce.ops_matching(run.trace_data, MOSAIC_CALL)
    per_step = run.counters["flash_calls_per_step"]
    kernel_s, steps = 0.0, 0
    for chip, spans in whole_train_steps(run).items():
        events = _inside(calls.get(chip, []), spans)
        if not spans or len(events) != per_step * len(spans):
            run.note(f"flash forwards: chip {chip} holds {len(events)} Mosaic "
                     f"calls in {len(spans)} whole steps, not {per_step} a "
                     "step; no roofline share is reported")
            return None
        kernel_s += sum(e.dur for e in events)
        steps += len(spans)
    if not steps:
        return None
    f, b = run.counters["flash_cost_per_step"]
    rows_share = 1.0 / run.chips  # a chip's kernels see its shard of the rows
    least = max(f / table["bf16_flops_per_s"], b / table["hbm_bytes_per_s"])
    bound = "compute" if f / table["bf16_flops_per_s"] >= b / table["hbm_bytes_per_s"] else "memory"
    run.note(f"flash forwards: {kernel_s / steps * 1e3:.3f} ms a step a chip, "
             f"least {least * rows_share * 1e3:.3f} ms, bound by {bound}")
    return 100.0 * least * rows_share * steps / kernel_s


def collective_exposed_ms_per_step(run):
    """Collective time with no compute running on that chip, a step, over
    the whole steps of the trace, in ms."""
    if run.trace_data is None:
        return None
    whole = whole_train_steps(run)
    if not whole:
        return None
    clipped = trace_reduce.Trace(
        ops={c: _inside(run.trace_data.ops.get(c, []), s) for c, s in whole.items()},
        async_ops={
            c: _inside(run.trace_data.async_ops.get(c, []), s)
            for c, s in whole.items()
        },
        modules={}, host={},
    )
    exposed = trace_reduce.exposed_collective_seconds(clipped)
    steps = min(len(s) for s in whole.values())
    if exposed is None or not steps:
        return None
    return exposed / steps * 1e3

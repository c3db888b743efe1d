#!/usr/bin/env python
"""Benchmark — flagship Transformer MT workload + CNN, per-chip throughput.

Protocol per BASELINE.md: the reference publishes no numbers; its contract is
self-timed training throughput (``pytorch_machine_translator.py:199-205``
times batches of 32 × 200-token sentences; ``pytorch_cnn.py:123,148-151``
times the CNN epoch loop). Here the same workloads (reference hypers) run as
data-parallel jitted train steps in bfloat16, and ``vs_baseline`` is the
ratio against the reference-equivalent PyTorch model (same shapes, Adam/SGD)
measured on CPU in-process — the reference's own engine on the hardware it
targets (CPU-only end to end, SURVEY.md §3 observation b).

Chip-only: the bench measures the TPU and nothing else. A run that finds no
TPU exits non-zero naming what it found, an unknown ``device_kind`` is an
error (no default peak), and a stage that raises ends the run non-zero — no
number is ever produced by, or carried over from, anything but this run on
this device. Everything runs in this one process, which holds the chip.

Aggregation policy: the headline ``value`` is the MEDIAN of ``TRIALS``
timing windows; ``max``, the full trial list, and the max/min ``spread`` are
reported alongside so an outlier is visible, not hidden. ``mfu`` is analytic
matmul/conv FLOPs per train step (fwd + 2× bwd) over the device's peak bf16
FLOP/s, computed at the median.

Measurement protocol: 60-step warmup; windows at N and 4N steps, headline
from the long window, with a paired-window difference estimate
(``paired_window``) that cancels the fixed per-trial completion-barrier
cost; a ``scanned`` sub-result measuring the same MT workload through
``fit(steps_per_call=K)``'s fused-scan dispatch path.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
   "median": N, "max": N, "trials": [...], "spread": N, "mfu": N,
   "device": {"platform": ..., "kind": ..., "count": N},
   "scanned": {...}, "packed": {...}, "composed": {...},
   "sweep": [...], "cnn": {"value": N, "unit": "samples/sec/chip", ...}}
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import sys
import time

SEQ = 200
BATCH_PER_CHIP = int(os.environ.get("BENCH_BATCH", "32"))
SRC_VOCAB = 8192
TRG_VOCAB = 10240
D_MODEL, FFN, HEADS, LAYERS = 512, 1024, 8, 1


def _env_int(specific: str, generic: str, default: int) -> int:
    return int(os.environ.get(specific, os.environ.get(generic, default)))


TPU_WARMUP = _env_int("BENCH_TPU_WARMUP", "BENCH_WARMUP", 60)
# TPU windows must dwarf the fixed per-trial completion barrier: 60 steps
# is the short window, 240 the long one; the CNN step is far shorter and
# needs ~500.
TPU_STEPS = _env_int("BENCH_TPU_STEPS", "BENCH_STEPS", 60)
TPU_CNN_STEPS = _env_int("BENCH_TPU_CNN_STEPS", "BENCH_CNN_STEPS", 500)
TRIALS = int(os.environ.get("BENCH_TRIALS", "10"))
# Long-window multiplier for the paired-window protocol (see
# _paired_window_stats): windows of STEPS and LONG_WINDOW×STEPS are both
# measured; their difference cancels the fixed per-trial sync cost.
LONG_WINDOW = int(os.environ.get("BENCH_LONG_WINDOW", "4"))
CNN_BATCH_PER_CHIP = int(os.environ.get("BENCH_CNN_BATCH", "512"))
CNN_TRIALS = int(os.environ.get("BENCH_CNN_TRIALS", "5"))

# Peak dense bf16 FLOP/s per chip by TPU generation (public spec sheets),
# matched as a substring of ``device_kind``.
_PEAK_BF16 = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def init_chip():
    """Import jax, send package logs to stderr (stdout is ONE machine-parsed
    JSON line) and insist on a TPU: exits non-zero, naming the platform it
    found, anywhere else. The compile cache is placed by the package import
    (``utils.compilation_cache``); nothing here sets a platform."""
    import jax

    from machine_learning_apache_spark_tpu.utils.logging import (
        route_logging_to_stderr,
    )

    route_logging_to_stderr()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"this bench measures the TPU and JAX found platform "
            f"{device.platform!r} ({device.device_kind!r}, "
            f"{len(jax.devices())} device(s)); run it through the chip tool"
        )
    return jax


def _peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for key, peak in _PEAK_BF16.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device.device_kind!r}; "
        f"add it to _PEAK_BF16 with its source (known: {sorted(_PEAK_BF16)})"
    )


def transformer_train_flops_per_step(
    batch: int, src_len: int, trg_len: int, layers: int = LAYERS
) -> float:
    """Analytic matmul FLOPs for one train step (fwd + 2× bwd ≈ 3× fwd).

    Counts only MXU work (projections, attention score/value matmuls, FFN,
    logits head); embedding lookups and softmax are excluded. Matches the
    reference architecture (d_model=512, ffn=1024, heads=8, 1 layer,
    ``pytorch_machine_translator.py:108-117``).
    """
    d, f = D_MODEL, FFN
    s, t = src_len, trg_len
    enc = layers * (4 * 2 * s * d * d + 2 * 2 * s * s * d + 2 * 2 * s * d * f)
    dec_self = 4 * 2 * t * d * d + 2 * 2 * t * t * d
    dec_cross = 2 * 2 * t * d * d + 2 * 2 * s * d * d + 2 * 2 * t * s * d
    dec_ffn = 2 * 2 * t * d * f
    dec = layers * (dec_self + dec_cross + dec_ffn)
    head = 2 * t * d * TRG_VOCAB
    return 3.0 * batch * (enc + dec + head)


def cnn_train_flops_per_step(batch: int, hw: int = 28, hidden: int = 10) -> float:
    """Analytic conv+dense FLOPs for one TinyVGG train step (3× fwd)."""
    fwd = 0.0
    h, c_in = hw, 1
    for _block in range(2):
        for _conv in range(2):
            fwd += 2 * 9 * c_in * hidden * h * h
            c_in = hidden
        h //= 2
    fwd += 2 * (hidden * h * h) * 10  # classifier head
    return 3.0 * batch * fwd


def _time_trials(step_fn, n_trials: int, n_steps: int, ready_fn) -> list[float]:
    """Per-trial wall-clock seconds for ``n_steps`` fully-materialized steps."""
    times = []
    for _ in range(n_trials):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            step_fn()
        ready_fn()
        times.append(time.perf_counter() - t0)
    return times


def _paired_window_stats(
    times_short: list[float],
    times_long: list[float],
    steps_short: int,
    steps_long: int,
    tokens_per_step: float,
) -> dict:
    """Cancel the fixed per-trial sync cost with two window lengths.

    The completion barrier is a device→host value fetch plus queue drain —
    a *fixed* cost per trial that inflates short windows. Timing windows of
    N and kN steps and differencing the medians solves for the per-step
    time with the constant eliminated:

        step_time = (median(T_long) - median(T_short)) / (kN - N)

    Returns the steady-state rate estimate and the implied per-trial
    overhead, both diagnostics alongside the directly-measured medians.
    """
    dt_s = statistics.median(times_short)
    dt_l = statistics.median(times_long)
    dstep = (dt_l - dt_s) / (steps_long - steps_short)
    if dstep <= 0:
        return {}  # noise exceeded the signal; nothing defensible to report
    overhead = dt_s - steps_short * dstep
    return {
        "steady_state_per_step_s": round(dstep, 6),
        "steady_state_rate": round(tokens_per_step / dstep, 1),
        "sync_overhead_s_per_trial": round(max(overhead, 0.0), 4),
    }


class MeasurementInvalid(RuntimeError):
    """A deliberate validity failure: an MFU above 1 proves the timing
    barrier (or the clock, or the FLOP model) is broken."""


def _value_barrier(holder) -> float:
    """Completion barrier whose result proves execution happened: transfer
    the trial's final loss scalar AND one element of an updated param to
    the host. Those bytes depend on the whole step chain (the loss on the
    last forward, the param element on the last optimizer update), so the
    fetch cannot return before every dispatched step has executed. Costs
    one scalar round-trip per *trial* (not per step).
    """
    import jax

    leaf = jax.tree.leaves(holder["state"].params)[0]
    # holder["loss"] exists only after the first step (warmup may be 0).
    loss = float(holder["loss"]) if "loss" in holder else 0.0
    return float(leaf.ravel()[0]) + loss


def _check_mfu(achieved: float, peak: float, label: str) -> float:
    """Reject physically impossible rates instead of reporting them."""
    mfu = achieved / peak
    if mfu > 1.0:
        raise MeasurementInvalid(
            f"measured {label} MFU {mfu:.2f} exceeds 1.0 — timing barrier "
            f"defeated; measurement invalid"
        )
    return mfu


def bench_transformer(
    jax,
    *,
    batch_per_chip: int | None = None,
    layers: int = LAYERS,
    trials: int | None = None,
    steps: int | None = None,
    warmup: int | None = None,
    scan_k: int = 1,
    seq: int | None = None,
) -> dict:
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from machine_learning_apache_spark_tpu.models import (
        Transformer,
        TransformerConfig,
    )
    from machine_learning_apache_spark_tpu.parallel import (
        DATA_AXIS,
        make_mesh,
        shard_params,
    )
    from machine_learning_apache_spark_tpu.train.losses import (
        masked_token_cross_entropy,
    )
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    t_setup = time.perf_counter()
    batch_per_chip = BATCH_PER_CHIP if batch_per_chip is None else batch_per_chip
    seq = SEQ if seq is None else seq
    trials = TRIALS if trials is None else trials
    n_chips = jax.device_count()
    device = jax.devices()[0]
    if steps is None:
        steps = TPU_STEPS
    if warmup is None:
        warmup = TPU_WARMUP
    cfg = TransformerConfig(
        src_vocab_size=SRC_VOCAB,
        trg_vocab_size=TRG_VOCAB,
        max_len=seq,
        num_layers=layers,
        dtype=jnp.bfloat16,
    )
    model = Transformer(cfg)
    mesh = make_mesh({DATA_AXIS: n_chips})
    batch = batch_per_chip * n_chips

    # Several distinct batches, rotated per step: reusing one batch would
    # invite (unfounded but unfalsifiable) work-elision doubts about the
    # measurement; rotation costs nothing and removes the hypothesis.
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    n_batches = 4
    batches = []
    for i in range(n_batches):
        rng = jax.random.key(i)
        src = jax.random.randint(rng, (batch, seq), 1, SRC_VOCAB, dtype=jnp.int32)
        trg = jax.random.randint(rng, (batch, seq), 1, TRG_VOCAB, dtype=jnp.int32)
        batches.append(
            (jax.device_put(src, sharding), jax.device_put(trg, sharding))
        )
    src, trg = batches[0]

    params = shard_params(
        model.init(jax.random.key(1), src[:2], trg[:2])["params"], mesh
    )
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer("adam", 1e-3)
    )

    def loss_fn(params, src, trg, rng):
        logits = model.apply(
            {"params": params},
            src,
            trg[:, :-1],
            deterministic=False,
            rngs={"dropout": rng},
        )
        return masked_token_cross_entropy(logits, trg[:, 1:], cfg.pad_id)

    # Donated state: in-place param/opt updates, no copy — HBM-traffic win.
    @functools.partial(jax.jit, donate_argnums=0)
    def step(state, src, trg, rng):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, src, trg, rng)
        return state.apply_gradients(grads), loss

    holder = {"state": state, "rng": jax.random.key(2), "i": 0}

    if scan_k > 1:
        # The scanned product path (train.loop.make_multi_step /
        # fit(steps_per_call=K)): K steps per dispatch. The distinct
        # batches rotate INSIDE the stack (cycled to length K); across
        # dispatches the same stack is replayed — unlike the per-step
        # path's endless rotation, but each step in a window still sees
        # the same input variety.
        import numpy as np
        from machine_learning_apache_spark_tpu.parallel import (
            shard_batch_stack,
        )
        from machine_learning_apache_spark_tpu.train.loop import (
            make_multi_step,
        )

        def scan_loss(params, b, rng):
            return loss_fn(params, b[0], b[1], rng), {}

        multi = make_multi_step(scan_loss)
        host = [
            (np.asarray(s), np.asarray(t))
            for s, t in batches[: min(n_batches, scan_k)]
        ]
        stacked = shard_batch_stack(
            mesh, [host[i % len(host)] for i in range(scan_k)]
        )

        def one_step():
            holder["state"], holder["rng"], losses, _ = multi(
                holder["state"], stacked, holder["rng"]
            )
            holder["loss"] = losses[-1]
    else:

        def one_step():
            holder["rng"], sub = jax.random.split(holder["rng"])
            s, t = batches[holder["i"] % n_batches]
            holder["i"] += 1
            holder["state"], holder["loss"] = step(holder["state"], s, t, sub)

    for _ in range(warmup):
        one_step()
    _value_barrier(holder)
    # Setup + compile + warmup wall time: the persistent compile cache's
    # effect shows here — two fresh-process runs of the same program
    # differ by the compile time the cache absorbed (VERDICT r04 item 5's
    # measured before/after).
    setup_s = time.perf_counter() - t_setup
    loss0 = float(holder["loss"]) if "loss" in holder else float("nan")
    log(
        f"jax transformer warmup done on {n_chips} × {device.platform} "
        f"(bs/chip={batch_per_chip}, layers={layers}, loss={loss0:.3f}, "
        f"setup+warmup {setup_s:.1f}s)"
    )

    if os.environ.get("BENCH_PROFILE_DIR"):
        # Device trace of a few steady-state steps — the ground truth for
        # reconciling measured throughput against analytic FLOPs (MFU).
        with jax.profiler.trace(os.environ["BENCH_PROFILE_DIR"]):
            for _ in range(5):
                one_step()
            _value_barrier(holder)
        log(f"profiler trace written to {os.environ['BENCH_PROFILE_DIR']}")

    barrier = lambda: _value_barrier(holder)  # noqa: E731
    times = _time_trials(one_step, trials, steps, barrier)
    for t, dt in enumerate(times):
        r = batch * seq * steps * scan_k / dt / n_chips
        log(f"jax trial {t}: {steps * scan_k} steps in {dt:.3f}s → "
            f"{r:,.0f} tokens/sec/chip")
    paired = {}
    head_steps, head_times = steps * scan_k, times
    if LONG_WINDOW > 1:
        # Long windows amortize the fixed per-trial sync round-trip; the
        # headline is the directly-measured long-window median, and the
        # short/long pair yields the sync-free steady-state diagnostic.
        steps_long = steps * LONG_WINDOW
        times_long = _time_trials(one_step, trials, steps_long, barrier)
        for t, dt in enumerate(times_long):
            r = batch * seq * steps_long * scan_k / dt / n_chips
            log(f"jax long trial {t}: {steps_long * scan_k} steps in "
                f"{dt:.3f}s → {r:,.0f} tokens/sec/chip")
        paired = _paired_window_stats(
            times, times_long, steps * scan_k, steps_long * scan_k,
            batch * seq / n_chips,
        )
        head_steps, head_times = steps_long * scan_k, times_long
    tps = sorted(batch * seq * head_steps / dt / n_chips for dt in head_times)
    median = statistics.median(tps)
    flops_step = transformer_train_flops_per_step(batch, seq, seq - 1, layers)
    peak = _peak_flops(device)
    median_dt = statistics.median(head_times)
    achieved = flops_step * head_steps / median_dt / n_chips
    mfu = _check_mfu(achieved, peak, "transformer")
    out = {
        "median": round(median, 1),
        "max": round(tps[-1], 1),
        "trials": [round(x, 1) for x in tps],
        "spread": round(tps[-1] / tps[0], 2) if tps[0] else None,
        "steps_per_trial": head_steps,
        "scan_k": scan_k,
        "flops_per_step": flops_step,
        "achieved_flops_per_sec_chip": round(achieved, 1),
        "mfu": round(mfu, 4),
        "device": getattr(device, "device_kind", device.platform),
        "n_chips": n_chips,
        "batch_per_chip": batch_per_chip,
        "layers": layers,
        "loss": round(float(holder["loss"]), 3),
        "setup_plus_warmup_s": round(setup_s, 1),
    }
    if paired:
        # MFU at the sync-free steady-state rate (diagnostic, not headline).
        steady_mfu = (
            flops_step / (batch * seq) * paired["steady_state_rate"] / peak
        )
        if steady_mfu > 1.0:
            log("paired-window estimate exceeds chip peak — differencing "
                "noise, discarding the diagnostic")
        else:
            paired["steady_state_mfu"] = round(steady_mfu, 4)
            out["paired_window"] = paired
    return out


def _synthetic_packed_corpus(n_pairs: int):
    """Multi30k-shaped ragged pairs (clipped-normal lengths, mean ~15 src /
    ~17 trg vs the reference's fixed 200-token rows,
    ``pytorch_machine_translator.py:70-98``), packed to the bench grid.
    Shared by the packed and composed stages so their pairs/sec numbers
    measure the same corpus distribution."""
    import numpy as np

    from machine_learning_apache_spark_tpu.data.packing import (
        pack_translation_pairs,
    )

    rng = np.random.default_rng(0)

    def ragged(n, vocab, mean):
        lens = np.clip(rng.normal(mean, 5.0, n), 4, 60).astype(int)
        return [list(rng.integers(4, vocab, l)) for l in lens]

    return pack_translation_pairs(
        ragged(n_pairs, SRC_VOCAB, 15.0), ragged(n_pairs, TRG_VOCAB, 17.0),
        src_len=SEQ, trg_len=SEQ,
    )


def bench_packed_transformer(
    jax, *, trials: int = 3, steps: int = 10, warmup: int = 10
) -> dict:
    """Effective-throughput measurement of sequence packing on the MT
    workload (``pack_sequences=True``): synthetic ragged pairs with a
    Multi30k-like length distribution (mean ~15 tokens vs the fixed
    200-token rows of ``pytorch_machine_translator.py:70-98``), packed by
    ``data.packing`` and trained with the packed loss. The headline metric
    is PAIRS/sec/chip — the work a user actually cares about — which the
    fixed-width layout caps at (token rate)/200 regardless of how short
    the sentences are.
    """
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from machine_learning_apache_spark_tpu.models import (
        Transformer,
        TransformerConfig,
    )
    from machine_learning_apache_spark_tpu.parallel import DATA_AXIS, make_mesh
    from machine_learning_apache_spark_tpu.recipes.translation import (
        make_packed_translation_loss,
    )
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    n_chips = jax.device_count()
    device = jax.devices()[0]
    batch = BATCH_PER_CHIP * n_chips
    packed = _synthetic_packed_corpus(4096)
    rows = len(packed.src)
    pairs_per_row = packed.pair_count / rows

    cfg = TransformerConfig(
        src_vocab_size=SRC_VOCAB,
        trg_vocab_size=TRG_VOCAB,
        max_len=SEQ,
        num_layers=LAYERS,
        dtype=jnp.bfloat16,
    )
    model = Transformer(cfg)
    mesh = make_mesh({DATA_AXIS: n_chips})
    sharding = NamedSharding(mesh, P(DATA_AXIS))

    n_batches = 4
    batches = []
    for i in range(n_batches):
        idx = (np.arange(batch) + i * batch) % rows
        batches.append(tuple(
            jax.device_put(jnp.asarray(a[idx]), sharding)
            for a in packed.arrays()
        ))

    params = model.init(
        jax.random.key(1), batches[0][0][:2], batches[0][3][:2, :-1]
    )["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer("adam", 1e-3)
    )
    loss_fn = make_packed_translation_loss(model, cfg.pad_id)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state, b, rng):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, b, rng
        )
        return state.apply_gradients(grads), loss

    holder = {"state": state, "rng": jax.random.key(2), "i": 0}

    def one_step():
        holder["rng"], sub = jax.random.split(holder["rng"])
        b = batches[holder["i"] % n_batches]
        holder["i"] += 1
        holder["state"], holder["loss"] = step(holder["state"], b, sub)

    for _ in range(warmup):
        one_step()
    _value_barrier(holder)
    log(f"packed warmup done ({pairs_per_row:.1f} pairs/row, "
        f"grid use {packed.token_efficiency:.1%})")

    barrier = lambda: _value_barrier(holder)  # noqa: E731
    if LONG_WINDOW > 1:
        # Long windows only: this bench reports one rate (no paired-window
        # diagnostic), so a short-window pass would be discarded work.
        steps = steps * LONG_WINDOW
    times = _time_trials(one_step, trials, steps, barrier)
    pairs_rate = sorted(
        batch * pairs_per_row * steps / dt / n_chips for dt in times
    )
    median = statistics.median(pairs_rate)
    for dt in times:
        log(f"packed: {steps} steps in {dt:.3f}s → "
            f"{batch * pairs_per_row * steps / dt / n_chips:,.0f} pairs/sec/chip")
    return {
        "pairs_per_sec_chip": round(median, 1),
        "max": round(pairs_rate[-1], 1),
        "spread": round(pairs_rate[-1] / pairs_rate[0], 2),
        "pairs_per_row": round(pairs_per_row, 2),
        "token_efficiency": round(packed.token_efficiency, 4),
        "unpacked_token_efficiency": round(packed.unpacked_efficiency, 4),
        "loss": round(float(holder["loss"]), 3),
    }


def bench_composed(
    jax,
    *,
    batch_per_chip: int = 512,
    scan_k: int = 4,
    trials: int = 4,
    steps: int = 5,
    warmup_dispatches: int = 25,
    n_pairs: int = 65536,
) -> dict:
    """Best-achievable record: the three throughput levers COMPOSED on the
    reference MT model — sequence packing (input density: ~11-12 pairs per
    200-token row instead of 1), scanned dispatch (``fit(steps_per_call=K)``
    semantics: K steps per host dispatch), and a large batch (MXU tiling +
    fixed-cost amortization; see TPU_ROOFLINE.md). This is the config
    a real user of the framework would run the reference's Multi30k workload
    at (``pytorch_machine_translator.py:199-205`` contract); the headline
    stages keep the reference's own bs=32 per-step shape for comparability,
    this one records what the framework actually achieves.

    Reported: pairs/sec/chip (the user-meaningful rate), the grid token
    rate and its MFU (what the chip computes, pad included), and the
    effective non-pad token rate.
    """
    import numpy as np
    import jax.numpy as jnp

    from machine_learning_apache_spark_tpu.models import (
        Transformer,
        TransformerConfig,
    )
    from machine_learning_apache_spark_tpu.parallel import (
        DATA_AXIS,
        make_mesh,
        shard_batch_stack,
    )
    from machine_learning_apache_spark_tpu.recipes.translation import (
        make_packed_translation_loss,
    )
    from machine_learning_apache_spark_tpu.train.loop import make_multi_step
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    n_chips = jax.device_count()
    device = jax.devices()[0]
    batch = batch_per_chip * n_chips
    # n_pairs default: enough distinct pairs that the scan stack's rows
    # don't repeat across the K stacked batches at bs=512.
    packed = _synthetic_packed_corpus(n_pairs)
    rows = len(packed.src)
    pairs_per_row = packed.pair_count / rows

    cfg = TransformerConfig(
        src_vocab_size=SRC_VOCAB,
        trg_vocab_size=TRG_VOCAB,
        max_len=SEQ,
        num_layers=LAYERS,
        dtype=jnp.bfloat16,
    )
    model = Transformer(cfg)
    mesh = make_mesh({DATA_AXIS: n_chips})

    host_batches = []
    for i in range(scan_k):
        idx = (np.arange(batch) + i * batch) % rows
        host_batches.append(tuple(a[idx] for a in packed.arrays()))
    stacked = shard_batch_stack(mesh, host_batches)

    params = model.init(
        jax.random.key(1),
        jnp.asarray(packed.src[:2]),
        jnp.asarray(packed.trg[:2, :-1]),
    )["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer("adam", 1e-3)
    )
    multi = make_multi_step(make_packed_translation_loss(model, cfg.pad_id))

    holder = {"state": state, "rng": jax.random.key(2)}

    def one_dispatch():
        holder["state"], holder["rng"], losses, _ = multi(
            holder["state"], stacked, holder["rng"]
        )
        holder["loss"] = losses[-1]

    for _ in range(warmup_dispatches):
        one_dispatch()
    _value_barrier(holder)
    log(
        f"composed warmup done (bs/chip={batch_per_chip}, scan_k={scan_k}, "
        f"{pairs_per_row:.1f} pairs/row, grid use "
        f"{packed.token_efficiency:.1%}, loss={float(holder['loss']):.3f})"
    )

    barrier = lambda: _value_barrier(holder)  # noqa: E731
    times = _time_trials(one_dispatch, trials, steps, barrier)
    real_steps = steps * scan_k
    pairs_rate = sorted(
        batch * pairs_per_row * real_steps / dt / n_chips for dt in times
    )
    for dt in times:
        log(f"composed: {real_steps} steps in {dt:.3f}s → "
            f"{batch * pairs_per_row * real_steps / dt / n_chips:,.0f} "
            f"pairs/sec/chip")
    median_pairs = statistics.median(pairs_rate)
    median_dt = statistics.median(times)
    grid_tokens = batch * SEQ * real_steps / median_dt / n_chips
    flops_step = transformer_train_flops_per_step(batch, SEQ, SEQ - 1, LAYERS)
    peak = _peak_flops(device)
    achieved = flops_step * real_steps / median_dt / n_chips
    mfu = _check_mfu(achieved, peak, "composed")
    return {
        "pairs_per_sec_chip": round(median_pairs, 1),
        "max": round(pairs_rate[-1], 1),
        "spread": round(pairs_rate[-1] / pairs_rate[0], 2),
        "grid_tokens_per_sec_chip": round(grid_tokens, 1),
        "effective_tokens_per_sec_chip": round(
            grid_tokens * packed.token_efficiency, 1
        ),
        "mfu": round(mfu, 4),
        "batch_per_chip": batch_per_chip,
        "scan_k": scan_k,
        "steps_per_trial": real_steps,
        "pairs_per_row": round(pairs_per_row, 2),
        "token_efficiency": round(packed.token_efficiency, 4),
        "loss": round(float(holder["loss"]), 3),
    }


def _sweep_plan() -> list[tuple[int, int]]:
    """(batch_per_chip, layers) points: batch {32, 128, 256, 512} × layers
    {1, 4}, minus the headline config (its own stage) and 512x4 (~50 s per
    trial; the surface is clear by then). ``BENCH_SWEEP_POINTS="32x4,128x4"``
    makes the plan exactly those points, in order."""
    only = os.environ.get("BENCH_SWEEP_POINTS", "").strip()
    if only:
        plan = []
        for tok in only.split(","):
            b, l = tok.strip().lower().split("x")
            plan.append((int(b), int(l)))
        return plan
    return [
        (bpc, layers)
        for layers in (1, 4)
        for bpc in (32, 128, 256, 512)
        if not (layers == 4 and bpc == 512)
        and not (bpc == BATCH_PER_CHIP and layers == LAYERS)
    ]


def bench_transformer_sweep(jax) -> list[dict]:
    """MFU scaling sweep on the MT workload. The reference config (bs=32,
    1 layer, seq 200) is latency-bound and undersells the MXU; this locates
    where the framework actually peaks. Fewer trials than the headline: the
    goal is an MFU-vs-config surface, not the headline number; the
    paired-window protocol inside bench_transformer still applies per
    point. Every point runs in this process — the one that holds the chip.
    """
    points = []
    for bpc, layers in _sweep_plan():
        r = bench_transformer(
            jax, batch_per_chip=bpc, layers=layers,
            trials=2, steps=10, warmup=5,
        )
        points.append({
            "batch_per_chip": bpc,
            "layers": layers,
            "tokens_per_sec_chip": r["median"],
            "mfu": r["mfu"],
            "spread": r["spread"],
            "steady_state_mfu": r.get("paired_window", {}).get(
                "steady_state_mfu"
            ),
        })
        log(
            f"sweep bs/chip={bpc} layers={layers}: "
            f"{r['median']:,.0f} tok/s/chip, mfu={r['mfu']}"
        )
    return points


def bench_cnn(jax) -> dict:
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from machine_learning_apache_spark_tpu.models import TinyVGG
    from machine_learning_apache_spark_tpu.parallel import DATA_AXIS, make_mesh
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    n_chips = jax.device_count()
    device = jax.devices()[0]
    model = TinyVGG(dtype=jnp.bfloat16)
    mesh = make_mesh({DATA_AXIS: n_chips})
    batch = CNN_BATCH_PER_CHIP * n_chips

    rng = jax.random.key(0)
    x = jax.random.normal(rng, (batch, 28, 28, 1), dtype=jnp.float32)
    y = jax.random.randint(rng, (batch,), 0, 10, dtype=jnp.int32)
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    x, y = jax.device_put(x, sharding), jax.device_put(y, sharding)

    params = model.init(jax.random.key(1), x[:2])["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer("sgd", 0.01)
    )

    def loss_fn(params, x, y):
        logits = model.apply({"params": params}, x)
        import optax

        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, x, y)
        return state.apply_gradients(grads), loss

    holder = {"state": state}

    # The TinyVGG step is far shorter than a host dispatch, so per-step
    # dispatch would measure the host. The framework's answer is the
    # scanned trainer (fit(steps_per_call=K) /
    # train.loop.make_multi_step): K steps fused into one dispatch. The
    # bench measures that product path; BENCH_CNN_SCAN=1 restores per-step
    # dispatch for comparison.
    scan_k = int(os.environ.get("BENCH_CNN_SCAN", "50"))
    if scan_k > 1:
        import numpy as np
        from machine_learning_apache_spark_tpu.parallel import (
            shard_batch_stack,
        )
        from machine_learning_apache_spark_tpu.train.loop import (
            make_multi_step,
        )

        def scan_loss(params, b, rng):
            bx, by = b
            return loss_fn(params, bx, by), {}

        multi = make_multi_step(scan_loss)
        stacked = shard_batch_stack(mesh, [(np.asarray(x), np.asarray(y))] * scan_k)
        holder["rng"] = jax.random.key(2)

        def one_step():
            holder["state"], holder["rng"], losses, _ = multi(
                holder["state"], stacked, holder["rng"]
            )
            holder["loss"] = losses[-1]
    else:

        def one_step():
            holder["state"], holder["loss"] = step(holder["state"], x, y)

    for _ in range(2 if scan_k > 1 else 30):
        one_step()
    _value_barrier(holder)
    log(f"jax cnn warmup done ({batch} samples/step, scan_k={scan_k})")

    barrier = lambda: _value_barrier(holder)  # noqa: E731
    # Window length targets ~TPU_CNN_STEPS *real* steps regardless of how
    # many are fused per dispatch.
    cnn_steps = max(TPU_CNN_STEPS // scan_k, 1)
    times = _time_trials(one_step, CNN_TRIALS, cnn_steps, barrier)
    paired = {}
    head_steps, head_times = cnn_steps * scan_k, times
    if LONG_WINDOW > 1:
        steps_long = cnn_steps * LONG_WINDOW
        times_long = _time_trials(one_step, CNN_TRIALS, steps_long, barrier)
        paired = _paired_window_stats(
            times, times_long, cnn_steps * scan_k, steps_long * scan_k,
            batch / n_chips,
        )
        head_steps, head_times = steps_long * scan_k, times_long
    sps = sorted(batch * head_steps / dt / n_chips for dt in head_times)
    median = statistics.median(sps)
    flops_step = cnn_train_flops_per_step(batch)
    peak = _peak_flops(device)
    achieved = flops_step * head_steps / statistics.median(head_times) / n_chips
    mfu = _check_mfu(achieved, peak, "CNN")
    out = {
        "value": round(median, 1),
        "unit": "samples/sec/chip",
        "median": round(median, 1),
        "max": round(sps[-1], 1),
        "trials": [round(x, 1) for x in sps],
        "spread": round(sps[-1] / sps[0], 2) if sps[0] else None,
        "steps_per_trial": head_steps,
        "scan_k": scan_k,
        "mfu": round(mfu, 4),
        "batch_per_chip": CNN_BATCH_PER_CHIP,
    }
    if paired:
        out["paired_window"] = paired
    return out


def bench_torch_transformer() -> float | None:
    """Reference-equivalent engine: torch.nn.Transformer, same shapes, CPU."""
    if os.environ.get("BENCH_SKIP_TORCH"):
        return None
    try:
        import torch
        import torch.nn as tnn

        torch.manual_seed(0)
        d, steps = D_MODEL, int(os.environ.get("BENCH_TORCH_STEPS", "10"))
        batch = min(BATCH_PER_CHIP, 32)

        class Ref(tnn.Module):
            def __init__(self):
                super().__init__()
                self.src_emb = tnn.Embedding(SRC_VOCAB, d)
                self.trg_emb = tnn.Embedding(TRG_VOCAB, d)
                self.core = tnn.Transformer(
                    d_model=d, nhead=HEADS, num_encoder_layers=LAYERS,
                    num_decoder_layers=LAYERS, dim_feedforward=FFN,
                    dropout=0.1, batch_first=True,
                )
                self.head = tnn.Linear(d, TRG_VOCAB)

            def forward(self, src, trg):
                mask = tnn.Transformer.generate_square_subsequent_mask(trg.shape[1])
                return self.head(
                    self.core(self.src_emb(src), self.trg_emb(trg), tgt_mask=mask)
                )

        model = Ref()
        opt = torch.optim.Adam(model.parameters(), lr=1e-3)
        loss_fn = tnn.CrossEntropyLoss(ignore_index=0)
        src = torch.randint(1, SRC_VOCAB, (batch, SEQ))
        trg = torch.randint(1, TRG_VOCAB, (batch, SEQ))

        def one_step():
            opt.zero_grad()
            logits = model(src, trg[:, :-1])
            loss = loss_fn(logits.reshape(-1, TRG_VOCAB), trg[:, 1:].reshape(-1))
            loss.backward()
            opt.step()

        one_step()  # warmup
        t0 = time.perf_counter()
        for _ in range(steps):
            one_step()
        dt = time.perf_counter() - t0
        tps = batch * SEQ * steps / dt
        log(f"torch-cpu transformer baseline: {steps} steps in {dt:.3f}s → "
            f"{tps:,.0f} tokens/sec")
        return tps
    except Exception as e:  # baked-in torch should work; degrade gracefully
        log(f"torch transformer baseline unavailable: {e!r}")
        return None


def bench_torch_cnn() -> float | None:
    """Reference-equivalent CNN engine: FashionMNISTModel shapes, CPU."""
    if os.environ.get("BENCH_SKIP_TORCH"):
        return None
    try:
        import torch
        import torch.nn as tnn

        torch.manual_seed(0)
        steps = int(os.environ.get("BENCH_TORCH_STEPS", "10"))
        batch = min(CNN_BATCH_PER_CHIP, 512)
        h = 10

        model = tnn.Sequential(
            tnn.Conv2d(1, h, 3, padding=1), tnn.ReLU(),
            tnn.Conv2d(h, h, 3, padding=1), tnn.ReLU(), tnn.MaxPool2d(2),
            tnn.Conv2d(h, h, 3, padding=1), tnn.ReLU(),
            tnn.Conv2d(h, h, 3, padding=1), tnn.ReLU(), tnn.MaxPool2d(2),
            tnn.Flatten(), tnn.Linear(h * 7 * 7, 10),
        )
        opt = torch.optim.SGD(model.parameters(), lr=0.01)
        loss_fn = tnn.CrossEntropyLoss()
        x = torch.randn(batch, 1, 28, 28)
        y = torch.randint(0, 10, (batch,))

        def one_step():
            opt.zero_grad()
            loss = loss_fn(model(x), y)
            loss.backward()
            opt.step()

        one_step()
        t0 = time.perf_counter()
        for _ in range(steps):
            one_step()
        dt = time.perf_counter() - t0
        sps = batch * steps / dt
        log(f"torch-cpu cnn baseline: {steps} steps in {dt:.3f}s → "
            f"{sps:,.0f} samples/sec")
        return sps
    except Exception as e:
        log(f"torch cnn baseline unavailable: {e!r}")
        return None


def main() -> None:
    jax = init_chip()
    from machine_learning_apache_spark_tpu.ops.attention import kernel_mesh
    from machine_learning_apache_spark_tpu.parallel import DATA_AXIS, make_mesh

    # Every stage jits the model over a batch sharded on this mesh; the
    # Pallas launches inside must know it (XLA cannot partition them).
    with kernel_mesh(make_mesh({DATA_AXIS: jax.device_count()})):
        _run_stages(jax)


def _run_stages(jax) -> None:
    # Every stage runs under a bench.<label> span. With MLSPARK_TELEMETRY=0
    # these are shared no-op context managers.
    from machine_learning_apache_spark_tpu import telemetry

    result = {
        "metric": "transformer_mt_train_throughput",
        "unit": "tokens/sec/chip",
    }
    with telemetry.span("bench.transformer"):
        mt = bench_transformer(jax)
    baseline = bench_torch_transformer()
    result["value"] = mt["median"]
    result["vs_baseline"] = (
        round(mt["median"] / baseline, 3) if baseline else 1.0
    )
    result.update(mt)
    device = jax.devices()[0]
    result["device"] = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }
    if not os.environ.get("BENCH_SKIP_SCANNED"):
        # The same MT workload through the scanned product path
        # (fit(steps_per_call=K) semantics): K=8 steps per dispatch removes
        # the per-dispatch host cost the paired-window estimator can only
        # model. Reported alongside (not replacing) the per-step headline.
        with telemetry.span("bench.transformer-scanned"):
            sc = bench_transformer(
                jax, scan_k=8, trials=5, steps=10, warmup=20
            )
        result["scanned"] = {
            k: sc[k]
            for k in (
                "median", "max", "trials", "spread",
                "steps_per_trial", "scan_k", "mfu", "paired_window",
            )
            if k in sc
        }
    if not os.environ.get("BENCH_SKIP_PACKED"):
        # Sequence packing on the same workload: pairs/sec/chip against the
        # fixed-width layout's (token rate)/SEQ ceiling.
        with telemetry.span("bench.packed"):
            pk = bench_packed_transformer(jax)
        pk["vs_unpacked_pairs_rate"] = round(
            pk["pairs_per_sec_chip"] / (result["median"] / SEQ), 2
        )
        result["packed"] = pk
    if not os.environ.get("BENCH_SKIP_COMPOSED"):
        # The three throughput levers composed (packing × scan × bs=512):
        # the "best achievable tokens/sec/chip" record a real user would
        # run at, alongside (never replacing) the reference-shape headline.
        with telemetry.span("bench.composed"):
            result["composed"] = bench_composed(
                jax,
                batch_per_chip=int(
                    os.environ.get("BENCH_COMPOSED_BATCH", "512")
                ),
                scan_k=int(os.environ.get("BENCH_COMPOSED_SCAN", "4")),
            )
    if not os.environ.get("BENCH_SKIP_SWEEP"):
        with telemetry.span("bench.sweep"):
            result["sweep"] = bench_transformer_sweep(jax)
    with telemetry.span("bench.cnn"):
        cnn = bench_cnn(jax)
    cnn_base = bench_torch_cnn()
    cnn["vs_baseline"] = (
        round(cnn["value"] / cnn_base, 3) if cnn_base else 1.0
    )
    result["cnn"] = cnn
    print(json.dumps(result))


if __name__ == "__main__":
    main()

"""A language-model training cell: ``train.loop.fit`` on full token rows.

Shares with ``kinds/train.py`` everything that is not the model: the ``Feed``
(three checked steps, the warm-up, then batches until ``--seconds`` have
passed), the ``Hook`` that stands at ``fit``'s checkpoint seam and reads the
first gradient out of Adam's first moment and the parameters' change after
step 3, the lap parsing, and the comparison (``compare.train_numbers``).

Brings: the model, loss and optimizer of ``recipes.language_model`` built
from the configuration's file (``build_program``); batches of token rows
``[rows, seq_len + 1]`` drawn uniformly from the vocabulary slice; weights
from ``weights_hybrid_lm``; the reference of ``reference/hybrid_lm.py`` (in
blocks of rows, after the window); the planted fault ``fault_top1_only``
(the reference routed to one expert instead of the configuration's ten);
the step's needed operations from ``flops_hybrid_lm`` with the routed
experts counted by the assignments that were local in the window; the
no-drop check a step (``with_step_verdict``) and the local assignments by lap
across the window (``_report_laps``: routing that drifts shows there); and, in a
traced run, the device time under the program's ``lm.*`` scopes, read out of
the ``.xplane.pb`` by ``scope_trace`` before the harness deletes it and left
in ``run.counters["scope_ms"]`` for the per-layer readers.
"""

from __future__ import annotations

import gc
import os
import re
import time

import numpy as np

from benchmark import compare, flops_hybrid_lm, scope_trace, trace_reduce
from benchmark import weights_hybrid_lm as weights
from benchmark.kinds import train
from benchmark.kinds.train import CHECK_STEPS, Feed, Hook
from benchmark.readers import TRAIN_STEP_MODULE

LOCAL = re.compile(r"moe_assignments_local: ([\d.]+)")
SCOPES = (
    "lm.gdn_scan", "lm.gdn_proj_conv", "lm.attn", "lm.moe.route",
    "lm.moe.experts", "lm.moe.shared", "lm.head_loss",
)


def model_config(cfg: dict):
    import jax.numpy as jnp

    from machine_learning_apache_spark_tpu.models.hybrid_lm import (
        HybridLMConfig,
    )

    return HybridLMConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        linear_key_heads=cfg["linear_num_key_heads"],
        linear_value_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel=cfg["linear_conv_kernel_dim"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=tuple(cfg["experts_held"]),
        expert_hidden=cfg["moe_intermediate_size"],
        shared_expert_hidden=cfg["shared_expert_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        router_aux_weight=cfg["router_aux_loss_coef"],
        rms_eps=cfg["rms_norm_eps"], remat=True,
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def build_program(run, params, cfg):
    """The system under test: the recipe's model, loss and optimizer, a
    fresh ``TrainState`` and the mesh."""
    from machine_learning_apache_spark_tpu.models.hybrid_lm import HybridLM
    from machine_learning_apache_spark_tpu.parallel.mesh import (
        data_parallel_mesh,
    )
    from machine_learning_apache_spark_tpu.recipes.language_model import (
        make_lm_loss,
    )
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    model = HybridLM(model_config(cfg))
    opt = cfg["optimizer"]
    tx = make_optimizer(
        opt["name"], opt["learning_rate"],
        b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
    )
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    mesh = data_parallel_mesh(run.chips) if run.chips > 1 else None
    return with_step_verdict(make_lm_loss(model)), state, mesh


def with_step_verdict(loss_fn):
    """The recipe's loss, its metrics one longer: ``moe_steps_unequal`` is 1.0
    in a step whose two counters of expert assignments differ (the router's
    choices that name a held expert, and the group sizes the grouped products
    were given). ``fit`` hands back an epoch's *means*: a mean of the counters
    could hide a step, the mean of this one counts them."""
    import jax.numpy as jnp

    def loss(params, batch, rng):
        value, metrics = loss_fn(params, batch, rng)
        unequal = (
            metrics["moe_assignments_local"] != metrics["moe_assignments_computed"]
        )
        return value, {**metrics, "moe_steps_unequal": unequal.astype(jnp.float32)}

    return loss


def token_batches(mix: dict, cfg: dict, rows: int, seed: int, count: int):
    """``count`` batches ``[rows, seq_len + 1]`` of token ids drawn uniformly
    from the vocabulary slice: every position a real token, all rows
    differ."""
    rng = np.random.default_rng([int(seed), 3])
    return [
        rng.integers(
            0, cfg["vocab_size"], (rows, int(mix["seq_len"]) + 1), dtype=np.int32
        )
        for _ in range(count)
    ]


def run(run) -> None:
    import jax

    with run.phase("program_imports"):
        # The model first: a program without it (the parent of the PR that
        # brought this cell) fails here, before any weight is made.
        from machine_learning_apache_spark_tpu.models import hybrid_lm  # noqa: F401
        from machine_learning_apache_spark_tpu import telemetry
        from machine_learning_apache_spark_tpu.train.loop import fit

    cfg, mix = run.cfg, run.mix
    rows = int(mix["rows_per_chip"]) * run.chips
    s_len = int(mix["seq_len"])

    with run.phase("weights"):
        params = weights.make_params(run.seed, cfg)
        # On the host: a second float32 copy of this model on the device
        # (2.5 GB) would stand in the step's way for the first three steps.
        p0 = jax.tree.map(np.asarray, jax.device_get(params))
    with run.phase("batches"):
        batches = token_batches(mix, cfg, rows, run.seed, int(mix["distinct_batches"]))
    with run.phase("program_objects"):
        loss_fn, state, mesh = build_program(run, params, cfg)
        del params

    feed = Feed(batches, int(mix["warm_steps"]), run.seconds)
    hook = Hook(p0, cfg["optimizer"]["b1"], cfg["hidden_size"])
    del p0
    laps: list[tuple[int, float, int]] = []
    local_so_far: list[tuple[int, float]] = []  # (step, the epoch's mean up to it)

    def emit(line: str) -> None:
        m = train.LAP.search(line)
        if m:
            laps.append((int(m[1]), float(m[2]), int(m[3])))
            local_so_far.append((int(m[1]), float(LOCAL.search(line)[1])))

    trace_dir = None
    first_window_step = CHECK_STEPS + int(mix["warm_steps"])
    fit_kwargs = {}
    if run.trace:
        trace_dir = os.path.join(run.out_dir, "trace")
        skip = int(mix["trace_skip_steps"])
        fit_kwargs = dict(
            profile_dir=trace_dir,
            profile_window=(
                first_window_step + skip,
                first_window_step + skip + int(mix["trace_steps"]),
            ),
        )
    telemetry.get_log().clear()
    step_key = jax.random.key(int(run.seed) & 0x7FFFFFFF)
    t_fit = time.monotonic()
    result = fit(
        state, loss_fn, feed, epochs=Feed.epochs, rng=step_key, mesh=mesh,
        log_every=int(mix["log_every"]), emit=emit, checkpointer=hook,
        checkpoint_every=1,
        prefetch_to_device=int(mix["prefetch_to_device"]), **fit_kwargs,
    )
    run.setup["fit_to_window_s"] = feed.window_start - t_fit
    run.mark_window_start(feed.window_start)
    window_s = hook.window_end - feed.window_start
    run.window_s = window_s
    steps = feed.window_steps
    tokens = steps * rows * s_len
    run.e2e["train_tokens_per_s_per_chip"] = tokens / window_s / run.chips

    losses = [float(h["loss"]) for h in result.history[:CHECK_STEPS]]
    window = result.history[-1]  # the window's epoch: means over its steps
    local = float(window["moe_assignments_local"])
    computed = float(window["moe_assignments_computed"])
    # Nothing dropped: every step computes exactly the assignments that
    # fell on the held experts. A step that did not counts as failed, in
    # the window or before it.
    steps_of = [1] * CHECK_STEPS + [int(mix["warm_steps"]), steps]
    run.attempted = steps
    run.failed = round(sum(
        float(h["moe_steps_unequal"]) * n for h, n in zip(result.history, steps_of)
    ))
    gc_report = feed.gc_watch.stop()
    run.counters.update(
        steps=steps, rows=rows, tokens=tokens, window_s=window_s,
        step_flops=flops_hybrid_lm.train_step_flops(cfg, rows, s_len, local),
        scan_cost_per_step=flops_hybrid_lm.scan_cost_per_step(cfg, rows, s_len),
        experts_cost_per_step=flops_hybrid_lm.experts_cost_per_step(cfg, local),
        lm_flash_cost_per_step=flops_hybrid_lm.flash_cost_per_step(cfg, rows, s_len),
        moe_assignments_local=local, moe_assignments_computed=computed,
        moe_tokens_held_mean=float(window["moe_tokens_held_mean"]),
        moe_tokens_held_max=float(window["moe_tokens_held_max"]),
        moe_aux=float(window["moe_aux"]),
        window_laps=[
            (sec, n) for step, sec, n in laps
            if step - n >= first_window_step
        ],
    )
    run.events = telemetry.get_log().snapshot()
    _report_scan_dispatch(run)
    run.note(
        f"window: {steps} steps of [{rows},{s_len}] in {window_s:.3f} s, "
        f"last loss {window.get('loss')}, assignments local {local:.1f}, "
        f"computed {computed:.1f} a step over {cfg['num_layers']} layers "
        f"(means; steps in which they differ: {run.failed}), "
        f"held max/mean {window['moe_tokens_held_max']:.1f}/"
        f"{window['moe_tokens_held_mean']:.1f}, {gc_report}"
    )
    _report_laps(run, local_so_far, first_window_step)
    run.trace_dir = trace_dir
    if trace_dir:
        _read_scopes(run, trace_dir)
    run.read_memory()

    # Free the program's state before the reference runs on the chip.
    measured = dict(
        losses=losses, grad_norms=hook.grad_norms,
        change_norms=hook.change_norms,
    )
    del result, state, hook, feed, loss_fn
    gc.unfreeze()
    gc.collect()

    block_rows = int(run.cell_file["reference_block_rows"])
    t_ref = time.monotonic()
    reference = reference_steps(
        run, cfg, batches[:CHECK_STEPS], block_rows=block_rows
    )
    run.note(f"reference: {time.monotonic() - t_ref:.1f} s after the window")
    run.compared, worst, printed = compare.train_numbers(
        measured, reference, run.cell_file["limits"]
    )
    run.note(f"worst leaves: {worst}; not compared: {printed}; losses "
             f"program {measured['losses']} reference {reference['losses']}")
    for what in ("grad_norms", "change_norms"):
        ratios = sorted(
            (measured[what][k] / max(v, 1e-30), k, v)
            for k, v in reference[what].items()
        )
        run.note(f"{what}, program / reference (reference's norm), the three "
                 "lowest and highest: " + "; ".join(
                     f"{k} {r:.4g} ({v:.3g})" for r, k, v in ratios[:3] + ratios[-3:]
                 ))
    if run.control:
        run.control_report = control_and_faults(
            run, cfg, batches[:CHECK_STEPS], reference, block_rows=block_rows
        )


def _report_laps(run, local_so_far, first_window_step: int) -> None:
    """Local assignments a step, lap by lap across the window, out of the
    running means of ``fit``'s log lines: routing that drifts while the
    window runs (a router learning to favour the held experts) shows as a
    trend here, where the window's mean hides it."""
    means, done, total = [], 0, 0.0
    for step, mean in local_so_far:
        n = step - first_window_step
        if n <= done:
            continue  # a lap of the warm-up
        means.append((mean * n - total) / (n - done))
        done, total = n, mean * n
    if means:
        run.counters["moe_assignments_local_by_lap"] = means
        run.note(
            "assignments local a step, by log lap across the window (the "
            "batches cycle, so like laps are a cycle apart): "
            + " ".join(f"{m:.0f}" for m in means)
        )


def _report_scan_dispatch(run) -> None:
    seen = {}
    for e in run.setup_events + run.events:
        if e.name == "ops.gated_delta_dispatch" and e.attrs:
            key = (e.attrs.get("site"), e.attrs.get("impl"), e.attrs.get("reason"))
            seen[key] = seen.get(key, 0) + 1
    for (site, impl, reason), n in seen.items():
        run.note(f"gated delta site {site}: {impl} ({reason}) x{n}")


def _read_scopes(run, trace_dir: str) -> None:
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        return
    t = time.monotonic()
    found = scope_trace.scope_seconds(
        path, SCOPES, TRAIN_STEP_MODULE, note=run.note
    )
    if found is None:
        return
    seconds, steps, step_s = found
    run.counters["scope_ms"] = {k: v * 1e3 for k, v in seconds.items()}
    run.note(
        f"device ms a step under the program's scopes, over {steps} whole "
        f"steps of {step_s * 1e3:.1f} ms (read in {time.monotonic() - t:.1f} s): "
        + ", ".join(f"{k} {v * 1e3:.3f}" for k, v in seconds.items())
        + f"; under none of them {(step_s - sum(seconds.values())) * 1e3:.3f}"
    )


def reference_steps(
    run, cfg, batches, *, block_rows, matmul=None, rows=None, frozen=False,
    top_k=None,
):
    """The plain reference through the first steps: each step's loss, the
    first gradient's leaf norms and the leaf norms of the parameters'
    change. ``matmul`` (the control's precision), ``rows`` (a slice of the
    batch), ``frozen`` (the state returned unchanged) and ``top_k`` (experts
    a token) let the same code stand in the program's place."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import hybrid_lm as ref

    device = jax.local_devices()[0]
    kwargs = {} if matmul is None else {"matmul": matmul}
    with ref.on_device(device):
        params = weights.make_params(run.seed, cfg)
        p0 = jax.tree.map(np.asarray, jax.device_get(params))
        opt = cfg["optimizer"]
        adam = {"m": None, "v": None, "t": 0}  # the moments stay on the host
        losses, grad_norms = [], None
        block_fns = ref.make_block_fns(cfg, top_k=top_k, **kwargs)
        for batch in batches:
            loss, grads = ref.loss_and_grads(
                params, cfg, batch, block_rows=block_rows, rows=rows,
                block_fns=block_fns, top_k=top_k,
            )
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = ref.leaf_norms(grads, cfg["hidden_size"])
            if not frozen:
                params, adam = ref.adam_step_host(
                    params, grads, adam, lr=opt["learning_rate"],
                    b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                )
            del grads
        del adam
        delta = jax.jit(
            lambda p, q: jax.tree.map(jnp.subtract, p, q), donate_argnums=0
        )(params, p0)
        change_norms = ref.leaf_norms(delta, cfg["hidden_size"])
    return dict(
        losses=losses, grad_norms=grad_norms, change_norms=change_norms
    )


def control_and_faults(run, cfg, batches, reference, *, block_rows):
    """Builder's readings (``--control``): the reference in int8 and float8
    and each planted fault, put in the program's place."""
    from benchmark.reference import hybrid_lm as ref

    n = batches[0].shape[0]
    stand_ins = {
        "control_int8": dict(matmul=ref.lowp_matmul),
        "control_fp8": dict(matmul=ref.fp8_matmul),
        "fault_half_batch": dict(rows=slice(0, n // 2)),
        "fault_state_unchanged": dict(frozen=True),
        "fault_top1_only": dict(top_k=1),
    }
    report = {}
    for name in compare.chosen(run.control, stand_ins):
        stand_in = reference_steps(
            run, cfg, batches, block_rows=block_rows, **stand_ins[name]
        )
        numbers, _, printed = compare.train_numbers(
            stand_in, reference, run.cell_file["limits"]
        )
        report[name] = compare.verdict(numbers, printed)
    return report


def toy(cfg: dict, mix: dict, cell_file: dict) -> None:
    """This kind's sizes for a CPU rehearsal (``benchmark.rehearse``): every
    width the kind reads, one linear and one full-attention layer, a share of
    4 of 16 experts, rows of two scan chunks (64 + 16, the second padded)."""
    cfg.update(
        vocab_size=80, hidden_size=32, num_layers=2, full_attention_interval=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        router_width=16, num_experts=4, experts_held=[4, 4],
        num_experts_per_tok=4, max_len=80,
    )
    mix.update(
        rows_per_chip=4, seq_len=80, warm_steps=1, distinct_batches=4,
        log_every=2, trace_skip_steps=1, trace_steps=2,
    )
    cell_file["reference_block_rows"] = 2

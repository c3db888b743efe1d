"""A training cell: ``train.loop.fit`` driven by a loader that stops
yielding when the clock runs out.

One ``fit`` call builds one compiled step with its state and carries it
from the seed through three checked steps, the warm-up and the window:

    epoch 0, 1, 2   one step each; the hook that ``fit`` hands its state to
                    after every epoch (the ``checkpointer=`` seam) reads the
                    first gradient out of Adam's first moment after step 1
                    and the parameters' change after step 3;
    epoch 3         the mix's warm-up steps; its end is a device sync;
    epoch 4         the window: batches until ``--seconds`` have passed,
                    then the loop's own drain and the hook's final sync.

The reference follows the same three steps from the same seed-made weights
and batches (dropout masks included) once the window has closed, the peak
memory has been read and the program's state is freed.
"""

from __future__ import annotations

import gc
import os
import re
import statistics
import time

from benchmark import compare, flops, program, traffic, weights

CHECK_STEPS = 3
LAP = re.compile(r"step (\d+) \|.*\| ([\d.]+) sec/(\d+) batches")


class Feed:
    """The loader ``fit`` iterates: epochs 0-2 one checked step each, epoch
    3 the warm-up, epoch 4 the window. Batches cycle through a fixed set
    made from the seed."""

    def __init__(self, batches, warm_steps: int, seconds: float):
        self.batches = batches
        self.warm_steps = warm_steps
        self.seconds = seconds
        self.epoch = 0
        self.cursor = 0
        self.window_start: float | None = None
        self.window_steps = 0
        from benchmark.run import GcWatch

        self.gc_watch = GcWatch()

    epochs = CHECK_STEPS + 2

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def _next(self):
        batch = self.batches[self.cursor % len(self.batches)]
        self.cursor += 1
        return batch

    def __iter__(self):
        if self.epoch < CHECK_STEPS:
            yield self._next()
        elif self.epoch == CHECK_STEPS:
            for _ in range(self.warm_steps):
                yield self._next()
        else:
            # The previous epoch ended in a device sync: the chip is idle
            # and everything is compiled. The window opens here.
            gc.collect()
            gc.freeze()
            self.gc_watch.start()
            self.window_start = time.monotonic()
            deadline = self.window_start + self.seconds
            while time.monotonic() < deadline:
                self.window_steps += 1
                yield self._next()


class Hook:
    """Stands where ``fit`` expects a checkpoint manager and is handed the
    live state after every epoch. It saves nothing: it reads what the
    comparison needs while the state exists, and closes the window."""

    directory = "(benchmark hook: nothing is written)"

    def __init__(self, p0, b1: float, block: int):
        self.p0 = p0
        self.b1 = b1
        self.block = block
        self.calls = 0
        self.grad_norms: dict | None = None
        self.change_norms: dict | None = None
        self.window_end: float | None = None

    def save(self, state, wait=False, meta=None):  # noqa: ARG002
        import jax

        from benchmark.reference.transformer import leaf_norms

        epoch = self.calls
        self.calls += 1
        if epoch == 0:
            mu = _first_moment(state.opt_state)
            self.grad_norms = {
                k: v / (1.0 - self.b1)
                for k, v in leaf_norms(mu, self.block).items()
            }
        elif epoch == CHECK_STEPS - 1:
            params = _unboxed(state.params)
            p0 = jax.tree.map(
                lambda a, b: jax.device_put(a, b.sharding), self.p0, params
            )
            delta = jax.jit(
                lambda p, q: jax.tree.map(lambda a, b: a - b, p, q)
            )(params, p0)
            self.change_norms = leaf_norms(delta, self.block)
            self.p0 = None
        elif epoch == CHECK_STEPS + 1:
            jax.block_until_ready(state.params)
            self.window_end = time.monotonic()

    def wait(self):
        pass


def _unboxed(tree):
    import flax.linen as nn

    return nn.unbox(tree)


def _first_moment(opt_state):
    """Adam's first moment, wherever the optimizer chain keeps it."""
    import jax

    found = [
        s.mu for s in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")
        ) if hasattr(s, "mu")
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return _unboxed(found[0])


def build_program(run, params, cfg, mix):
    """The system under test for this cell: the model, the recipe's loss
    and optimizer, a fresh ``TrainState`` and the mesh."""
    from machine_learning_apache_spark_tpu.parallel.mesh import (
        data_parallel_mesh,
    )
    from machine_learning_apache_spark_tpu.recipes.translation import (
        make_translation_loss,
    )
    from machine_learning_apache_spark_tpu.train.state import (
        TrainState,
        make_optimizer,
    )

    model = program.make_model(cfg)
    opt = cfg["optimizer"]
    tx = make_optimizer(
        opt["name"], opt["learning_rate"],
        b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
    )
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    mesh = data_parallel_mesh(run.chips) if run.chips > 1 else None
    return model, make_translation_loss(model, cfg["pad_id"]), state, mesh


def run(run) -> None:
    import jax

    with run.phase("program_imports"):
        from machine_learning_apache_spark_tpu import telemetry
        from machine_learning_apache_spark_tpu.train.loop import fit

    cfg, mix = run.cfg, run.mix
    rows = int(mix["rows_per_chip"]) * run.chips
    s_len, t_len = int(mix["src_len"]), int(mix["trg_len"])

    with run.phase("weights"):
        params = weights.make_params(run.seed, cfg)
        p0 = jax.tree.map(lambda x: x.copy(), params)
        jax.block_until_ready(p0)
    with run.phase("batches"):
        batches = traffic.train_batches(
            mix, cfg, rows, run.seed, int(mix["distinct_batches"])
        )
    with run.phase("program_objects"):
        model, loss_fn, state, mesh = build_program(run, params, cfg, mix)
        del params

    feed = Feed(batches, int(mix["warm_steps"]), run.seconds)
    hook = Hook(p0, cfg["optimizer"]["b1"], cfg["d_model"])
    del p0
    laps: list[tuple[int, float, int]] = []

    def emit(line: str) -> None:
        m = LAP.search(line)
        if m:
            laps.append((int(m[1]), float(m[2]), int(m[3])))

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
    tokens = steps * rows * t_len
    run.e2e["train_tokens_per_s_per_chip"] = tokens / window_s / run.chips
    run.attempted, run.failed = steps, 0

    losses = [float(h["loss"]) for h in result.history[:CHECK_STEPS]]
    gc_report = feed.gc_watch.stop()
    run.counters.update(
        steps=steps, rows=rows, tokens=tokens, window_s=window_s,
        step_flops=flops.train_step_flops(cfg, rows, s_len, t_len),
        flash_cost_per_step=flops.train_flash_forward_cost(
            cfg, rows, s_len, t_len
        ),
        flash_calls_per_step=3 * cfg["num_layers"],
        window_laps=[
            (sec, n) for step, sec, n in laps
            if step - n >= first_window_step
        ],
    )
    run.events = telemetry.get_log().snapshot()
    run.note(
        f"window: {steps} steps of [{rows},{t_len}] in {window_s:.3f} s, "
        f"last loss {result.history[-1].get('loss')}, {gc_report}"
    )
    run.trace_dir = trace_dir
    run.read_memory()

    # Free the program's state before the reference runs on the chip.
    measured = dict(
        losses=losses, grad_norms=hook.grad_norms,
        change_norms=hook.change_norms,
    )
    del result, state, hook, feed
    gc.unfreeze()
    gc.collect()

    t_ref = time.monotonic()
    reference = reference_steps(
        run, cfg, batches[:CHECK_STEPS], step_key,
        block_rows=int(run.cell_file["reference_block_rows"]),
    )
    run.note(f"reference: {time.monotonic() - t_ref:.1f} s after the window")
    run.compared, worst, printed = compare.train_numbers(
        measured, reference, run.cell_file["limits"]
    )
    run.note(f"worst leaves: {worst}; not compared: {printed}; losses "
             f"program {measured['losses']} reference {reference['losses']}")
    if run.control:
        run.control_report = control_and_faults(
            run, cfg, batches[:CHECK_STEPS], step_key, reference,
            block_rows=int(run.cell_file["reference_block_rows"]),
        )


def reference_steps(
    run, cfg, batches, step_key, *, block_rows, matmul=None, rows=None,
    frozen=False,
):
    """The plain reference through the first steps: each step's loss, the
    first gradient's leaf norms and the leaf norms of the parameters'
    change. ``matmul`` (the control's precision), ``rows`` (a slice of the
    batch: a planted fault) and ``frozen`` (the state returned unchanged)
    let the same code stand in the program's place."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import transformer as ref

    device = jax.local_devices()[0]
    kwargs = {} if matmul is None else {"matmul": matmul}
    with ref.on_device(device):
        params = weights.make_params(run.seed, cfg)
        p0 = params
        opt = cfg["optimizer"]
        adam = ref.adam_init(params)
        losses, grad_norms = [], None
        key = step_key
        block_grad = ref.make_block_grad(
            cfg, batches[0][0].shape[0], dropout=True, **kwargs
        )
        for src, trg in batches:
            key, sub = jax.random.split(key)
            loss, grads = ref.loss_and_grads(
                params, cfg, jnp.asarray(src), jnp.asarray(trg),
                step_key=sub, block_rows=block_rows, rows=rows,
                block_grad=block_grad,
            )
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = ref.leaf_norms(grads, cfg["d_model"])
            if not frozen:
                params, adam = ref.adam_step(
                    params, grads, adam, lr=opt["learning_rate"],
                    b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                )
            del grads
        delta = jax.jit(lambda p, q: jax.tree.map(jnp.subtract, p, q))(
            params, p0
        )
        change_norms = ref.leaf_norms(delta, cfg["d_model"])
    return dict(
        losses=losses, grad_norms=grad_norms, change_norms=change_norms
    )


def control_and_faults(run, cfg, batches, step_key, reference, *, block_rows):
    """Builder's readings (``--control``): the reference in int8 (the
    control; float8 is read beside it) and each planted fault, put in the
    program's place, compared like it and given ``decide``'s verdict."""
    from benchmark.reference import transformer as ref

    n = batches[0][0].shape[0]
    stand_ins = {
        "control_int8": dict(matmul=ref.lowp_matmul),
        "control_fp8": dict(matmul=ref.fp8_matmul),
        "fault_half_batch": dict(rows=slice(0, n // 2)),
        "fault_state_unchanged": dict(frozen=True),
    }
    if run.chips > 1:
        stand_ins["fault_no_exchange"] = dict(rows=slice(0, n // run.chips))
    report = {}
    for name in compare.chosen(run.control, stand_ins):
        stand_in = reference_steps(
            run, cfg, batches, step_key, block_rows=block_rows,
            **stand_ins[name],
        )
        numbers, _, printed = compare.train_numbers(
            stand_in, reference, run.cell_file["limits"]
        )
        report[name] = compare.verdict(numbers, printed)
    return report


def toy(cfg: dict, mix: dict, cell_file: dict) -> None:
    """This kind's sizes for a CPU rehearsal (``benchmark.rehearse``)."""
    mix.update(
        rows_per_chip=8, src_len=12, trg_len=12, warm_steps=1,
        distinct_batches=4, log_every=2, trace_skip_steps=1, trace_steps=2,
    )
    cell_file["reference_block_rows"] = 4


def summarize_laps(run) -> float | None:
    laps = run.counters.get("window_laps") or []
    per_step = [sec / n for sec, n in laps if n > 0]
    return statistics.median(per_step) * 1e3 if per_step else None

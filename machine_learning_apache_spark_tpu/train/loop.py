"""Training/eval loop machinery — reference layer L7, implemented once.

Every reference script re-implements the same loop inline (SURVEY.md §1 L7):
epochs × batches of {forward → loss → zero_grad → backward → step}, then an
eval pass of softmax→argmax→accuracy, with wall-clock prints. Here the loop
body is a single jitted function (forward+backward+update fused into one XLA
program) and the Python loop only feeds batches and accumulates metrics.

Data parallelism needs no separate loop: with params replicated and the batch
sharded over the mesh's ``"data"`` axis, XLA's sharding propagation compiles
the gradient reduction into a ``psum`` over ICI — the reference's entire
DDP/gloo layer (C11) disappears into the compiled step (SURVEY.md §7).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu import telemetry
from machine_learning_apache_spark_tpu.parallel.mesh import shard_batch
from machine_learning_apache_spark_tpu.train.metrics import MetricBundle, logits_accuracy
from machine_learning_apache_spark_tpu.train.state import TrainState
from machine_learning_apache_spark_tpu.utils.logging import get_logger
from machine_learning_apache_spark_tpu.utils.profiling import annotate
from machine_learning_apache_spark_tpu.utils.timing import Timer

log = get_logger(__name__)

# loss_fn contract: (params, batch, rng) -> (scalar_loss, aux_dict)
LossFn = Callable[[Any, Any, jax.Array], tuple[jnp.ndarray, dict]]


def make_train_step(loss_fn: LossFn):
    """One fused forward+backward+update XLA program.

    The incoming state is donated: params/opt-state buffers are updated in
    place instead of copied — on TPU that halves the optimizer's HBM
    traffic, typically the bound on small models. Callers must rebind
    (``state = step(state, ...)``), which ``fit`` does.
    """

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state: TrainState, batch, rng: jax.Array):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch, rng
        )
        return state.apply_gradients(grads), loss, aux

    return step


def make_multi_step(loss_fn: LossFn):
    """K fused train steps per host dispatch, scanned inside ONE XLA program.

    Why this exists: every ``step(...)`` call costs a host dispatch, and a
    small model's device step (TinyVGG) is shorter than that — the host,
    not the chip, sets the pace. ``lax.scan`` moves the step
    loop into the compiled program: one dispatch covers K steps, the device
    runs back-to-back, and the host has K step-times to enqueue the next
    call. The K microbatches arrive stacked on a leading axis
    (``parallel.shard_batch_stack``); K is implicit in the shapes.

    Rng contract: the body splits exactly like ``fit``'s host loop
    (``rng, step_rng = split(rng)`` per step) and the advanced key is
    returned, so a run produces bit-identical params whether dispatched
    one step at a time or K at a time (pinned by
    ``tests/test_train.py::TestStepsPerCall``).
    """

    @functools.partial(jax.jit, donate_argnums=0)
    def multi_step(state: TrainState, batches, rng: jax.Array):
        def body(carry, batch):
            state, rng = carry
            rng, step_rng = jax.random.split(rng)
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch, step_rng
            )
            return (state.apply_gradients(grads), rng), (loss, aux)

        (state, rng), (losses, auxes) = jax.lax.scan(body, (state, rng), batches)
        return state, rng, losses, auxes

    return multi_step


def make_eval_step(loss_fn: LossFn):
    @jax.jit
    # mlspark-lint: ok jit-donate -- eval step: state is read, not updated; donating would consume the caller's buffers
    def step(state: TrainState, batch, rng: jax.Array):
        return loss_fn(state.params, batch, rng)

    return step


@dataclass
class FitResult:
    state: TrainState
    train_seconds: float
    history: list[dict] = field(default_factory=list)
    # Step the run auto-resumed from (fit(resume=True) found a valid
    # checkpoint); None for a fresh run.
    resumed_step: int | None = None

    @property
    def final_loss(self) -> float:
        return self.history[-1]["loss"] if self.history else float("nan")


def _rng_to_meta(rng: jax.Array) -> list[int]:
    """Host-serializable form of a PRNG key for the checkpoint sidecar."""
    import numpy as np

    return np.asarray(jax.device_get(jax.random.key_data(rng))).tolist()


def _rng_from_meta(data: list[int]) -> jax.Array:
    return jax.random.wrap_key_data(jnp.asarray(data, dtype=jnp.uint32))


def _with_comms_counters(zstep, state):
    """Wrap the fused ZeRO-1 step with the comms telemetry contract
    (docs/OBSERVABILITY.md): per-step wire-byte counters (static amounts —
    no device sync), a per-chip optimizer-state-bytes gauge set once, and
    one ``counter`` event per fit so the gang report
    (``telemetry_report.py`` comms section) can compute bytes/step."""
    if not telemetry.enabled():
        return zstep
    from machine_learning_apache_spark_tpu.parallel import zero as _zero

    stats = zstep.comms_stats
    reg = telemetry.get_registry()
    reg.gauge("comms", "opt_state_bytes_per_chip").set(
        _zero.opt_state_bytes_per_chip(state)
    )
    telemetry.annotate(
        "comms.zero1",
        **{k: v for k, v in stats.items() if k != "grad_bytes_fp32"},
    )
    rs = reg.counter("comms", "bytes_reduce_scattered")
    ag = reg.counter("comms", "bytes_allgathered")
    exposed = reg.counter("comms", "bytes_exposed")
    overlapped = reg.counter("comms", "bytes_overlapped")
    counted = [0]

    def step(st, batch, rng):
        out = zstep(st, batch, rng)
        rs.inc(stats["reduce_scatter_bytes"])
        ag.inc(stats["allgather_bytes"])
        exposed.inc(stats["bytes_exposed"])
        overlapped.inc(stats["bytes_overlapped"])
        counted[0] += 1
        return out

    def flush():
        if not counted[0]:
            return
        log_ = telemetry.get_log()
        common = {
            "steps": counted[0],
            "comms_dtype": stats["comms_dtype"],
            "overlap": stats["overlap"],
        }
        log_.emit(
            "counter", "comms.bytes_reduce_scattered",
            value=counted[0] * stats["reduce_scatter_bytes"], attrs=common,
        )
        log_.emit(
            "counter", "comms.bytes_allgathered",
            value=counted[0] * stats["allgather_bytes"], attrs=common,
        )
        # The exposed/overlapped split of the same wire bytes — the static
        # pipeline model from comms_bytes_per_step (overlap on: 1/nb of
        # each collective exposed at the pipeline fill/drain; off: all of
        # it). telemetry_report's comms section turns these into the
        # comms-bound/compute-bound verdict inputs.
        log_.emit(
            "counter", "comms.bytes_exposed",
            value=counted[0] * stats["bytes_exposed"], attrs=common,
        )
        log_.emit(
            "counter", "comms.bytes_overlapped",
            value=counted[0] * stats["bytes_overlapped"], attrs=common,
        )
        counted[0] = 0

    step.flush_comms = flush
    return step


def fit(
    state: TrainState,
    loss_fn: LossFn,
    train_loader: Iterable | None = None,
    *,
    data: Iterable | None = None,
    epochs: int,
    rng: jax.Array | None = None,
    mesh=None,
    log_every: int = 100,
    emit: Callable[[str], None] | None = None,
    checkpointer=None,
    checkpoint_every: int = 1,
    profile_dir: str | None = None,
    profile_window: tuple[int, int] = (2, 5),
    metrics_file: str | None = None,
    sync_check_every: int = 0,
    zero1: bool = False,
    dp_mode: str | None = None,
    dp_bucket_bytes: int | None = None,
    dp_comms_dtype: str | None = None,
    dp_overlap: bool | None = None,
    steps_per_call: int = 1,
    prefetch_to_device: int = 0,
    resume: bool = False,
    elastic: bool | None = None,
) -> FitResult:
    """The canonical loop (``pytorch_cnn.py:125-146`` shape): epochs × batches,
    per-``log_every``-batch loss/time prints
    (``pytorch_machine_translator.py:199-205``), total wall-time at the end
    (the universal reference metric, SURVEY.md §6).

    ``train_loader`` yields batch pytrees; if it has ``set_epoch``, it is
    called per epoch (the ``sampler.set_epoch`` contract,
    ``distributed_cnn.py:168``, with correct Q3 semantics).

    ``data=`` is an alias for ``train_loader`` and the idiomatic spelling
    for an ``ingest.StreamingPipeline``: fit binds its mesh to the
    pipeline's device stage, consumes device-resident batches directly,
    captures the pipeline's stream state (mixture RNG, cursors) in each
    checkpoint's meta sidecar, restores it on ``resume=True`` so the
    resumed run replays the identical batch sequence, and shuts the
    pipeline's producer threads down when fit returns OR raises (no
    leaked threads — docs/DATA.md). The scanned ``steps_per_call`` path
    and fit's own ``prefetch_to_device`` stack/shard host batches
    themselves, so with either of those the pipeline is bound to yield
    host batches.

    ``checkpointer`` (a ``train.checkpoint.CheckpointManager``) saves the
    state every ``checkpoint_every`` epochs — persistence the reference
    lacks entirely (SURVEY.md §5 checkpoint/resume).

    ``profile_dir`` captures a jax.profiler device trace over the global-step
    window ``profile_window`` (skipping compile/warmup steps) — the tracing
    subsystem the reference approximates with ``time.time()`` pairs
    (SURVEY.md §5).

    ``metrics_file`` appends one JSON line per epoch (and a final run
    record) — the structured counterpart of the reference's print-only
    metrics (SURVEY.md §5 metrics/logging).

    ``sync_check_every=N`` runs ``parallel.assert_replicas_in_sync`` on the
    params every N epochs — the race-detector analogue for the reference's
    Q2-class replica-drift bug (SURVEY.md §5), raising if a multi-process
    gang's replicas diverge. 0 (default) disables the check (it is a
    cross-host sync point).

    ``dp_mode="zero1"`` (or env ``MLSPARK_DP_MODE=zero1`` — the launcher
    gang plumbing) switches the data-parallel update to the fused ZeRO-1
    step (``parallel.zero``): gradients reduce-scatter over the ``data``
    axis, each chip updates its 1/N parameter shard (optimizer moments
    sharded from the start — ~1/N the optimizer memory), updated params
    allgather back. Same trajectory as the replicated step (bit-identical
    with the default fp32 comms). ``dp_bucket_bytes`` /
    ``dp_comms_dtype`` (env ``MLSPARK_ZERO1_BUCKET_BYTES`` /
    ``MLSPARK_COMMS_DTYPE``) tune the gradient collective;
    ``dp_overlap`` (env ``MLSPARK_ZERO1_OVERLAP``, default on) selects
    the pipelined bucket schedule that hides the reduce-scatter behind
    backward and the params allgather behind the per-bucket optimizer
    updates — see docs/PARALLELISM.md for the tradeoffs. On a hybrid
    ``data x model`` mesh (``parallel.make_mesh({"data": D, "model":
    T})``) the ZeRO-1 update composes with tensor parallelism: params
    keep their logical TP placement, the flat optimizer moments shard
    over all D x T devices, and the step runs the implicit
    weight-update-sharding form (fp32 comms only). Distinct from the
    legacy ``zero1=True`` flag (implicit opt-state sharding, replicated
    step).

    ``steps_per_call=K`` dispatches K batches per host→device call via a
    ``lax.scan``-fused step (``make_multi_step``) — same math, same rng
    stream, K× fewer dispatches; the win for small/fast models whose step
    time is comparable to dispatch overhead. Ragged trailing groups (end of
    epoch) fall back to single steps, so any loader length works.

    ``prefetch_to_device=N`` (with a mesh, single-step path) shards batches
    onto the mesh N ahead of consumption (``parallel.device_prefetch``):
    host→device transfers overlap device compute instead of serializing in
    front of each dispatch. Combine with the loader's host-side
    ``prefetch`` for a fully double-buffered input pipeline.

    ``resume=True`` (with a ``checkpointer``) restores the newest valid
    checkpoint before training and continues the run from it: params and
    opt-state from the checkpoint, epoch counter and rng stream from its
    sidecar meta (docs/FAULT_TOLERANCE.md). The epoch loop then runs only
    the remaining epochs and the rng stream picks up exactly where the
    interrupted run left it, so a resumed trajectory is bit-identical to
    an uninterrupted one from the last checkpoint onward. No checkpoint
    on disk -> a normal fresh run; ``FitResult.resumed_step`` records
    which happened.

    Every checkpoint sidecar carries a topology stamp (world size, mesh
    axes, dp mode, ZeRO-1 bucket layout). A resume whose own topology
    matches restores bit-identically as above; on mismatch, ``elastic``
    decides (arg > ``MLSPARK_ELASTIC`` env — set by
    ``Distributor(elastic=True)`` — > off): disabled raises
    ``TopologyMismatch`` naming both topologies (a wrong-world resume
    must never silently misload per-rank shards); enabled routes the
    restore through ``train/reshard.py`` — the old gang's per-rank flat
    optimizer shards are reassembled and resharded onto this run's mesh,
    params/rng/epoch adopt, and the ingest stream state is re-scattered
    (equalization recomputes for the new shard count). See
    docs/FAULT_TOLERANCE.md "Elastic resume".

    The input ``state``'s buffers are CONSUMED (the fused step donates them
    for in-place updates); use ``FitResult.state``, never the argument,
    afterwards. Build from copied params if two fits must share an init.
    """
    from machine_learning_apache_spark_tpu.ops.attention import kernel_mesh
    from machine_learning_apache_spark_tpu.utils.profiling import StepWindowTracer
    from machine_learning_apache_spark_tpu.parallel import zero as _zero

    if data is not None:
        if train_loader is not None:
            raise ValueError("pass either train_loader or data=, not both")
        train_loader = data
    if train_loader is None:
        raise ValueError("fit needs a train_loader (or data=...)")
    emit = emit or log.info
    rng = rng if rng is not None else jax.random.key(0)
    if steps_per_call < 1:
        raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
    # Streaming-pipeline integration (duck-typed marker, no import cycle):
    # bind fit's mesh into the pipeline's device stage — except on the
    # host-batch paths (scan stacking, fit-side device prefetch), which
    # place batches themselves.
    streaming = getattr(train_loader, "is_streaming_pipeline", False)
    if streaming:
        if mesh is not None:
            train_loader.bind(mesh=mesh)
        if steps_per_call > 1 or (prefetch_to_device > 0 and mesh is not None):
            train_loader.bind(device=False)
    mode = _zero.resolve_dp_mode(dp_mode)
    if mode == "zero1":
        # The fused sharded-update path (parallel.zero,
        # docs/PARALLELISM.md): reduce-scatter grads, update this chip's
        # 1/N param shard, allgather. Distinct from the legacy
        # zero1=True flag, which shards the optimizer moments via XLA
        # propagation but keeps the replicated allreduce step.
        if mesh is None:
            raise ValueError("dp_mode='zero1' requires a mesh (use_mesh=True)")
        if zero1:
            raise ValueError(
                "pass either dp_mode='zero1' (fused reduce-scatter step) or "
                "zero1=True (implicit opt-state sharding), not both"
            )
        if steps_per_call > 1:
            raise ValueError(
                "dp_mode='zero1' runs its own fused step; steps_per_call "
                "fusion is not supported with it"
            )
    elif (
        dp_bucket_bytes is not None
        or dp_comms_dtype is not None
        or dp_overlap is not None
    ):
        raise ValueError(
            "dp_bucket_bytes/dp_comms_dtype/dp_overlap only apply to "
            "dp_mode='zero1'"
        )
    step_fn = make_train_step(loss_fn)
    multi_fn = make_multi_step(loss_fn) if steps_per_call > 1 else None
    tracer = StepWindowTracer(
        profile_dir, start=profile_window[0], stop=profile_window[1]
    )
    if mesh is not None and mode == "zero1":
        config = _zero.Zero1Config.from_env(
            bucket_bytes=dp_bucket_bytes,
            comms_dtype=dp_comms_dtype,
            overlap=dp_overlap,
        )
        state = _zero.shard_optimizer_state(state, mesh, config)
        step_fn = _with_comms_counters(
            _zero.make_zero1_step(loss_fn, mesh, state), state
        )
    elif mesh is not None:
        # Logical-annotation-aware placement: DP-only meshes replicate (DDP
        # whole-replica semantics); a mesh with a "model" axis tensor-shards
        # annotated params and their optimizer moments (SURVEY.md §2.3).
        # zero1=True additionally shards optimizer moments 1/N over the
        # "data" axis (ZeRO stage 1) — identical math, less HBM per chip.
        from machine_learning_apache_spark_tpu.parallel.tensor_parallel import (
            shard_state,
        )

        state = shard_state(state, mesh, zero1=zero1)
    elif zero1:
        # Never a silent no-op (same convention as the recipe-surface
        # parallelism flags): without a mesh there is nothing to shard
        # the optimizer moments over.
        raise ValueError("zero1=True requires a mesh (use_mesh=True)")

    resumed_step: int | None = None
    resume_meta: dict = {}
    start_epoch = 0
    if resume and checkpointer is not None:
        from machine_learning_apache_spark_tpu.train import (
            checkpoint as _ckpt,
            reshard as _reshard,
        )

        # After shard_state so the restore template carries the run's real
        # layout — orbax restores straight into the sharded buffers.
        # Topology is validated BEFORE any restore: a cross-topology
        # attempt would fail shapes-first (or worse, misload), so the
        # stamp decides the route up front.
        current = _ckpt.topology_stamp(state)
        old = checkpointer.newest_topology_stamp()
        crossed = old is not None and not _ckpt.same_topology(old, current)
        if crossed:
            if not _reshard.resolve_elastic(elastic):
                raise _reshard.TopologyMismatch(
                    f"checkpoints under {checkpointer.directory} were "
                    f"written by a different topology — checkpoint "
                    f"topology {old} vs this run's {current}. Pass "
                    "elastic=True (or set MLSPARK_ELASTIC=1, which "
                    "Distributor(elastic=True) does) to reshard, or "
                    "point the run at a fresh checkpoint directory."
                )
            restored = _reshard.elastic_restore(
                checkpointer, state, old_stamp=old
            )
        else:
            restored = checkpointer.restore_latest_valid(state)
        if restored is not None:
            state, resumed_step, resume_meta = restored
            if "rng" in resume_meta:
                rng = _rng_from_meta(resume_meta["rng"])
            start_epoch = int(resume_meta.get("epoch", -1)) + 1
            if streaming and resume_meta.get("ingest") is not None:
                # Stream position (mixture RNG state, per-source cursors)
                # from the sidecar: the resumed run replays the exact
                # batch sequence the interrupted one would have produced.
                ingest_state = resume_meta["ingest"]
                if crossed:
                    from machine_learning_apache_spark_tpu.ingest import (
                        rescatter_stream_state,
                    )

                    ingest_state = rescatter_stream_state(
                        ingest_state,
                        old_world=int(old.get("world_size", 1)),
                        new_world=int(current.get("world_size", 1)),
                        shard=getattr(train_loader, "shard", "records"),
                    )
                train_loader.load_state_dict(ingest_state)
            if crossed:
                telemetry.annotate(
                    "train.elastic_resume",
                    step=int(resumed_step),
                    old_world=int(old.get("world_size", 1)),
                    new_world=int(current.get("world_size", 1)),
                    old_mesh=old.get("mesh"),
                    new_mesh=current.get("mesh"),
                    dp_mode=current.get("dp_mode"),
                )
                emit(
                    f"elastic resume: resharded checkpoint step "
                    f"{resumed_step} from world "
                    f"{old.get('world_size')} onto world "
                    f"{current.get('world_size')}"
                )
            emit(
                f"resuming from checkpoint step {resumed_step} "
                f"(starting epoch {start_epoch})"
            )

    from machine_learning_apache_spark_tpu.train.metrics import MetricsLogger

    # Rank-0 gated like every other metrics emission (utils.logging): a
    # multi-process gang writing one shared file would duplicate every record.
    sink = (
        MetricsLogger(metrics_file)
        if metrics_file and jax.process_index() == 0
        else None
    )
    total_timer = Timer("train").start()
    span_timer = Timer("span").start()
    fit_span = telemetry.span(
        "train.fit", epochs=epochs, steps_per_call=steps_per_call,
        resumed_step=resumed_step,
    )
    try:
        try:
            # kernel_mesh: the steps trace inside, and a Pallas kernel
            # must know the mesh to launch per shard (it cannot be
            # partitioned by XLA).
            with fit_span, kernel_mesh(mesh):
                state, history = _run_epochs(
                    state, step_fn, train_loader, epochs, rng, mesh,
                    log_every, emit, tracer, checkpointer, checkpoint_every,
                    span_timer, sink, sync_check_every, multi_fn,
                    steps_per_call, prefetch_to_device, start_epoch,
                    int(resumed_step) if resumed_step is not None else 0,
                )
        except BaseException as e:
            # Flight recorder: an unhandled exception out of the training
            # loop ships with its last events (the failing step's spans are
            # the newest entries). Errored span_end for train.fit was just
            # emitted by the with-block, so it is included.
            telemetry.dump_flight(
                f"train.fit:{type(e).__name__}", extra={"error": str(e)[:500]}
            )
            raise
        finally:
            # An exception mid-window must still stop the (process-global)
            # jax profiler, or every later trace in this process fails to
            # start.
            tracer.close()
            # Comms byte totals land on the event log even for a run that
            # died mid-epoch (the flight recorder then carries them too).
            if hasattr(step_fn, "flush_comms"):
                step_fn.flush_comms()
        if not history and resume_meta.get("metrics"):
            # Already-complete resume (a gang retry where THIS rank had
            # finished before teardown): zero epochs remain, so report the
            # final epoch's metrics recorded in the checkpoint sidecar —
            # the caller's loss-parity checks must hold on every retried
            # rank, including the ones with nothing left to do.
            history = [dict(resume_meta["metrics"])]
        # Block on the final state so the reported wall-time includes device
        # work (the reference's time.time() pairs measure eager CPU
        # execution; under async dispatch the analogue requires a sync point).
        jax.block_until_ready(state.params)
        seconds = total_timer.stop()
        if checkpointer is not None:
            checkpointer.wait()  # durability barrier, outside the timed span
        if sink is not None:
            sink.write({
                "kind": "run",
                "train_seconds": seconds,
                "epochs": len(history),
                "final_loss": history[-1].get("loss") if history else None,
            })
    finally:
        if sink is not None:
            sink.close()
        if streaming:
            # Producer-thread teardown on BOTH exits (return and raise):
            # a crashed fit must not leave ingest threads pinning buffered
            # batches (pinned by tests/test_ingest.py).
            train_loader.shutdown()
    emit(f"Training Time: {seconds:.3f} sec")
    return FitResult(
        state=state, train_seconds=seconds, history=history,
        resumed_step=resumed_step,
    )


def _timed_batches(batches: Iterable):
    """``batches``' items, each pulled inside a ``train.data_wait`` span:
    the time the loop waited for its loader, one span a step. The pull
    that finds the loader exhausted says so (``exhausted``) and is no
    step's wait."""
    it = iter(batches)
    while True:
        with annotate("train.data_wait") as wait:
            try:
                batch = next(it)
            except StopIteration:
                wait.set(exhausted=True)
                return
        yield batch


def _run_epochs(
    state, step_fn, train_loader, epochs, rng, mesh, log_every, emit,
    tracer, checkpointer, checkpoint_every, span_timer, sink=None,
    sync_check_every=0, multi_fn=None, steps_per_call=1,
    prefetch_to_device=0, start_epoch=0, start_step=0,
):
    from machine_learning_apache_spark_tpu.parallel.mesh import (
        device_prefetch,
        shard_batch_stack,
    )
    from machine_learning_apache_spark_tpu.utils.faults import maybe_fault

    # Device prefetch applies to the single-step path: sharded transfers
    # are issued N batches ahead so they overlap compute. The scanned path
    # stacks its own groups (and one dispatch already buys K step-times of
    # host slack), so it keeps raw batches.
    use_prefetch = (
        prefetch_to_device > 0 and mesh is not None and multi_fn is None
    )
    # A streaming pipeline with an active device stage delivers batches
    # already placed (device_put, or mesh-sharded when fit bound a mesh);
    # the single-step path must not re-shard them.
    pipeline_device = getattr(train_loader, "yields_device_batches", False)

    history: list[dict] = []
    # On resume the step counter continues from the restored checkpoint, so
    # step-pinned coordinates (profiler windows, injected faults, log lines)
    # mean the same thing in a resumed run as in an uninterrupted one.
    global_step = start_step
    last_emit_step = global_step
    for epoch in range(start_epoch, epochs):
        # Manual enter/exit (not a with-block) keeps the 130-line epoch body
        # at its indent. On an exception the span_end is skipped — the step
        # span and fit span still close errored, and _Span.__exit__ pops
        # leaked ids, so parent attribution stays correct.
        epoch_span = telemetry.span("train.epoch", epoch=epoch)
        epoch_span.__enter__()
        # Refresh the liveness beacon once per epoch: heartbeat payloads
        # and /healthz report phase + step without touching the hot loop.
        telemetry.beacon_update(
            phase="train", epoch=epoch, step=global_step
        )
        if hasattr(train_loader, "set_epoch"):
            train_loader.set_epoch(epoch)
        epoch_metrics = MetricBundle()
        # Step outputs stay on-device until a log point — float()ing per step
        # would sync the host into every step and serialize async dispatch.
        # Entries are (mean_loss, mean_aux, n_steps): n_steps > 1 for a
        # scanned multi-step dispatch, keeping epoch means weight-exact.
        pending: list[tuple] = []

        def _drain():
            for dev_loss, dev_aux, n in jax.device_get(pending):
                epoch_metrics.mean("loss").update(dev_loss, n)
                for k, v in dev_aux.items():
                    epoch_metrics.mean(k).update(v, n)
            pending.clear()

        def _log_point(prev_step):
            # Stride-aware: emit when the counter crossed a log_every
            # boundary this dispatch (multi-step strides can jump past the
            # exact multiple).
            return log_every and (
                global_step // log_every > prev_step // log_every
            )

        def _emit_log():
            # The lap spans however many batches actually ran since the last
            # emit — with a multi-step stride that need not equal log_every
            # (one K-step dispatch can cross several boundaries), so report
            # the real count.
            nonlocal last_emit_step
            covered = global_step - last_emit_step
            last_emit_step = global_step
            # Log cadence doubles as beacon cadence: step stays fresh on
            # /healthz and in heartbeat payloads at zero hot-loop cost.
            telemetry.beacon_update(phase="train", step=global_step)
            _drain()
            emit(
                f"epoch {epoch} step {global_step} | "
                f"{epoch_metrics.log_line()} | {span_timer.lap():.3f} sec/{covered} batches"
            )

        group: list = []

        def _flush_group():
            nonlocal state, rng, global_step
            stacked = (
                shard_batch_stack(mesh, group)
                if mesh is not None
                else jax.tree.map(lambda *xs: jnp.stack(xs), *group)
            )
            tracer.on_step(global_step)
            prev = global_step
            with annotate(
                "train.step_group", start=prev, count=len(group)
            ):
                # The scanned dispatch covers steps [prev, prev+K): check
                # every coordinate in the span so a step-pinned fault fires
                # regardless of steps_per_call (at group granularity — the
                # whole group is lost, which is within the
                # <=1-checkpoint-interval guarantee).
                for s in range(prev, prev + len(group)):
                    maybe_fault("train_step", step=s)
                state, rng, losses, auxes = multi_fn(state, stacked, rng)
            global_step += len(group)
            pending.append((
                losses.mean(),
                jax.tree.map(lambda v: v.mean(), auxes),
                len(group),
            ))
            group.clear()
            if _log_point(prev):
                _emit_log()

        def _single_step(batch, presharded=False):
            nonlocal state, rng, global_step
            if mesh is not None and not presharded:
                batch = shard_batch(mesh, batch)
            rng, step_rng = jax.random.split(rng)
            tracer.on_step(global_step)
            with annotate("train.step", step=global_step):
                maybe_fault("train_step", step=global_step)
                state, loss, aux = step_fn(state, batch, step_rng)
            global_step += 1
            pending.append((loss, aux, 1))
            if _log_point(global_step - 1):
                _emit_log()

        epoch_iter = (
            device_prefetch(train_loader, mesh, depth=prefetch_to_device)
            if use_prefetch
            else train_loader
        )
        for batch in _timed_batches(epoch_iter):
            if multi_fn is not None:
                group.append(batch)
                if len(group) == steps_per_call:
                    _flush_group()
            else:
                _single_step(batch, presharded=use_prefetch or pipeline_device)
        # Ragged trailing group: fewer than steps_per_call batches left in
        # the epoch — run them as single steps (a scan over a shorter stack
        # would force a recompile per distinct remainder length).
        for batch in group:
            _single_step(batch)
        group.clear()
        _drain()
        computed = epoch_metrics.compute()
        computed["epoch"] = epoch
        history.append(computed)
        if sink is not None:
            # state.step (not the run-local counter): stays consistent with
            # checkpoint labels across resumed runs.
            sink.write({"kind": "epoch", "step": int(state.step), **computed})
        if log_every:
            emit(f"epoch {epoch} done | {epoch_metrics.log_line()}")
        if sync_check_every and (epoch + 1) % sync_check_every == 0:
            # BEFORE the checkpoint save: a diverged state must raise here,
            # not get persisted as the latest resumable checkpoint first.
            from machine_learning_apache_spark_tpu.parallel import (
                assert_replicas_in_sync,
            )

            div = assert_replicas_in_sync(state.params)
            emit(f"epoch {epoch} replica divergence: {div:.3g}")
        if checkpointer is not None and (
            (epoch + 1) % max(checkpoint_every, 1) == 0 or epoch == epochs - 1
        ):
            # Async: orbax snapshots to host and writes in the background, so
            # checkpoint I/O never stalls device dispatch mid-training. The
            # sidecar meta carries the epoch counter and the post-epoch rng
            # key so fit(resume=True) continues the exact trajectory.
            meta = {
                "epoch": epoch,
                "rng": _rng_to_meta(rng),
                # JSON-safe copy of this epoch's metrics, so an
                # already-complete resume can still report them.
                "metrics": {
                    k: (v if isinstance(v, int) else float(v))
                    for k, v in computed.items()
                },
            }
            if getattr(train_loader, "is_streaming_pipeline", False):
                # Stream cursor + sampler RNG next to the rng key: the
                # epoch boundary is a quiescent point (the producer thread
                # has finished the epoch), so this capture is exact.
                meta["ingest"] = train_loader.state_dict()
            checkpointer.save(state, wait=False, meta=meta)
        epoch_span.__exit__(None, None, None)
    return state, history


def evaluate(
    state: TrainState,
    loss_fn: LossFn,
    eval_loader: Iterable,
    *,
    mesh=None,
    rng: jax.Array | None = None,
    emit: Callable[[str], None] | None = None,
) -> dict:
    """Eval pass: accumulated loss + metrics — the reference's
    ``model.eval()`` + ``no_grad`` + accuracy block
    (``pytorch_cnn.py:154-176``). Deterministic (loss_fn receives a fixed
    key; dropout layers must run deterministic under it).

    Consumes the WHOLE loader, matching the reference: a ragged tail batch
    (``drop_last=False`` loaders) that does not divide the mesh's data axis
    runs unsharded on the default device — one extra compile, zero skipped
    rows. Per-batch metrics are weighted by the real row count, and the
    total is returned as ``eval_samples`` so callers can assert full
    coverage. Exception: under a multi-process gang a ragged local tail
    cannot be assembled into a global array for the sharded step, so it is
    skipped with a warning (the single-controller boundary; every
    single-process path keeps full coverage).
    """
    from machine_learning_apache_spark_tpu.ops.attention import kernel_mesh
    from machine_learning_apache_spark_tpu.parallel.mesh import DATA_AXIS

    emit = emit or log.info
    rng = rng if rng is not None else jax.random.key(0)
    step_fn = make_eval_step(loss_fn)
    metrics = MetricBundle()
    # Divisibility is judged against the LOCAL device count: each process
    # contributes its local rows (shard_batch assembles the global array).
    local_size = (
        mesh.shape[DATA_AXIS] // jax.process_count() if mesh is not None else 1
    )
    total = 0
    # Pallas launches in the step trace per shard of the mesh.
    with kernel_mesh(mesh):
        for batch in eval_loader:
            n = len(jax.tree.leaves(batch)[0])
            if mesh is not None and n % local_size == 0:
                batch = shard_batch(mesh, batch)
            elif mesh is not None and jax.process_count() > 1:
                log.warning(
                    "skipping %d-row ragged eval tail: a process-local tail "
                    "cannot join the sharded step (%d local devices)",
                    n, local_size,
                )
                continue
            loss, aux = step_fn(state, batch, rng)
            total += n
            metrics.mean("test_loss").update(loss, n)
            for k, v in aux.items():
                metrics.mean(k).update(v, n)
    out = metrics.compute()
    emit(" | ".join(f"{k}: {v:.5f}" for k, v in out.items()))
    out["eval_samples"] = total
    return out


def select_last_valid(
    logits: jnp.ndarray, tokens: jnp.ndarray, pad_id: int
) -> jnp.ndarray:
    """``[B, T, C]`` logits → ``[B, C]`` at each row's last non-pad
    position (all-pad rows fall back to position 0). Training loss and
    serving (``inference.Classifier``) MUST select through this one helper
    — scoring a different timestep than the loss trained silently degrades
    every deployed last-valid classifier."""
    idx = jnp.maximum((tokens != pad_id).sum(axis=-1) - 1, 0)
    return jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0, :]


def classification_loss(
    apply_fn, *, last_timestep: bool = False, train: bool = True,
    pad_id: int | None = None,
) -> LossFn:
    """Standard CE classification loss over ``(features, labels)`` batches.

    ``last_timestep=True`` selects ``logits[:, -1, :]`` — the LSTM recipe's
    last-position head (``pytorch_lstm.py:160``). With ``pad_id`` set, the
    selection becomes each row's last NON-PAD position instead of the fixed
    final column — the correct-semantics variant of the reference's
    last-position read, which on end-padded batches scores the hidden state
    after up to ``fixed_len − len(row)`` pad steps (state the recurrence
    must carry through constant inputs; a learning-speed tax the reference
    pays silently). ``train=True`` runs dropout (``model.train()``); pass
    ``train=False`` for the eval pass (``model.eval()`` + ``no_grad``,
    ``pytorch_cnn.py:154-176``).
    """
    from machine_learning_apache_spark_tpu.train.losses import cross_entropy

    def loss_fn(params, batch, rng):
        features, labels = batch
        logits = apply_fn(
            {"params": params},
            features,
            deterministic=not train,
            rngs={"dropout": rng} if train else None,
        )
        if last_timestep:
            if pad_id is not None:
                logits = select_last_valid(logits, features, pad_id)
            else:
                logits = logits[:, -1, :]
        loss = cross_entropy(logits, labels)
        return loss, {"accuracy": logits_accuracy(logits, labels)}

    return loss_fn

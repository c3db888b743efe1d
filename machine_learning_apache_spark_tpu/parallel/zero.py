"""ZeRO-1 sharded weight update for the data-parallel path.

``make_data_parallel_step`` replicates everything: every chip holds the
full params *and* the full optimizer moments and pays a full-gradient
allreduce per step. "Automatic Cross-Replica Sharding of Weight Update
in Data-Parallel Training" (arxiv 2004.13336, PAPERS.md) observes the
allreduce is a reduce-scatter + allgather in disguise, and the weight
update between the two halves only ever needs 1/N of the gradient — so
each chip can own 1/N of the parameters for update purposes and the
moments shrink by N with bit-equal convergence semantics:

    reduce_scatter(grads) -> tx.update on this chip's shard -> allgather(params)

This module is the explicit fused form of that rewrite (the implicit
form — ``tensor_parallel.shard_state(zero1=True)``, XLA propagation —
predates it and stays supported as ``fit(zero1=True)``):

- gradients are flattened into one fp32 vector and cut into **buckets**
  (``bucket_bytes``; DDP's bucketing, SURVEY.md §2.2) so the
  reduce-scatter pipelines instead of waiting for the full gradient; the
  ragged tail is zero-padded inside the fused step, never on the host;
- each bucket optionally travels in a compressed ``comms_dtype`` —
  ``bfloat16``, or ``int8`` with a per-bucket scale chosen so the N-way
  sum cannot overflow (EQuARX, arxiv 2506.17615) — while params and
  moments accumulate in fp32 (master copies);
- the optimizer state is built **sharded from the start**
  (``jit(out_shardings=...)`` over ``tx.init``): the replicated moments
  never exist, so peak per-chip optimizer memory is ~1/N from step 0;
- with ``overlap=True`` (the default; ``MLSPARK_ZERO1_OVERLAP``) the
  buckets form a **pipeline instead of a barrier**: each bucket's
  gradient segment is assembled straight from the grad leaves it spans
  (no full-vector concat first) and its ``psum_scatter`` is issued in
  reverse bucket order — the order backward produces gradients — so the
  reduce-scatter of bucket k overlaps the still-running backward of
  earlier layers; on the tail, the optimizer update runs **per bucket**
  and each bucket's params ``all_gather`` is issued immediately, so the
  gather of bucket k hides behind the update of bucket k+1. The pipeline
  is elementwise-identical to the serial schedule, so fp32 overlap mode
  is bit-identical to overlap-off (the equivalence gate pins it).

Hybrid data x model meshes: ``make_zero1_step`` composes with tensor
parallelism on 2-D ``data x model`` meshes (veScale, arxiv 2509.07003:
the sharded-update spec is orthogonal to TP). On a hybrid mesh the step
switches from the explicit ``shard_map`` program to the *implicit* form
of the same rewrite — params keep their TP placement
(``tensor_parallel`` logical rules), the flat fp32 master/optimizer
vector is sharded over ``(data, model)`` jointly (so moments shrink by
the full device count), and ``with_sharding_constraint`` pins the
layouts while XLA's weight-update sharding compiles the
reduce-scatter/allgather pair and schedules its own overlap. The
implicit form cannot *place* the collectives itself, but it can bound
what crosses the wire: with ``comms_dtype`` bf16/int8 the hybrid step
quantize-dequantizes each gradient bucket (per-bucket absmax scale for
int8 — the same EQuARX-style machinery as the explicit path) *before*
the sharded update, so whatever reduce-scatter XLA schedules moves
bf16/int8-precision values while master weights, moments, and the
all-gathered params stay fp32. Gradient parity vs the fp32 wire is
bounded by the QDQ rounding alone (tests/test_zero.py pins both the
fp32 equivalence gate and the compressed-wire tolerance).

Shard layout (explicit path): device ``i`` owns the ``i``-th 1/N slice
of *every bucket* (what ``psum_scatter`` hands it), concatenated. The
flat optimizer-state leaves live in that bucket-major order; it is
internally consistent across init/update/checkpoint and no caller reads
them elementwise.

Limitations (documented, checked where cheap): the optimizer chain must
be elementwise per-parameter (sgd/adam/adamw + schedules are; a
``clip_by_global_norm`` INSIDE ``tx`` would clip by the shard-local norm
— pass ``grad_clip=`` here instead, which clips by the true global norm
via a scalar psum); ``steps_per_call`` fusion and MultiSteps-style
cross-step state are out of scope for the fused step.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from machine_learning_apache_spark_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from machine_learning_apache_spark_tpu.train.state import UPDATE_SCOPE
from machine_learning_apache_spark_tpu.utils import env as envcfg

# Environment contract (launcher gang plumbing: the driver sets these on
# the Distributor, workers' fit() picks them up — docs/PARALLELISM.md).
ENV_DP_MODE = "MLSPARK_DP_MODE"
ENV_BUCKET_BYTES = "MLSPARK_ZERO1_BUCKET_BYTES"
ENV_COMMS_DTYPE = "MLSPARK_COMMS_DTYPE"
ENV_OVERLAP = "MLSPARK_ZERO1_OVERLAP"

DP_MODES = ("replicated", "zero1")
COMMS_DTYPES = ("float32", "bfloat16", "int8")

#: DDP's default bucket is 25 MB; the models here are far smaller, and a
#: 4 MiB bucket already gives the reduce-scatter several pipeline stages
#: on every workload in the repo.
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024

_WIRE_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}


def resolve_dp_mode(dp_mode: str | None) -> str:
    """Explicit argument > ``MLSPARK_DP_MODE`` env > ``"replicated"``."""
    # raw() rather than get_str(): the registry's choices check would raise
    # before this guard, and callers rely on the dp_mode-named message below.
    mode = dp_mode or envcfg.raw(ENV_DP_MODE) or "replicated"
    if mode not in DP_MODES:
        raise ValueError(f"unknown dp_mode {mode!r} (expected one of {DP_MODES})")
    return mode


def _parse_bool(raw: str, *, env: str) -> bool:
    lowered = raw.strip().lower()
    if lowered in ("1", "true", "on", "yes"):
        return True
    if lowered in ("0", "false", "off", "no"):
        return False
    raise ValueError(f"{env}={raw!r} is not a boolean (use 1/0/true/false/on/off)")


@dataclasses.dataclass(frozen=True)
class Zero1Config:
    """Comms-efficiency knobs for the fused ZeRO-1 step.

    ``overlap`` selects the pipelined bucket schedule (reduce-scatter
    issued per bucket in backward order, per-bucket update + eager
    allgather on the tail) instead of the serial
    flatten -> reduce-scatter-all -> update -> allgather-all barrier.
    Both schedules are elementwise-identical; overlap only changes what
    the XLA latency-hiding scheduler is *allowed* to run concurrently.
    """

    axis: str = DATA_AXIS
    bucket_bytes: int = DEFAULT_BUCKET_BYTES
    comms_dtype: str = "float32"
    overlap: bool = True

    def __post_init__(self) -> None:
        if self.comms_dtype not in COMMS_DTYPES:
            raise ValueError(
                f"unknown comms_dtype {self.comms_dtype!r} "
                f"(expected one of {COMMS_DTYPES})"
            )
        if self.bucket_bytes < 4:
            raise ValueError(
                f"bucket_bytes must hold at least one fp32 element, "
                f"got {self.bucket_bytes}"
            )

    @classmethod
    def from_env(
        cls,
        *,
        axis: str = DATA_AXIS,
        bucket_bytes: int | None = None,
        comms_dtype: str | None = None,
        overlap: bool | None = None,
    ) -> "Zero1Config":
        """Explicit arguments win; unset ones fall back to the launcher
        env contract, then to defaults."""
        if bucket_bytes is None:
            bucket_bytes = envcfg.get_int(ENV_BUCKET_BYTES, DEFAULT_BUCKET_BYTES)
        if comms_dtype is None:
            comms_dtype = envcfg.get_str(ENV_COMMS_DTYPE)
        if overlap is None:
            raw = envcfg.raw(ENV_OVERLAP)
            overlap = True if raw is None else _parse_bool(raw, env=ENV_OVERLAP)
        return cls(
            axis=axis,
            bucket_bytes=bucket_bytes,
            comms_dtype=comms_dtype,
            overlap=overlap,
        )


@dataclasses.dataclass(frozen=True)
class _FlatPlan:
    """Static description of the params-tree <-> flat-fp32-vector mapping.

    Buckets partition ``[0, padded)``; every bucket length (and therefore
    ``padded``) is a multiple of the axis size, so ``psum_scatter`` tiles
    each bucket evenly and the zero pad lives entirely in the last bucket.
    """

    treedef: Any
    shapes: tuple
    dtypes: tuple
    sizes: tuple
    total: int
    padded: int
    shard_len: int
    buckets: tuple  # ((start, stop), ...) in flat padded coordinates


def make_flat_plan(params, axis_size: int, bucket_bytes: int) -> _FlatPlan:
    leaves, treedef = jax.tree.flatten(params)
    if not leaves:
        raise ValueError("cannot build a ZeRO-1 plan for an empty params tree")
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    sizes = tuple(int(l.size) for l in leaves)
    total = sum(sizes)
    # Bucket element counts are fp32-denominated (the master accumulation
    # dtype) and rounded up to a multiple of the axis size so every
    # bucket reduce-scatters evenly.
    elems = max(bucket_bytes // 4, 1)
    elems = -(-elems // axis_size) * axis_size
    padded = -(-total // axis_size) * axis_size
    buckets = tuple(
        (start, min(start + elems, padded)) for start in range(0, padded, elems)
    )
    return _FlatPlan(
        treedef=treedef,
        shapes=shapes,
        dtypes=dtypes,
        sizes=sizes,
        total=total,
        padded=padded,
        shard_len=padded // axis_size,
        buckets=buckets,
    )


def _flatten(tree, plan: _FlatPlan, constrain=None):
    """Params/grads tree -> one fp32 vector of length ``plan.padded``.

    ``constrain`` (a ``NamedSharding``) pins every raveled leaf to one
    common sharding before the concat. The hybrid path needs this for
    *correctness*, not placement: on jax 0.4.37/CPU, ``jnp.concatenate``
    over 1-D operands that carry different input shardings (a mix of
    TP-sharded and replicated leaves) miscompiles and returns permuted
    data — the SPMD partitioner's "involuntary full rematerialization"
    path. Constraining the operands to one sharding sidesteps it.
    """
    leaves = jax.tree.leaves(tree)
    raveled = [jnp.ravel(l).astype(jnp.float32) for l in leaves]
    if constrain is not None:
        raveled = [
            jax.lax.with_sharding_constraint(r, constrain) for r in raveled
        ]
    flat = raveled[0] if len(raveled) == 1 else jnp.concatenate(raveled)
    if plan.padded > plan.total:
        flat = jnp.pad(flat, (0, plan.padded - plan.total))
    return flat


def _bucket_segment(leaves, plan: _FlatPlan, k: int):
    """Bucket ``k``'s fp32 segment assembled straight from the leaves it
    spans — the overlap path's replacement for ``_flatten`` + slice.

    Built this way, the segment's data dependencies are exactly the grad
    leaves inside the bucket, so its ``psum_scatter`` becomes eligible
    the moment backward has produced *those* gradients; a full-vector
    concat would make every bucket wait for the whole backward. The zero
    pad always lives in the last bucket (``make_flat_plan`` guarantees
    it), appended here explicitly.
    """
    s, e = plan.buckets[k]
    parts = []
    offset = 0
    for leaf, size in zip(leaves, plan.sizes):
        lo, hi = max(s, offset), min(e, offset + size)
        if lo < hi:
            parts.append(
                jnp.ravel(leaf)[lo - offset:hi - offset].astype(jnp.float32)
            )
        offset += size
    if e > plan.total:
        parts.append(jnp.zeros((e - max(s, plan.total),), jnp.float32))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _unflatten(flat, plan: _FlatPlan):
    """Inverse of ``_flatten``: slice, reshape, and restore leaf dtypes."""
    leaves = []
    offset = 0
    for shape, dtype, size in zip(plan.shapes, plan.dtypes, plan.sizes):
        leaves.append(
            flat[offset:offset + size].reshape(shape).astype(dtype)
        )
        offset += size
    return jax.tree.unflatten(plan.treedef, leaves)


def _opt_spec_tree(opt_shapes, axes):
    """PartitionSpecs for an optimizer state built over the flat vector:
    vector-shaped leaves shard over ``axes`` (one mesh axis name, or a
    tuple of names for the hybrid joint sharding), scalars (step counts)
    replicate."""
    return jax.tree.map(
        lambda l: P(axes) if getattr(l, "ndim", 0) >= 1 else P(), opt_shapes
    )


def _reduce_scatter_bucket(seg, axis: str, axis_size: int, comms_dtype: str):
    """One bucket's gradient reduce-scatter in the configured wire dtype.

    fp32: exact. bf16: cast-reduce-cast (lossy mantissa, fp32 master state
    untouched). int8: per-bucket scale chosen as ``pmax(|seg|) * N / 127``
    so each shard contributes at most 127/N — the N-way integer sum can
    never overflow int8 (the EQuARX trick, minus their block granularity).
    """
    if comms_dtype == "float32":
        return jax.lax.psum_scatter(
            seg, axis, scatter_dimension=0, tiled=True
        )
    if comms_dtype == "bfloat16":
        piece = jax.lax.psum_scatter(
            seg.astype(jnp.bfloat16), axis, scatter_dimension=0, tiled=True
        )
        return piece.astype(jnp.float32)
    # int8 with per-bucket scale.
    absmax = jax.lax.pmax(jnp.max(jnp.abs(seg)), axis)
    scale = jnp.maximum(absmax * axis_size / 127.0, jnp.float32(1e-30))
    q = jnp.clip(jnp.round(seg / scale), -127, 127).astype(jnp.int8)
    piece = jax.lax.psum_scatter(q, axis, scatter_dimension=0, tiled=True)
    return piece.astype(jnp.float32) * scale


def comms_bytes_per_step(plan: _FlatPlan, config: Zero1Config) -> dict:
    """Static wire accounting for one fused step (what the telemetry
    counters report): reduce-scatter payload in the wire dtype (+4 bytes
    per int8 bucket for the scale), allgather of the updated fp32 params.

    The exposed/overlapped split is the static pipeline model, not a
    measurement: with ``overlap=True`` and ``nb`` buckets, the pipeline
    can hide every bucket's collective behind another bucket's compute
    except the first reduce-scatter fill and the last allgather drain —
    so ``(nb - 1) / nb`` of each collective's bytes count as overlapped
    and ``1 / nb`` stays exposed. With ``overlap=False`` the schedule is
    a barrier and every byte is exposed. ``tools/comms_bench.py`` turns
    this into an exposed-collective-*time* estimate by scaling measured
    standalone collective times with these fractions.
    """
    wire = _WIRE_ITEMSIZE[config.comms_dtype]
    rs = plan.padded * wire
    if config.comms_dtype == "int8":
        rs += 4 * len(plan.buckets)
    ag = plan.padded * 4
    nb = len(plan.buckets)
    hidden_frac = (nb - 1) / nb if config.overlap else 0.0
    rs_hidden = int(rs * hidden_frac)
    ag_hidden = int(ag * hidden_frac)
    return {
        "reduce_scatter_bytes": rs,
        "allgather_bytes": ag,
        "grad_bytes_fp32": plan.padded * 4,
        "n_buckets": nb,
        "bucket_bytes": config.bucket_bytes,
        "comms_dtype": config.comms_dtype,
        "padded_elems": plan.padded,
        "pad_elems": plan.padded - plan.total,
        "overlap": config.overlap,
        "hidden_fraction": hidden_frac,
        "bytes_overlapped": rs_hidden + ag_hidden,
        "bytes_exposed": (rs - rs_hidden) + (ag - ag_hidden),
    }


class Zero1State(struct.PyTreeNode):
    """TrainState analogue for the fused ZeRO-1 step: params replicated,
    optimizer state flat (fp32, bucket-major shard layout) and sharded
    1/N over the data axis. Same field names as ``TrainState`` where the
    semantics coincide, so ``fit``/checkpointing address both uniformly.
    """

    step: jax.Array | int
    params: Any
    opt_state: Any
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    plan: _FlatPlan = struct.field(pytree_node=False)
    config: Zero1Config = struct.field(pytree_node=False)


def _require_zero1_mesh(mesh: Mesh, axis: str) -> tuple[int, int]:
    """Validate the mesh for ``dp_mode='zero1'`` and classify its layout.

    Returns ``(axis_size, model_ways)``: ``model_ways > 1`` means the
    hybrid data x model composition (implicit sharded-update step over a
    TP mesh); ``model_ways == 1`` is the pure data-parallel explicit
    ``shard_map`` path. Any other >1 axis (pipeline, seq, expert) is a
    genuinely unsupported layout for the sharded weight update — those
    axes split the *step*, not just the placement — and raises.
    """
    if axis not in mesh.axis_names:
        raise ValueError(
            f"zero1 needs a mesh with a {axis!r} axis; got {mesh.axis_names}"
        )
    axis_size = mesh.shape[axis]
    if axis_size <= 1:
        raise ValueError(
            f"zero1 needs a >1 {axis!r} axis to shard over; got {axis_size} "
            f"(mesh {dict(mesh.shape)})"
        )
    model_ways = mesh.shape.get(MODEL_AXIS, 1)
    other = {
        a: s
        for a, s in mesh.shape.items()
        if a not in (axis, MODEL_AXIS) and s > 1
    }
    if other:
        raise ValueError(
            "dp_mode='zero1' shards the weight update over the data axis "
            "and composes only with tensor parallelism on the 'model' "
            f"axis; mesh has extra >1 axes {other}. Pipeline/sequence/"
            "expert axes restructure the step itself — use the dedicated "
            "paths (parallel.pipeline_parallel, ring/ulysses attention, "
            "moe) on meshes without a zero1 data axis."
        )
    return axis_size, model_ways


def init_sharded(
    *,
    apply_fn: Callable,
    params,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    config: Zero1Config | None = None,
) -> Zero1State:
    """Build a ``Zero1State`` whose optimizer state is sharded from the
    start: ``tx.init`` runs under ``jit(out_shardings=1/N)`` over the flat
    fp32 vector, so XLA materializes each moment directly as N shards —
    the replicated copy never exists on any chip.

    Pure data mesh: params are placed replicated (ZeRO-1 keeps
    whole-replica params) and moments shard 1/N over the data axis.
    Hybrid data x model mesh: params are placed per their logical TP
    annotations (``tensor_parallel.shard_params`` — plain/unannotated
    leaves stay replicated) and the flat moments shard jointly over
    ``(data, model)``, so the optimizer footprint shrinks by the *full*
    device count, not just the data ways.
    """
    config = config or Zero1Config()
    axis_size, model_ways = _require_zero1_mesh(mesh, config.axis)
    hybrid = model_ways > 1
    import flax.linen as nn

    if hybrid:
        # Place params per their logical TP annotations (specs read off
        # the boxed tree; plain leaves replicate), dropping any sharded
        # dim the leaf cannot fill evenly — same policy as shard_state.
        from machine_learning_apache_spark_tpu.parallel import (
            tensor_parallel as _tp,
        )

        shardings_tree = _tp.mesh_shardings(params, mesh)
        params = nn.unbox(params)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, _tp._divisible_sharding(s, x)),
            params,
            shardings_tree,
        )
    else:
        params = nn.unbox(params)
    # The flat vector must tile evenly over every device that holds a
    # piece of it: N for the explicit path, N x TP for the hybrid joint
    # sharding. (The plan's treedef is over the unboxed tree — what the
    # step sees.)
    plan = make_flat_plan(params, axis_size * model_ways, config.bucket_bytes)

    opt_axes = (config.axis, MODEL_AXIS) if hybrid else config.axis
    flat_spec = jax.ShapeDtypeStruct((plan.padded,), jnp.float32)
    opt_shapes = jax.eval_shape(tx.init, flat_spec)
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        _opt_spec_tree(opt_shapes, opt_axes),
    )

    @functools.partial(jax.jit, out_shardings=shardings)
    def _init():
        return tx.init(jnp.zeros((plan.padded,), jnp.float32))

    if not hybrid:
        params = jax.device_put(params, NamedSharding(mesh, P()))
    return Zero1State(
        step=0,
        params=params,
        opt_state=_init(),
        apply_fn=apply_fn,
        tx=tx,
        plan=plan,
        config=config,
    )


def shard_optimizer_state(
    state, mesh: Mesh, config: Zero1Config | None = None
) -> Zero1State:
    """``TrainState -> Zero1State`` entry point for ``fit(dp_mode="zero1")``.

    The optimizer state is re-initialized sharded (``init_sharded``), not
    migrated: for a fresh ``TrainState.create`` the moments are zeros in
    both layouts, so this is lossless; converting a mid-run state would
    silently reset its moments, so that raises.
    """
    if isinstance(state, Zero1State):
        return state
    if int(jax.device_get(state.step)) != 0:
        raise ValueError(
            "shard_optimizer_state re-initializes the optimizer moments "
            f"(sharded from the start); converting a mid-run state at step "
            f"{int(jax.device_get(state.step))} would silently discard them. "
            "Start zero1 runs from a fresh state (resume restores into the "
            "sharded layout afterwards)."
        )
    return init_sharded(
        apply_fn=state.apply_fn, params=state.params, tx=state.tx,
        mesh=mesh, config=config,
    )


def make_zero1_step(
    loss_fn: Callable,
    mesh: Mesh,
    state: Zero1State,
    *,
    grad_clip: float | None = None,
):
    """Fused ZeRO-1 train step: reduce-scatter(grads) -> 1/N optimizer
    update -> allgather(params), one compiled program.

    Same calling convention as ``make_data_parallel_step``'s result —
    ``step(state, batch, rng) -> (state, loss, aux)`` with ``state``
    donated — but the state must be a ``Zero1State`` (``init_sharded`` /
    ``shard_optimizer_state``); the step specializes to its flat plan,
    optimizer, and comms config at construction. Per-shard loss/grad
    math is identical to the replicated step (same ``fold_in`` rng
    decorrelation, same ``loss / N`` scaling), so with
    ``comms_dtype="float32"`` the two modes walk the same trajectory
    (tests/test_zero.py pins it).

    ``grad_clip`` applies optax's ``clip_by_global_norm`` rule using the
    TRUE global norm (shard-local sum of squares psummed over the axis) —
    the one cross-parameter coupling the sharded update cannot express
    inside ``tx`` itself.

    The returned step carries ``step.comms_stats`` (static wire-byte
    accounting per step) for the telemetry counters.
    """
    if not isinstance(state, Zero1State):
        raise TypeError(
            "make_zero1_step needs a Zero1State (init_sharded / "
            f"shard_optimizer_state), got {type(state).__name__}"
        )
    config = state.config
    plan = state.plan
    tx = state.tx
    axis = config.axis
    axis_size, model_ways = _require_zero1_mesh(mesh, axis)
    if plan.padded % (axis_size * model_ways):
        raise ValueError(
            f"state plan (padded={plan.padded}) does not divide the mesh's "
            f"{axis!r} x model layout ({axis_size} x {model_ways}); the "
            "state was built for a different mesh"
        )
    if model_ways > 1:
        step = _make_hybrid_step(loss_fn, mesh, state, grad_clip)
        step.comms_stats = comms_bytes_per_step(plan, config)
        return step

    def grads_and_loss(params, batch, rng):
        idx = jax.lax.axis_index(axis)
        rng = jax.random.fold_in(rng, idx)

        def scaled_loss(p):
            loss, aux = loss_fn(p, batch, rng)
            return loss / axis_size, (loss, aux)

        (_, (loss, aux)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True
        )(params)
        loss = jax.lax.pmean(loss, axis)
        aux = jax.tree.map(lambda x: jax.lax.pmean(x, axis), aux)
        return idx, grads, loss, aux

    def per_shard_serial(params, opt_state, batch, rng):
        """Barrier schedule: flatten everything, reduce-scatter every
        bucket, one optimizer update, allgather every bucket. The
        overlap path's bit-identity reference."""
        idx, grads, loss, aux = grads_and_loss(params, batch, rng)

        # Bucketed reduce-scatter: after this, this chip holds the
        # global-mean gradient for its 1/N slice of every bucket.
        flat_g = _flatten(grads, plan)
        g_pieces = [
            _reduce_scatter_bucket(
                flat_g[s:e], axis, axis_size, config.comms_dtype
            )
            for s, e in plan.buckets
        ]
        g_shard = jnp.concatenate(g_pieces)

        if grad_clip is not None:
            # Shard pieces tile the padded vector exactly once, so the
            # psum of local sums-of-squares IS the global norm -- one
            # scalar collective, exactly optax.clip_by_global_norm.
            g_norm = jnp.sqrt(
                jax.lax.psum(jnp.sum(jnp.square(g_shard)), axis)
            )
            scale = jnp.where(g_norm < grad_clip, 1.0, grad_clip / g_norm)
            g_shard = g_shard * scale

        # This chip's matching param shard (same bucket-major layout).
        flat_p = _flatten(params, plan)
        p_pieces = [
            jax.lax.dynamic_slice_in_dim(
                flat_p,
                s + idx * ((e - s) // axis_size),
                (e - s) // axis_size,
            )
            for s, e in plan.buckets
        ]
        p_shard = jnp.concatenate(p_pieces)

        with jax.named_scope(UPDATE_SCOPE):
            updates, new_opt = tx.update(g_shard, opt_state, p_shard)
            new_p_shard = optax.apply_updates(p_shard, updates)

        # Allgather per bucket piece: tiled gather in device order
        # reconstructs each bucket segment contiguously.
        new_segments = []
        offset = 0
        for s, e in plan.buckets:
            piece_len = (e - s) // axis_size
            piece = new_p_shard[offset:offset + piece_len]
            offset += piece_len
            new_segments.append(
                jax.lax.all_gather(piece, axis, tiled=True)
            )
        flat_new = jnp.concatenate(new_segments)
        return _unflatten(flat_new, plan), new_opt, loss, aux

    def per_shard_overlap(params, opt_state, batch, rng):
        """Pipelined schedule. Same elementwise math as the serial body
        — every difference is dependency structure:

        - each bucket's gradient segment comes from ``_bucket_segment``
          (only the leaves it spans), and the ``psum_scatter``s are
          issued in *reverse* bucket order — backward emits last-layer
          gradients first and the flat plan is first-layer-first, so
          reverse order lets reduce-scatter of bucket k start while
          backward for earlier layers is still running;
        - the optimizer update runs per bucket on that bucket's slice of
          the flat moments, and each bucket's params ``all_gather`` is
          issued immediately after its update — so the gather of bucket
          k has no data dependency on the update of bucket k+1 and the
          latency-hiding scheduler can run them concurrently.

        Per-bucket slices of an elementwise optimizer chain update each
        element exactly as the full-vector call does (scalar counts
        increment identically in every bucket; the first bucket's copy
        is kept), so default fp32 overlap on/off walk bit-identical
        trajectories — the gate in tests/test_zero.py and the bench
        equivalence section both pin it. Compressed wire dtypes and
        ``grad_clip`` runs agree only to float tolerance (~1 ulp): their
        cross-element reductions (bucket absmax, global norm) compile to
        different reduction trees in the two schedules.
        """
        idx, grads, loss, aux = grads_and_loss(params, batch, rng)
        grad_leaves = jax.tree.leaves(grads)
        param_leaves = jax.tree.leaves(params)
        n_buckets = len(plan.buckets)

        g_pieces: list = [None] * n_buckets
        for k in reversed(range(n_buckets)):
            g_pieces[k] = _reduce_scatter_bucket(
                _bucket_segment(grad_leaves, plan, k),
                axis, axis_size, config.comms_dtype,
            )

        if grad_clip is not None:
            # Same reduction shape as the serial body (sum over the
            # concatenated shard) so clipped trajectories stay
            # bit-identical too. The norm is a true pipeline barrier —
            # cross-bucket coupling is what global-norm clipping means.
            g_shard = jnp.concatenate(g_pieces)
            g_norm = jnp.sqrt(
                jax.lax.psum(jnp.sum(jnp.square(g_shard)), axis)
            )
            scale = jnp.where(g_norm < grad_clip, 1.0, grad_clip / g_norm)
            g_pieces = [piece * scale for piece in g_pieces]

        new_opt_buckets = []
        gathered = []
        shard_offset = 0
        for k, (s, e) in enumerate(plan.buckets):
            piece_len = (e - s) // axis_size
            p_piece = jax.lax.dynamic_slice_in_dim(
                _bucket_segment(param_leaves, plan, k),
                idx * piece_len, piece_len,
            )
            opt_k = jax.tree.map(
                lambda l: (
                    l[shard_offset:shard_offset + piece_len]
                    if getattr(l, "ndim", 0) >= 1 else l
                ),
                opt_state,
            )
            with jax.named_scope(UPDATE_SCOPE):
                updates_k, new_opt_k = tx.update(g_pieces[k], opt_k, p_piece)
                new_piece = optax.apply_updates(p_piece, updates_k)
            gathered.append(jax.lax.all_gather(new_piece, axis, tiled=True))
            new_opt_buckets.append(new_opt_k)
            shard_offset += piece_len

        def recombine(*bucket_leaves):
            if getattr(bucket_leaves[0], "ndim", 0) >= 1:
                return jnp.concatenate(bucket_leaves)
            return bucket_leaves[0]

        new_opt = jax.tree.map(recombine, *new_opt_buckets)
        flat_new = jnp.concatenate(gathered)
        return _unflatten(flat_new, plan), new_opt, loss, aux

    per_shard = per_shard_overlap if config.overlap else per_shard_serial

    flat_spec = jax.ShapeDtypeStruct((plan.padded,), jnp.float32)
    opt_specs = _opt_spec_tree(jax.eval_shape(tx.init, flat_spec), axis)
    # check_vma=False, at this one call: (1) the sharded update needs each
    # shard's *local* gradient to feed ``psum_scatter`` -- under vma typing,
    # differentiating w.r.t. the replicated (``P()``) params psums the
    # cotangents first, which is the full allreduce this step exists to
    # avoid, and the scatter would then sum it a second time; (2) the new
    # params come out of ``all_gather(tiled=True)`` and loss/aux out of
    # ``pmean`` -- replicated by construction, but the public ``all_gather``
    # is typed varying, so the ``P()`` out_specs cannot be inferred. The
    # bit-identity gates in tests/test_zero.py check the replication claim.
    sharded = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(), opt_specs, P(axis), P()),
        out_specs=(P(), opt_specs, P(), P()),
        check_vma=False,
    )

    @functools.partial(jax.jit, donate_argnums=0)
    def _step(zstate: Zero1State, batch, rng: jax.Array):
        new_params, new_opt, loss, aux = sharded(
            zstate.params, zstate.opt_state, batch, rng
        )
        return (
            zstate.replace(
                step=zstate.step + 1, params=new_params, opt_state=new_opt
            ),
            loss,
            aux,
        )

    def step(zstate: Zero1State, batch, rng: jax.Array):
        return _step(zstate, batch, rng)

    step.comms_stats = comms_bytes_per_step(plan, config)
    return step


def _make_hybrid_step(
    loss_fn: Callable,
    mesh: Mesh,
    state: Zero1State,
    grad_clip: float | None,
):
    """The implicit sharded-update step for hybrid data x model meshes.

    ``shard_map`` cannot express this composition on the pinned jax
    (partial-manual mode — ``auto={'model'}`` — aborts in the SPMD
    partitioner), so the hybrid step is a plain ``jit`` program: params
    keep their TP placement, the flat fp32 master vector and optimizer
    moments are constrained to ``P((data, model))``, and XLA's weight
    update sharding compiles the reduce-scatter / shard-update /
    allgather sequence (arxiv 2004.13336's original formulation) and
    schedules its own comm/compute overlap.

    ``config.comms_dtype`` bf16/int8 bounds the gradient wire precision
    at the semantic level: each bucket of the flat gradient is
    quantize-dequantized (per-bucket absmax scale for int8, plain
    round-trip for bf16) *before* the sharded update, so the values any
    XLA-scheduled reduce-scatter moves carry at most the compressed
    dtype's information, while the fp32 master weights, moments, and the
    all-gathered params are untouched. Honest caveat: unlike the
    explicit ``shard_map`` path, this does not force the physical
    collective to ship 1/2-byte elements — XLA owns the schedule — but
    the numerics (and therefore training behaviour) match the
    compressed-wire contract, and ``comms_bytes_per_step`` reports the
    semantic wire bytes for the telemetry counters.

    Step semantics match ``make_train_step`` (one global-batch loss under
    jit; no per-replica rng fold-in), which is exactly what the
    pure-TP + replicated-DP parity reference uses.
    """
    config, plan, tx = state.config, state.plan, state.tx
    flat_sharding = NamedSharding(mesh, P((config.axis, MODEL_AXIS)))
    replicated = NamedSharding(mesh, P())
    param_shardings = jax.tree.map(
        lambda l: (
            l.sharding
            if isinstance(getattr(l, "sharding", None), NamedSharding)
            else replicated
        ),
        state.params,
    )

    def _compress_wire(flat_g):
        """Per-bucket QDQ at the wire dtype. Bucket boundaries are
        multiples of the full shard count (make_flat_plan is built with
        ``axis_size * model_ways``), so each segment's QDQ is aligned
        with the shards the sharded update will move."""
        if config.comms_dtype == "float32":
            return flat_g
        segs = []
        for s, e in plan.buckets:
            seg = flat_g[s:e]
            if config.comms_dtype == "bfloat16":
                seg = seg.astype(jnp.bfloat16).astype(jnp.float32)
            else:  # int8, per-bucket absmax scale — no N-way-sum
                # headroom factor: XLA performs the reduction in fp32
                # after dequantization, so only the stored values are
                # bounded to [-127, 127].
                absmax = jnp.max(jnp.abs(seg))
                scale = jnp.maximum(absmax / 127.0, jnp.float32(1e-30))
                seg = (
                    jnp.clip(jnp.round(seg / scale), -127, 127) * scale
                )
            segs.append(seg)
        return jax.lax.with_sharding_constraint(
            jnp.concatenate(segs), flat_sharding
        )

    @functools.partial(jax.jit, donate_argnums=0)
    def _step(zstate: Zero1State, batch, rng: jax.Array):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            zstate.params, batch, rng
        )
        # ``constrain=replicated`` is the concat-miscompile workaround
        # (see _flatten); the outer constraint is the actual ZeRO
        # placement the update runs in.
        flat_g = jax.lax.with_sharding_constraint(
            _flatten(grads, plan, constrain=replicated), flat_sharding
        )
        flat_g = _compress_wire(flat_g)
        if grad_clip is not None:
            # True global norm (the pad is zeros) — optax
            # clip_by_global_norm semantics, no psum needed under jit.
            g_norm = jnp.sqrt(jnp.sum(jnp.square(flat_g)))
            scale = jnp.where(g_norm < grad_clip, 1.0, grad_clip / g_norm)
            flat_g = flat_g * scale
        flat_p = jax.lax.with_sharding_constraint(
            _flatten(zstate.params, plan, constrain=replicated), flat_sharding
        )
        with jax.named_scope(UPDATE_SCOPE):
            updates, new_opt = tx.update(flat_g, zstate.opt_state, flat_p)
            new_flat = optax.apply_updates(flat_p, updates)
        new_flat = jax.lax.with_sharding_constraint(new_flat, replicated)
        new_params = jax.tree.map(
            jax.lax.with_sharding_constraint,
            _unflatten(new_flat, plan),
            param_shardings,
        )
        return (
            zstate.replace(
                step=zstate.step + 1, params=new_params, opt_state=new_opt
            ),
            loss,
            aux,
        )

    def step(zstate: Zero1State, batch, rng: jax.Array):
        return _step(zstate, batch, rng)

    return step


def plan_layout(plan: _FlatPlan) -> dict:
    """JSON-safe bucket layout of a plan's flat vector — the ``layout``
    record of the checkpoint topology stamp, and the input
    ``train.reshard.BucketLayout.from_json`` consumes for cross-topology
    resharding. ``world`` is the flat shard count (``axis_size *
    model_ways`` on a hybrid mesh), recoverable as ``padded /
    shard_len``; the treedef/leaf shapes are deliberately excluded
    (resharding is pure byte-range redistribution and never needs them).
    """
    return {
        "total": int(plan.total),
        "world": int(plan.padded // plan.shard_len),
        "padded": int(plan.padded),
        "shard_len": int(plan.shard_len),
        "buckets": [[int(s), int(e)] for s, e in plan.buckets],
    }


def opt_state_bytes(opt_state) -> int:
    """Logical (unsharded) byte size of an optimizer-state tree — the
    replicated-mode per-chip footprint."""
    return sum(
        int(l.size) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(opt_state)
        if hasattr(l, "dtype")
    )


def opt_state_bytes_per_chip(state) -> int:
    """Measured per-device optimizer-state residency: max over devices of
    the bytes of addressable shard data. For a replicated state this
    equals ``opt_state_bytes``; for a ZeRO-1 state it is ~1/N of it."""
    per_device: dict = {}
    for leaf in jax.tree.leaves(state.opt_state):
        if not isinstance(leaf, jax.Array):
            continue
        for shard in leaf.addressable_shards:
            per_device[shard.device] = (
                per_device.get(shard.device, 0) + shard.data.nbytes
            )
    return max(per_device.values(), default=0)


__all__ = [
    "COMMS_DTYPES",
    "DEFAULT_BUCKET_BYTES",
    "DP_MODES",
    "ENV_BUCKET_BYTES",
    "ENV_COMMS_DTYPE",
    "ENV_DP_MODE",
    "ENV_OVERLAP",
    "Zero1Config",
    "Zero1State",
    "comms_bytes_per_step",
    "init_sharded",
    "make_flat_plan",
    "make_zero1_step",
    "opt_state_bytes",
    "opt_state_bytes_per_chip",
    "plan_layout",
    "resolve_dp_mode",
    "shard_optimizer_state",
]

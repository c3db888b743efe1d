"""Pipeline parallelism — stage-wise SPMD over the mesh ``"pipeline"`` axis.

The reference has no PP (SURVEY.md §2.3 lists it "not required for parity;
leave hook documented"); this module is the working hook: a GPipe-style
microbatch schedule expressed as one compiled SPMD program, the idiomatic
TPU form (no per-stage processes, no send/recv runtime — ``shard_map`` +
``ppermute`` and a ``lax.scan`` over schedule ticks).

Layout: the mesh's ``pipeline`` axis has one device (group) per stage; the
``stage_params`` operand enters the shard_map split over its leading stage
dim, so each stage materializes only its own slice inside the schedule
(caller-held state outside may still be replicated — see
``pipeline_transformer``'s memory note). The global batch is
split into M microbatches. On tick t, stage s applies itself to the
activations of microbatch t−s and passes the result to stage s+1 via a
single-hop ``ppermute`` — after M + S − 1 ticks every microbatch has
traversed every stage. The classic pipeline bubble (S−1 idle ticks) shrinks
as M grows; activations cross only nearest-neighbour ICI links.

All stages must share one layer shape (the homogeneous-stack case — exactly
the Transformer encoder/decoder stack shape in the zoo); the first/last
stages' embedding/head stay outside the pipelined region, which is standard.

Differentiable end to end: the backward pass reverses the ring through the
``ppermute`` transpose inside the scan, giving the standard reverse
pipeline schedule for grads.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from machine_learning_apache_spark_tpu.parallel.mesh import PIPELINE_AXIS


def _pipeline_shard_fn(
    stage_params, x, aux, aux_rep, *, stage_fn, n_micro, axis, mesh_axes
):
    """Per-stage body under shard_map.

    ``stage_params``: this stage's params (leading stage dim of size 1,
    squeezed). ``x``: the full batch (replicated across stages),
    ``[n_micro, micro_batch, ...]``. ``aux``/``aux_rep``: optional pytrees
    of per-microbatch constants (leaves ``[n_micro, ...]``; ``aux`` is
    per-example and data-sharded, ``aux_rep`` replicated); stage s at tick
    t is processing microbatch t−s, so it receives that microbatch's aux
    slices alongside the activations.
    """
    n_stages = jax.lax.psum(1, axis)
    stage_id = jax.lax.axis_index(axis)
    params = jax.tree.map(lambda p: p[0], stage_params)

    ticks = n_micro + n_stages - 1
    # Fresh carries are replicated constants; mark them device-varying over
    # the pipeline axis so the scan carry type stays uniform after ppermute.
    varying = lambda v: jax.lax.pcast(v, tuple(mesh_axes), to="varying")
    state = varying(jnp.zeros_like(x[0]))  # activation held by this stage
    outputs = varying(jnp.zeros_like(x))
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry
        # Stage 0 injects microbatch t from the batch (while valid); others
        # take what arrived from the previous stage.
        feed = jax.lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False
        )
        inp = jnp.where(stage_id == 0, feed, state)
        if aux is None and aux_rep is None:
            out = stage_fn(params, inp)
        else:
            # The microbatch THIS stage is processing now (clipped during
            # warmup/drain ticks, whose garbage compute is discarded below).
            mb = jnp.clip(t - stage_id, 0, n_micro - 1)
            index = lambda tree: jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, mb, axis=0, keepdims=False
                ),
                tree,
            )
            out = stage_fn(params, inp, index(aux), index(aux_rep), stage_id, t)
        # Microbatch m = t - stage_id finished the last stage at this tick.
        m = t - stage_id
        valid = (m >= 0) & (m < n_micro)

        def write(outputs):
            return jax.lax.dynamic_update_index_in_dim(
                outputs, out, jnp.clip(m, 0, n_micro - 1), axis=0
            )

        outputs = jnp.where(
            valid & (stage_id == n_stages - 1), write(outputs), outputs
        )
        # Hand activations to the next stage (the wrap-around edge back to
        # stage 0 carries garbage that stage 0 ignores — it always injects).
        state = jax.lax.ppermute(out, axis, perm)
        return (state, outputs), None

    (state, outputs), _ = jax.lax.scan(
        tick, (state, outputs), jnp.arange(ticks)
    )
    # Only the last stage holds real outputs; broadcast them to every stage
    # so the result leaves shard_map replicated (psum of one-hot copies).
    outputs = jnp.where(stage_id == n_stages - 1, outputs, 0.0)
    return jax.lax.psum(outputs, axis)


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    mesh: Mesh,
    *,
    n_micro: int | None = None,
    axis: str = PIPELINE_AXIS,
    aux=None,
    aux_replicated=None,
) -> jnp.ndarray:
    """Run ``x`` through ``n_stages`` sequential applications of
    ``stage_fn``, pipelined over the mesh's ``axis``.

    - ``stage_fn(params, x) -> y`` with ``y.shape == x.shape`` (homogeneous
      stack; the residual-block contract of the zoo Transformer's layers).
      With ``aux``/``aux_replicated``, the contract widens to
      ``stage_fn(params, x, aux_m, rep_m, stage_id, tick) -> y`` where
      ``aux_m``/``rep_m`` are the current microbatch's slices.
    - ``stage_params``: pytree whose leaves carry a leading stage dimension
      of size ``mesh.shape[axis]`` (stage i uses slice i).
    - ``x``: ``[batch, ...]``; split into ``n_micro`` microbatches (defaults
      to the stage count — more microbatches, smaller bubble).
    - ``aux``: optional pytree of per-example constants (each leaf
      ``[batch, ...]`` — e.g. attention validity masks, encoder memory);
      microbatched alongside ``x`` and handed to the stage processing that
      microbatch.
    - ``aux_replicated``: optional pytree of per-MICROBATCH constants
      (leaves ``[n_micro, ...]``, e.g. dropout rng key data) that ride
      replicated — never sharded over the data axis.

    Composes with data parallelism: on a mesh that also carries a ``"data"``
    axis, the microbatch dim of ``x``/``aux`` is sharded over it and the
    stages' compute runs on each data shard independently (activations cross
    only the pipeline axis). Other nontrivial mesh axes are rejected —
    TP/SP inside a pipeline stage is out of scope.

    Returns ``stage_fn^(n_stages)(x)`` exactly — parity with the sequential
    loop is pinned by ``tests/test_pipeline_parallel.py``.
    """
    from machine_learning_apache_spark_tpu.parallel.mesh import DATA_AXIS

    n_stages = mesh.shape[axis]
    n_micro = n_micro or n_stages
    batch = x.shape[0]
    if batch % n_micro:
        raise ValueError(f"batch {batch} not divisible by n_micro={n_micro}")
    leaves = jax.tree.leaves(stage_params)
    if not leaves:
        raise ValueError("stage_params is empty")
    leading = {leaf.shape[0] for leaf in leaves}
    if leading != {n_stages}:
        raise ValueError(
            f"stage_params leading dim(s) {leading} != {n_stages} stages"
        )
    unsupported = [
        a
        for a in mesh.axis_names
        if a not in (axis, DATA_AXIS) and mesh.shape[a] > 1
    ]
    if unsupported:
        raise ValueError(
            f"pipeline_apply supports only {axis!r}×{DATA_AXIS!r} meshes; "
            f"got extra nontrivial axes {unsupported}"
        )
    data = DATA_AXIS if DATA_AXIS in mesh.axis_names else None
    if data:
        micro = batch // n_micro
        if micro % mesh.shape[data]:
            raise ValueError(
                f"microbatch {micro} not divisible by the {data!r} axis "
                f"({mesh.shape[data]} ways)"
            )
    # Microbatch dim replicated over stages, example dim sharded over data.
    batch_spec = P(None, data) if data else P()

    xs = x.reshape(n_micro, batch // n_micro, *x.shape[1:])
    aux_ms = (
        jax.tree.map(
            lambda a: a.reshape(n_micro, batch // n_micro, *a.shape[1:]), aux
        )
        if aux is not None
        else None
    )
    fn = jax.shard_map(
        functools.partial(
            _pipeline_shard_fn,
            stage_fn=stage_fn,
            n_micro=n_micro,
            axis=axis,
            mesh_axes=(axis,),
        ),
        mesh=mesh,
        in_specs=(
            P(axis),
            batch_spec,
            jax.tree.map(lambda _: batch_spec, aux_ms),
            jax.tree.map(lambda _: P(), aux_replicated),
        ),
        out_specs=batch_spec,
    )
    out = fn(stage_params, xs, aux_ms, aux_replicated)
    return out.reshape(batch, *x.shape[1:])

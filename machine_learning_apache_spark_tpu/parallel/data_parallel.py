"""Data parallelism — the reference's DDP layer as compiled collectives.

The reference wraps each model in ``DDP(model)`` over a gloo process group and
lets backward hooks allreduce gradients (C11, ``distributed_cnn.py:152-156``).
Here the same replica-synchronous semantics are ~3 lines inside the compiled
step (SURVEY.md §7): params replicated, batch sharded over the mesh axis
``"data"``, ``lax.pmean`` of grads — XLA emits the allreduce over ICI and
overlaps it with compute (subsuming DDP's bucketing, SURVEY.md §2.2).

Two equivalent paths are provided:

- implicit — ``train.fit(..., mesh=mesh)``: jit + sharded inputs; XLA's
  sharding propagation inserts the reduction.
- explicit — ``make_data_parallel_step``: ``shard_map`` with a visible
  ``lax.pmean``, the form that generalizes to the hybrid dp×tp×sp meshes.

The DDP-equivalence property the reference *intends* (broken there by quirks
Q2/Q3): an N-way sharded step on batch B must produce the same params as a
single-device step on the whole of B. ``tests/test_data_parallel.py`` asserts
it on the virtual 8-device CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from machine_learning_apache_spark_tpu.parallel.mesh import DATA_AXIS
from machine_learning_apache_spark_tpu.train.state import TrainState


def make_data_parallel_step(
    loss_fn: Callable, mesh: Mesh, *, axis: str = DATA_AXIS
):
    """Fused DP train step: grads pmean'd over ``axis`` inside ``shard_map``.

    ``loss_fn(params, batch, rng) -> (loss, aux)`` sees only this shard's
    slice of the batch. Dropout keys are decorrelated per shard via
    ``fold_in(axis_index)`` — matching DDP, where each replica draws its own
    dropout mask.
    """

    axis_size = mesh.shape[axis]

    def per_shard(params, batch, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))

        def scaled_loss(p):
            loss, aux = loss_fn(p, batch, rng)
            return loss / axis_size, (loss, aux)

        (_, (loss, aux)), grads = jax.value_and_grad(scaled_loss, has_aux=True)(
            params
        )
        # The DDP gradient allreduce (distributed_cnn.py:156 backward hooks):
        # params enter replicated (in_spec P()), so shard_map's transpose
        # inserts the psum-of-cotangents across `axis` automatically — with
        # the 1/axis_size loss scaling above, `grads` IS the global-mean
        # gradient, as one compiled collective over ICI. An explicit psum
        # here would reduce a second time.
        loss = jax.lax.pmean(loss, axis)
        aux = jax.tree.map(lambda x: jax.lax.pmean(x, axis), aux)
        return grads, loss, aux

    sharded = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(), P(axis), P()),
        out_specs=(P(), P(), P()),
    )

    @functools.partial(jax.jit, donate_argnums=0)
    def step(state: TrainState, batch, rng: jax.Array):
        grads, loss, aux = sharded(state.params, batch, rng)
        return state.apply_gradients(grads), loss, aux

    return step


def make_data_parallel_eval_step(loss_fn: Callable, mesh: Mesh, *, axis: str = DATA_AXIS):
    def per_shard(params, batch, rng):
        loss, aux = loss_fn(params, batch, rng)
        return jax.lax.pmean(loss, axis), jax.tree.map(
            lambda x: jax.lax.pmean(x, axis), aux
        )

    sharded = jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P(), P(axis), P()), out_specs=(P(), P())
    )

    @jax.jit
    # mlspark-lint: ok jit-donate -- eval step: state is read, not updated; donating would consume the caller's buffers
    def step(state: TrainState, batch, rng: jax.Array):
        return sharded(state.params, batch, rng)

    return step


def pad_batch_to_multiple(batch, multiple: int):
    """Pad the leading dim so it divides the data axis (XLA needs equal
    shards). Returns (padded_batch, real_count) — metrics weight by
    ``real_count``; padded rows repeat row 0 and carry zero loss weight only
    if the loss masks them, so prefer drop_last loaders for training."""
    leaves = jax.tree.leaves(batch)
    n = leaves[0].shape[0]
    target = -(-n // multiple) * multiple
    if target == n:
        return batch, n
    pad = target - n

    def _pad(x):
        reps = jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)], axis=0)
        return reps

    return jax.tree.map(_pad, batch), n


def _params_of(tree):
    """Fingerprint the PARAMS only when handed a whole train state
    (``TrainState``/``Zero1State``): the optimizer state may be legitimately
    sharded (zero1) and must never poison a replication check."""
    if hasattr(tree, "params") and hasattr(tree, "opt_state"):
        return tree.params
    return tree


def _check_fingerprintable(params, *, require_replicated: bool) -> None:
    """Clear errors instead of wrong answers: a leaf this process cannot
    read whole (multi-process sharding) can't be fingerprinted, and a
    sharded (non-replicated) tree must never enter the cross-replica sync
    check — each process would hash different data and the allgather would
    compare apples to oranges."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if not isinstance(leaf, jax.Array):
            continue
        where = jax.tree_util.keystr(path)
        if not leaf.is_fully_addressable:
            raise ValueError(
                f"params_fingerprint: leaf {where} is sharded across "
                "processes and cannot be read whole here; fingerprint "
                "state.params (replicated), not a sharded tree"
            )
        if (
            require_replicated
            and len(leaf.sharding.device_set) > 1
            and not leaf.is_fully_replicated
        ):
            raise ValueError(
                f"assert_replicas_in_sync: leaf {where} is sharded "
                f"({leaf.sharding}), not replicated — the cross-process "
                "fingerprint comparison is only meaningful for replicated "
                "params. Pass state.params (zero1 keeps params replicated; "
                "its sharded optimizer state must stay out of this check)."
            )


def params_fingerprint(params) -> float:
    """Order-stable scalar fingerprint of a param pytree (sum of |p| per leaf,
    combined) — cheap to compare across processes. Accepts a bare params
    tree or a whole train state (params-only fingerprint)."""
    params = _params_of(params)
    _check_fingerprintable(params, require_replicated=False)
    leaves = jax.tree.leaves(params)
    total = 0.0
    for i, p in enumerate(leaves):
        total += (i + 1) * float(jnp.sum(jnp.abs(p.astype(jnp.float32))))
    return total


def assert_replicas_in_sync(params, *, atol: float = 1e-6) -> float:
    """Race-detector analogue (SURVEY.md §5): in a multi-process run, gather
    every process's param fingerprint and assert they agree — the compiled-world
    check for the reference's Q2-class replica-drift bug (forward through the
    raw module bypassing DDP sync, ``distributed_cnn.py:175``). Single-process
    runs (single-controller semantics: one logical copy) pass trivially.

    Accepts a bare params tree or a whole train state (only ``.params`` is
    checked); raises ``ValueError`` on a non-replicated tree rather than
    allgathering fingerprints of different data.

    Returns the max cross-process divergence.
    """
    params = _params_of(params)
    _check_fingerprintable(params, require_replicated=True)
    fp = params_fingerprint(params)
    if jax.process_count() == 1:
        return 0.0
    from jax.experimental import multihost_utils

    all_fps = multihost_utils.process_allgather(jnp.asarray(fp))
    div = float(jnp.max(jnp.abs(all_fps - all_fps[0])))
    if div > atol * max(abs(fp), 1.0):
        raise AssertionError(
            f"replica divergence {div} across {jax.process_count()} processes"
        )
    return div

"""Device-mesh construction.

This is where the reference's distributed runtime (gloo process groups,
``distributed_cnn.py:152``) maps onto TPU hardware: a ``jax.sharding.Mesh``
over the slice, with collectives compiled into the step and riding ICI.

Axis convention (used across the framework):

- ``"data"``     — batch-sharded data parallelism (the reference's DDP, C11).
- ``"model"``    — tensor parallelism (capability headroom; SURVEY.md §2.3).
- ``"seq"``      — sequence/context parallelism for ring attention.
- ``"pipeline"`` — pipeline stages.
- ``"expert"``   — expert parallelism (MoE; unused by the zoo, reserved).
"""

from __future__ import annotations

import math
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPELINE_AXIS = "pipeline"
EXPERT_AXIS = "expert"

_CANONICAL_ORDER = (DATA_AXIS, PIPELINE_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)


def make_mesh(
    axes: Mapping[str, int] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a mesh from an axis-name → size mapping.

    Size ``0`` or ``-1`` on at most one axis means "all remaining devices".
    With no axes given, returns a pure data-parallel mesh over every device.
    Axes are laid out so the innermost (fastest-varying, best-ICI-locality)
    axis is ``model``, then ``seq`` — tensor- and sequence-parallel
    collectives are latency-bound and want nearest-neighbour links, while
    data-parallel allreduce tolerates the outer axis.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    axes = dict(axes or {DATA_AXIS: n})

    wildcard = [k for k, v in axes.items() if v in (0, -1)]
    if len(wildcard) > 1:
        raise ValueError(f"at most one wildcard axis, got {wildcard}")
    fixed = math.prod(v for v in axes.values() if v not in (0, -1))
    if wildcard:
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by fixed axes {axes}")
        axes[wildcard[0]] = n // fixed
    if math.prod(axes.values()) != n:
        raise ValueError(f"mesh {axes} does not cover {n} devices")

    names = sorted(
        axes.keys(),
        key=lambda a: _CANONICAL_ORDER.index(a) if a in _CANONICAL_ORDER else 0,
    )
    shape = tuple(axes[a] for a in names)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, names)


def data_parallel_mesh(n: int | None = None) -> Mesh:
    """The parity mesh: one axis ``"data"`` over n (default: all) devices —
    the TPU form of the reference's N gloo ranks (SURVEY.md §2.4)."""
    devices = jax.devices()[:n] if n else None
    return make_mesh({DATA_AXIS: 0 if n is None else n}, devices=devices)


def data_model_mesh(model: int, data: int | None = None) -> Mesh:
    """The hybrid 2-D mesh: ``data x model`` with ``model`` innermost
    (canonical axis order), the layout ``fit(dp_mode="zero1")`` composes
    ZeRO-1 and tensor parallelism over. ``data=None`` spreads whatever
    devices remain after the model axis (``data = n_devices / model``)."""
    if model <= 0:
        raise ValueError(f"model axis size must be positive, got {model}")
    return make_mesh({DATA_AXIS: 0 if data is None else data, MODEL_AXIS: model})


def batch_sharding(mesh: Mesh, *, axis: str = DATA_AXIS) -> NamedSharding:
    """Sharding for a batch-leading array: dim 0 split over the data axis —
    the ``DistributedSampler`` partitioning (``distributed_cnn.py:112-119``)
    expressed as a sharding instead of a sampler."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated (DDP keeps whole replicas of params on every rank —
    ``DDP(model)`` at ``distributed_cnn.py:156``)."""
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch, *, axis: str = DATA_AXIS):
    """Place a host-local pytree of arrays onto the mesh, batch-dim sharded.

    Single-process: a plain sharded ``device_put``. Multi-process (the mesh
    spans hosts): each process holds only its *local slice* of the global
    batch (the ``DistributedSampler`` shard, ``distributed_cnn.py:112-119``)
    and the global array is assembled per-shard via
    ``jax.make_array_from_process_local_data`` — the L3 mapping in SURVEY.md
    (§1): per-process slicing + sharded device arrays.
    """
    sharding = batch_sharding(mesh, axis=axis)
    if jax.process_count() > 1:
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                sharding, np.asarray(x)
            ),
            batch,
        )
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def shard_batch_stack(mesh: Mesh, batches: list, *, axis: str = DATA_AXIS):
    """Stack K host batches into one ``[K, batch, ...]`` pytree for the
    scanned multi-step trainer (``train.loop.make_multi_step``): the scan
    axis (dim 0) replicated, each step's batch dim (dim 1) sharded over the
    data axis exactly as ``shard_batch`` would shard it alone.

    Multi-process: each process contributes ``[K, local_batch, ...]`` and
    the global array is assembled per-shard, same contract as
    ``shard_batch``.
    """
    stacked = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *batches
    )
    sharding = NamedSharding(mesh, P(None, axis))
    if jax.process_count() > 1:
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(sharding, x),
            stacked,
        )
    return jax.tree.map(lambda x: jax.device_put(x, sharding), stacked)


def on_one_device(tree):
    """``(tree, device)`` with every array of ``tree`` on one device: the
    lowest-id device any of them touches (None, and ``tree`` unchanged,
    when it holds no device arrays). Inference runs where its params are,
    and params trained under a mesh arrive replicated over all of it —
    a serving engine or a one-shot decode is a one-device program."""
    devices = {
        d
        for leaf in jax.tree.leaves(tree)
        if isinstance(leaf, jax.Array)
        for d in leaf.sharding.device_set
    }
    device = min(devices, key=lambda d: d.id, default=None)
    if len(devices) > 1:
        tree = jax.device_put(tree, device)
    return tree, device


def device_prefetch(batches, mesh: Mesh, *, depth: int = 2,
                    axis: str = DATA_AXIS):
    """Shard batches onto the mesh ``depth`` ahead of consumption.

    ``jax.device_put`` only *enqueues* a transfer, so issuing the next
    batches' transfers before the current step is consumed lets host→device
    copies overlap device compute — the input-pipeline double-buffering
    every TPU workload wants. Bounded at ``depth`` in-flight batches to
    cap HBM staging memory. Values are unchanged
    (pinned by ``tests/test_train.py::TestDevicePrefetch``).
    """
    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    from collections import deque

    q: deque = deque()
    for batch in batches:
        q.append(shard_batch(mesh, batch, axis=axis))
        if len(q) >= depth:
            yield q.popleft()
    while q:
        yield q.popleft()


def replicate(mesh: Mesh, tree):
    sharding = replicated_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)

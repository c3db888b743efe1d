"""Ulysses-style sequence parallelism — all-to-all head↔sequence resharding.

The second long-context mechanism next to ``ring_attention`` (the task's
"ring attention or all-to-all sequence/context parallelism"). Same
placement contract — Q/K/V ``[B, H, S, D]`` sharded along S over the mesh
``"seq"`` axis — but a different communication shape:

- **Ring**: K/V chunks rotate n−1 hops around the ICI ring; each hop is a
  small nearest-neighbour transfer overlapped with that hop's block
  FLOPs. Peak memory O(S/n · S/n) scores; any head count.
- **Ulysses** (this module): ONE ``all_to_all`` converts the layout from
  sequence-sharded/all-heads to head-sharded/full-sequence, each device
  runs ordinary full-length attention for its H/n heads, and one inverse
  ``all_to_all`` restores the layout. Three big collectives total (Q, KV
  in, out back) instead of n−1 hops — fewer, larger transfers that load
  ICI better when the per-hop ring transfers would be latency-bound.
  Requires ``num_heads % n == 0``; peak memory O(S²) scores per H/n heads
  unless the inner attention is itself blockwise (on TPU the inner call
  streams through the Pallas flash kernel, keeping O(S) rows).

Inside the shard_map the inner attention is computed directly (flash on
TPU, fused-XLA dense elsewhere) — never through
``ops.attention.dot_product_attention``, whose active sequence-parallel
context would recurse back here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from machine_learning_apache_spark_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS


def _inner_attention(q, k, v, kv_valid, *, causal):
    """Full-length attention for this device's head group (no SP dispatch —
    see module docstring)."""
    if jax.default_backend() == "tpu":
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            flash_attention,
        )

        return flash_attention(q, k, v, causal=causal, kv_valid=kv_valid)
    from machine_learning_apache_spark_tpu.ops.attention import (
        scaled_dot_product_attention,
    )
    from machine_learning_apache_spark_tpu.ops.masks import (
        combine_masks,
        make_causal_mask,
    )

    mask = None
    if kv_valid is not None:
        mask = kv_valid[:, None, None, :]
    if causal:
        mask = combine_masks(mask, make_causal_mask(q.shape[2], k.shape[2]))
    out = scaled_dot_product_attention(q, k, v, mask)
    if kv_valid is not None:
        # Fully-padded rows emit ZEROS (the ring/flash convention): the
        # finite NEG_INF masking above would otherwise softmax an all-masked
        # row to uniform weights and return the mean of V.
        out = jnp.where(kv_valid.any(-1)[:, None, None, None], out, 0.0)
    return out


def _ulysses_shard_fn(q, k, v, kv_valid, *, axis, causal):
    """Per-device body: local shards are ``[b, H, S/n, d]`` (+ kv_valid
    ``[b, S/n]``). all_to_all to ``[b, H/n, S, d]``, attend, invert."""
    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis, tiled=True
    )
    # sequence-sharded/all-heads → head-sharded/full-sequence. K and V ride
    # ONE exchange (stacked on a leading dim) — 3 collectives total per
    # call: q in, kv in, out back.
    q = a2a(q, split_axis=1, concat_axis=2)
    kv = a2a(jnp.stack([k, v]), split_axis=2, concat_axis=3)
    k, v = kv[0], kv[1]
    if kv_valid is not None:
        # Per-key validity must cover the FULL gathered sequence.
        kv_valid = jax.lax.all_gather(kv_valid, axis, axis=1, tiled=True)
    out = _inner_attention(q, k, v, kv_valid, causal=causal)
    # head-sharded/full-sequence → sequence-sharded/all-heads
    return a2a(out, split_axis=2, concat_axis=1)


def ulysses_attention(
    query: jnp.ndarray,
    key: jnp.ndarray,
    value: jnp.ndarray,
    mesh: Mesh,
    *,
    causal: bool = False,
    kv_valid: jnp.ndarray | None = None,
    seq_axis: str = SEQ_AXIS,
    batch_axis: str | None = DATA_AXIS,
) -> jnp.ndarray:
    """Sequence-parallel attention over ``[B, H, S, D]`` streams via
    head↔sequence ``all_to_all`` — drop-in for ``ring_attention`` (same
    signature, same placement, same output), for models whose head count
    divides the ``seq_axis``.

    ``kv_valid`` (``[B, S]`` bool, True = attendable) is gathered once to
    full length. Fully-padded rows emit zeros (the flash-kernel
    convention). Differentiable: ``all_to_all`` is its own transpose up to
    axis swap, so the backward runs the inverse exchanges.
    """
    if query.shape != key.shape or key.shape != value.shape:
        raise ValueError(
            f"ulysses attention is self-attention-shaped: q/k/v must match, "
            f"got {query.shape}/{key.shape}/{value.shape}"
        )
    n = mesh.shape[seq_axis]
    if query.shape[2] % n:
        raise ValueError(
            f"sequence length {query.shape[2]} not divisible by "
            f"{seq_axis}={n}"
        )
    if query.shape[1] % n:
        raise ValueError(
            f"ulysses needs num_heads ({query.shape[1]}) divisible by "
            f"{seq_axis}={n}; use ring attention for this head count"
        )
    if kv_valid is not None and kv_valid.shape != (
        query.shape[0], query.shape[2],
    ):
        raise ValueError(
            f"kv_valid must be [batch={query.shape[0]}, "
            f"seq={query.shape[2]}], got {kv_valid.shape}"
        )
    batch = batch_axis if batch_axis in mesh.shape else None
    spec = P(batch, None, seq_axis, None)
    valid_spec = P(batch, seq_axis)
    fn = jax.shard_map(
        functools.partial(_ulysses_shard_fn, axis=seq_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec, valid_spec if kv_valid is not None else P()),
        out_specs=spec,
    )
    return fn(query, key, value, kv_valid)

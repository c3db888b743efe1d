"""Ring attention — sequence/context parallelism over the mesh ``"seq"`` axis.

The reference has no long-context mechanism at all: sequence length is
bounded by construction (Truncate(128) / fixed 200, SURVEY.md §5) and
attention is full O(S²) dense with a materialized [S,S] mask
(``transformer.py:12-25``). This module is the framework's scaling path for
sequences that do not fit one chip.

Mechanism (Ring Attention / blockwise flash over ICI): Q, K, V are sharded
along the sequence dimension over the ``"seq"`` mesh axis. Each device keeps
its Q shard resident and runs the flash-attention online-softmax recurrence

    m' = max(m, rowmax(S_blk));  α = exp(m - m')
    l' = l·α + rowsum(exp(S_blk - m'))
    acc' = acc·α + exp(S_blk - m') @ V_blk

over K/V shards that *rotate around the ring* via ``lax.ppermute`` — after
``seq`` steps every Q block has attended to every K/V block, with only
1/seq-th of K/V resident per device at any time and the per-hop transfer
riding nearest-neighbour ICI links. Communication overlaps compute under
XLA's scheduler (each scan step's ppermute is independent of that step's
FLOPs). Peak memory per chip: O(S/n · S/n) scores instead of O(S²).

Causality never materializes an [S,S] mask: each hop classifies its K/V
shard by *global* chunk position — fully-behind chunks attend densely,
fully-ahead chunks are skipped (their contribution multiplies in as exp(-∞)
= 0), and only the diagonal chunk applies a local triangular mask. The hop
schedule starts at the device's own chunk, so every query row sees its
diagonal at step 0 and the running max is finite from the first update (no
0/0 in the recurrence).

Same accumulator as ``ops.pallas_attention`` (SURVEY.md §5's design seam:
blockwise attention core so ring/CP variants slot in behind one signature).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from machine_learning_apache_spark_tpu.ops.attention import NEG_INF
from machine_learning_apache_spark_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS


def _block_update(q, k, v, m, l, acc, bias, scale):
    """One online-softmax block update (float32 accumulators)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m_new = jnp.maximum(m, s.max(axis=-1))
    # NEG_INF-biased columns must contribute exactly zero even when the row
    # max itself is NEG_INF (all-masked so far): exp(-inf - -inf) would be 1.
    p = jnp.where(
        s > NEG_INF * 0.5, jnp.exp(s - m_new[..., None]), 0.0
    )
    alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def _ring_shard_fn(q, k, v, kv_valid, *, axis, causal, scale, mesh_axes):
    """Per-device body under shard_map: q/k/v are the local sequence shards
    ``[B, H, S_local, D]``; kv_valid (may be None) is ``[B, S_local]``."""
    n = jax.lax.psum(1, axis)
    me = jax.lax.axis_index(axis)
    b, h, s_q, d = q.shape
    s_k = k.shape[2]

    # Fresh accumulators are replicated constants; mark them device-varying
    # over exactly the axes q varies over (the in_specs axes — NOT every mesh
    # axis: varying over an axis absent from out_specs is a trace error on
    # e.g. a dp×tp×sp mesh) so the scan carry type stays uniform.
    varying = lambda x: jax.lax.pcast(x, tuple(mesh_axes), to="varying")
    m = varying(jnp.full((b, h, s_q), NEG_INF, jnp.float32))
    l = varying(jnp.zeros((b, h, s_q), jnp.float32))
    acc = varying(jnp.zeros((b, h, s_q, d), jnp.float32))

    # Local positions within a chunk; global position = chunk_id * s + pos.
    q_pos = jnp.arange(s_q)
    k_pos = jnp.arange(s_k)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, hop):
        k_blk, v_blk, kv_blk, m, l, acc = carry
        # After `hop` forward rotations, this device holds the chunk that
        # started on device me - hop (mod n).
        src = (me - hop) % n

        def attend(m, l, acc):
            bias = None
            if causal:
                # Global causal test, chunk-granular: diagonal → local
                # triangle, behind → no bias (fully-ahead chunks never reach
                # here — see the cond below).
                q_glob = me * s_q + q_pos  # [s_q]
                k_glob = src * s_k + k_pos  # [s_k]
                bias = jnp.where(
                    q_glob[:, None] >= k_glob[None, :], 0.0, NEG_INF
                ).astype(jnp.float32)
            if kv_blk is not None:
                # Per-key padding validity rides the ring with its K/V chunk.
                kv_bias = jnp.where(kv_blk, 0.0, NEG_INF).astype(jnp.float32)
                kv_bias = kv_bias[:, None, None, :]  # [b, 1, 1, s_k]
                bias = kv_bias if bias is None else bias + kv_bias
            return _block_update(q, k_blk, v_blk, m, l, acc, bias, scale)

        if causal:
            # SKIP fully-ahead chunks — a real branch, not a zeroed compute:
            # without it the causal ring does ~2× the necessary FLOPs.
            fully_ahead = src * s_k > me * s_q + (s_q - 1)
            m, l, acc = jax.lax.cond(
                fully_ahead, lambda m, l, acc: (m, l, acc), attend, m, l, acc
            )
        else:
            m, l, acc = attend(m, l, acc)
        # Rotate K/V one hop around the ring for the next step. The final
        # rotation restores the original layout (and keeps the scan carry
        # shape uniform); XLA overlaps it with this step's compute.
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        if kv_blk is not None:
            kv_blk = jax.lax.ppermute(kv_blk, axis, perm)
        return (k_blk, v_blk, kv_blk, m, l, acc), None

    (k, v, kv_valid, m, l, acc), _ = jax.lax.scan(
        step, (k, v, kv_valid, m, l, acc), jnp.arange(n)
    )
    # Rows with zero valid keys (fully-padded) emit zeros, never NaN.
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l[..., None]).astype(q.dtype)


def ring_attention(
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
    """Sequence-parallel attention over ``[B, H, S, D]`` streams.

    ``S`` is sharded over ``seq_axis`` (and ``B`` over ``batch_axis`` when it
    is in the mesh) — a drop-in for ``scaled_dot_product_attention`` on
    sequences too long for one chip. Self-attention shapes only (Sq == Sk);
    the ``seq_axis`` size must divide the global sequence length.

    ``kv_valid`` (``[B, S]`` bool, True = attendable) is the per-key padding
    mask of the MT model; its chunks ride the ring alongside K/V. Fully-
    padded rows emit zeros (matching the flash kernel's convention).

    Differentiable: the backward pass re-runs the ring in reverse via the
    transpose of ``ppermute`` inside the scan.
    """
    if query.shape != key.shape or key.shape != value.shape:
        raise ValueError(
            f"ring attention is self-attention-shaped: q/k/v must match, got "
            f"{query.shape}/{key.shape}/{value.shape}"
        )
    n = mesh.shape[seq_axis]
    if query.shape[2] % n:
        raise ValueError(
            f"sequence length {query.shape[2]} not divisible by "
            f"{seq_axis}={n}"
        )
    if kv_valid is not None and kv_valid.shape != (
        query.shape[0], query.shape[2],
    ):
        raise ValueError(
            f"kv_valid must be [batch={query.shape[0]}, "
            f"seq={query.shape[2]}], got {kv_valid.shape}"
        )
    scale = 1.0 / (query.shape[-1] ** 0.5)
    batch = batch_axis if batch_axis in mesh.shape else None
    spec = P(batch, None, seq_axis, None)
    valid_spec = P(batch, seq_axis)
    spec_axes = (seq_axis,) if batch is None else (batch, seq_axis)
    fn = jax.shard_map(
        functools.partial(
            _ring_shard_fn,
            axis=seq_axis,
            causal=causal,
            scale=scale,
            mesh_axes=spec_axes,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec, valid_spec if kv_valid is not None else P()),
        out_specs=spec,
    )
    return fn(query, key, value, kv_valid)

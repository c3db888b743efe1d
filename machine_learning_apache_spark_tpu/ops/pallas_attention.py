"""Blockwise flash attention as a Pallas TPU kernel.

The reference's attention core materializes the full ``[S, S]`` score matrix
(``transformer.py:12-25``). On TPU that is HBM-bandwidth-bound and caps the
sequence length; this kernel streams K/V blocks through VMEM with an online
softmax (running max / denominator / output accumulator in scratch), never
materializing scores — the flash-attention recurrence:

    m_new = max(m, rowmax(S_blk))
    l_new = l * exp(m - m_new) + rowsum(exp(S_blk - m_new))
    acc   = acc * exp(m - m_new) + exp(S_blk - m_new) @ V_blk

Grid = (batch*heads, q_blocks, k_blocks) with the k axis innermost and
sequential, so the scratch accumulators persist across k iterations of one
q block. The same per-block accumulator is what ``parallel/ring_attention.py``
rotates over ICI for sequence parallelism (SURVEY.md §5 long-context seam).

**Tiles.** A grid step costs about half a microsecond on a v5e whatever it
holds, and a 128 x 128 tile of scores is a sixth of that in products, so the
tile sides are chosen from the launch's own shapes (``_choose_tiling``): each
side is padded to the largest of 1024 / 512 / 256 / 128 that adds at most an
eighth to it, and each of the three kernels (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``) takes the largest ``block_q x block_k`` of those sizes
whose reckoned VMEM (double-buffered operand and output tiles, the float32
accumulators, the float32 ``[block_q, block_k]`` temporaries) stays under
``VMEM_BUDGET``. Under causality a skipped step fetches nothing (its block
index is clamped to the last one needed) and a tile wholly under the
diagonal and inside the real lengths builds no mask. Each launch leaves one
``ops.attention_dispatch`` record (site ``flash_tiles``) with its tiles,
grid and reckoned VMEM.

Numerics are float32 in the accumulators regardless of input dtype
(bfloat16-friendly: matmuls feed the MXU in the input dtype, reductions stay
exact enough to train).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def _when(cond, body) -> None:
    """``pl.when`` that also takes a condition known while tracing."""
    if isinstance(cond, bool):
        if cond:
            body()
    else:
        pl.when(cond)(body)


def _both(a, b):
    """``a & b`` where either may be known while tracing."""
    if isinstance(a, bool):
        return b if a else False
    if isinstance(b, bool):
        return a if b else False
    return a & b


def _tile_predicates(
    i, j, *, causal, causal_offset, q_len, kv_len, q_pad, k_pad, block_q,
    block_k, has_kv_valid,
):
    """``(needed, unmasked)`` of the score tile at query block ``i``, key
    block ``j``, each a Python bool where the shapes alone decide.

    ``needed``: under causality, key blocks strictly above the (bottom-right
    aligned) diagonal contribute nothing — their compute is skipped entirely
    (this is where flash attention halves the FLOPs). ``unmasked``: every
    key and query of the tile is real and, under causality, its last key is
    at or under its first query's diagonal — no element is masked, so the
    kernel builds no iota / compare / select for it. Every row of such a
    tile sees key 0, so its ``lse`` is finite. ``q_len=None`` leaves the
    query side out (the forward never masks padded query rows)."""
    if causal:
        needed = j * block_k <= i * block_q + block_q - 1 + causal_offset
    else:
        needed = True
    if has_kv_valid:
        return needed, False
    unmasked = True if kv_len == k_pad else (j + 1) * block_k <= kv_len
    if q_len is not None and q_len != q_pad:
        unmasked = _both(unmasked, (i + 1) * block_q <= q_len)
    if causal:
        unmasked = _both(
            unmasked, (j + 1) * block_k - 1 <= i * block_q + causal_offset
        )
    return needed, unmasked


def _run_tile(needed, unmasked, body) -> None:
    """``body(masked)`` for a needed tile: the unmasked form where the
    tile's predicates allow it."""
    if unmasked is not False:
        _when(_both(needed, unmasked), functools.partial(body, False))
    if unmasked is not True:
        masked = needed if unmasked is False else _both(needed, ~unmasked)
        _when(masked, functools.partial(body, True))


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    *refs,
    has_kv_valid: bool,
    return_lse: bool,
    causal: bool,
    causal_offset: int,
    kv_len: int,
    k_pad: int,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    scale: float,
):
    # The kv_valid operand exists only when a mask was passed — the unmasked
    # hot path pays no extra HBM traffic or per-tile AND. The lse output
    # exists only under differentiation (the backward kernels recompute
    # probabilities from it instead of saving the [S, S] matrix).
    refs = list(refs)
    kv_valid_ref = refs.pop(0) if has_kv_valid else None
    o_ref = refs.pop(0)
    lse_ref = refs.pop(0) if return_lse else None
    m_scr, l_scr, acc_scr = refs
    i = pl.program_id(1)  # query-block index
    j = pl.program_id(2)  # key-block index (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    needed, unmasked = _tile_predicates(
        i, j, causal=causal, causal_offset=causal_offset, q_len=None,
        kv_len=kv_len, q_pad=None, k_pad=k_pad, block_q=block_q,
        block_k=block_k, has_kv_valid=has_kv_valid,
    )

    def _block(masked: bool):
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]  # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        if masked:
            k_idx = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            mask = k_idx < kv_len  # wrapper zero-pads K; padded keys masked
            if has_kv_valid:
                # Per-key validity (padding mask): [1, block_k] over rows.
                mask = mask & (kv_valid_ref[0] != 0)
            if causal:
                # Bottom-right-aligned diagonal: the last real query row sees
                # all kv_len keys even when q_len != kv_len (decode
                # convention).
                q_idx = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                mask = mask & (k_idx <= q_idx + causal_offset)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        if masked:
            # Explicit zero for masked entries: when a row's running max is
            # still NEG_INF (no valid key seen yet), exp(s - m) would be
            # exp(0)=1 and silently average V; zeroing keeps l=0 so
            # _finalize emits zeros.
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_cur

    _run_tile(needed, unmasked, _block)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        # Fully-masked rows (query padding) have l == 0; emit zeros, not NaN.
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        if return_lse:
            # Row softmax normalizer in log space; NEG_INF marks fully-masked
            # rows so the backward masks them out entirely.
            lse_ref[0] = jnp.where(
                l == 0.0, NEG_INF, m_scr[:] + jnp.log(safe_l)
            )


def _out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """``out_shape`` entry for a kernel whose result varies over the same
    manual mesh axes as its operands — inside a ``shard_map`` that checks
    varying-ness, ``pallas_call`` wants it stated."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _round_up(size: int, multiple: int) -> int:
    return -(-size // multiple) * multiple


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    target = _round_up(size, multiple)
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad)


# -- tiling ------------------------------------------------------------------

_LANES = 128
_TILE_SIDES = (1024, 512, 256, 128)
# What a launch's tiles may take of VMEM as ``_vmem_bytes`` reckons it. A
# v5e core has 128 MiB; Mosaic's default scoped limit is 16 MiB, and a launch
# reckoned over three quarters of that asks for its reckoning and a quarter.
VMEM_BUDGET = 32 * 2**20
_MOSAIC_DEFAULT_VMEM = 16 * 2**20
_VMEM_PHYSICAL = 128 * 2**20


class _Tiling(NamedTuple):
    """Padded lengths of a site and ``(block_q, block_k)`` of each kernel."""

    q_pad: int
    k_pad: int
    fwd: tuple[int, int]
    dq: tuple[int, int]
    dkv: tuple[int, int]


def _vmem_bytes(
    kernel: str, block_q: int, block_k: int, d_pad: int, itemsize: int
) -> int:
    """VMEM a grid step of ``kernel`` ("fwd", "dq", "dkv") holds, reckoned:
    the pipeline's two buffers of every operand and output tile, the float32
    accumulators, and the float32 ``[block_q, block_k]`` temporaries live at
    once: three in the forward and in dq, four in dkv. That count is the
    least that stays at or over what Mosaic allocates for a v5e (the limit
    bisected on the compiled kernels at 512 to 1024 a side, ``d_pad`` 128 and
    256, bfloat16 and float32: 0.6 to 0.95 of this reckoning). A float32
    ``[rows, 1]`` column takes whole lanes, a ``[1, cols]`` row whole
    sublanes."""
    q_tile, k_tile = block_q * d_pad, block_k * d_pad
    column, row = block_q * _LANES * 4, 8 * block_q * 4
    scores = block_q * block_k * 4
    if kernel == "fwd":
        tiles = (2 * q_tile + 2 * k_tile) * itemsize + column  # q o k v lse
        return 2 * tiles + q_tile * 4 + 2 * column + 3 * scores
    if kernel == "dq":
        tiles = (3 * q_tile + 2 * k_tile) * itemsize + 2 * column
        return 2 * tiles + q_tile * 4 + 3 * scores
    tiles = (2 * q_tile + 4 * k_tile) * itemsize + 2 * row
    return 2 * tiles + 2 * k_tile * 4 + 4 * scores


def _side(length: int, block: int | None, sublanes: bool) -> tuple[int, int]:
    """``(padded length, largest tile)`` of one side of the scores.

    A caller's ``block`` is honoured, clamped to the side as it always was
    (a short query side to a multiple of 8, a key side to one of 128).
    Chosen: the largest of ``_TILE_SIDES`` whose padding adds at most an
    eighth to the side padded to 128 — doubling a tile's side halves that
    side's grid steps, which an eighth more rows does not cost — so a site
    of 200 or 256 positions is one tile a side."""
    whole = _round_up(length, _LANES)
    if sublanes:
        whole = max(8, _round_up(length, 8))
    if block is not None:
        block = min(block, whole)
        return _round_up(length, block), block
    if sublanes and length <= _LANES:
        return whole, whole
    base = _round_up(length, _LANES)
    tile = next(
        t for t in _TILE_SIDES if _round_up(length, t) - base <= base // 8
    )
    return _round_up(length, tile), tile


def _choose_tiling(
    q_len: int, kv_len: int, d_pad: int, itemsize: int,
    block_q: int | None = None, block_k: int | None = None,
) -> _Tiling:
    """Tiles of the three kernels from what a launcher sees of its operands.

    Each side is padded once (``_side``), whatever the kernel: that is what
    lets the backward read the forward's ``lse`` at its padded length. A
    kernel then takes the ``block_q x block_k`` with the fewest grid steps
    among the ``_TILE_SIDES`` up to each side's largest tile (all divide the
    padded side) that ``_vmem_bytes`` reckons under ``VMEM_BUDGET``; between
    shapes of one area, the one the chip's sweep read the faster: the wider
    ``block_k`` in the forward (fewer rescalings of the accumulator a query
    block), the wider ``block_q`` in both backward kernels. A side given by
    the caller is that size in every kernel."""
    q_pad, q_tile = _side(q_len, block_q, sublanes=True)
    k_pad, k_tile = _side(kv_len, block_k, sublanes=False)

    def sides(tile, given):
        if given is not None or tile not in _TILE_SIDES:
            return (tile,)
        return tuple(t for t in _TILE_SIDES if t <= tile)

    def best(kernel):
        fits = [
            (bq, bk)
            for bq in sides(q_tile, block_q) for bk in sides(k_tile, block_k)
            if _vmem_bytes(kernel, bq, bk, d_pad, itemsize) <= VMEM_BUDGET
        ] or [(sides(q_tile, block_q)[-1], sides(k_tile, block_k)[-1])]
        wide = 1 if kernel == "fwd" else 0
        return max(fits, key=lambda t: (t[0] * t[1], t[wide]))

    return _Tiling(q_pad, k_pad, best("fwd"), best("dq"), best("dkv"))


def _compiler_params(vmem_bytes: int) -> pltpu.CompilerParams:
    limit = None
    if vmem_bytes > _MOSAIC_DEFAULT_VMEM * 3 // 4:
        limit = min(vmem_bytes * 5 // 4, _VMEM_PHYSICAL * 3 // 4)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=limit,
    )


def _last_needed_key_block(
    causal, causal_offset, block_q, block_k, num_k_blocks
):
    """Index map of the key side in a grid ``(bh, q block i, k block j)``:
    ``j``, held at the last block query block ``i`` needs under causality."""
    if not causal:
        return lambda i, j: j

    def key_block(i, j):
        last = jnp.maximum(i * block_q + block_q - 1 + causal_offset, 0)
        return jnp.minimum(j, jnp.minimum(last // block_k, num_k_blocks - 1))

    return key_block


def _first_needed_query_block(
    causal, causal_offset, block_q, block_k, num_q_blocks
):
    """Index map of the query side in the dkv grid ``(bh, k block j, q block
    i)``: ``i``, held at the first block key block ``j`` needs."""
    if not causal:
        return lambda j, i: i

    def query_block(j, i):
        first = jnp.maximum(j * block_k - causal_offset, 0) // block_q
        return jnp.maximum(i, jnp.minimum(first, num_q_blocks - 1))

    return query_block


def _record_tiles(direction, q, k_pad, causal, *launches) -> None:
    """One ``ops.attention_dispatch`` record a launcher a traced program:
    which tiles each of its kernels took, on which grid, at what reckoned
    VMEM — inside a per-shard launch, of the shard."""
    from machine_learning_apache_spark_tpu.ops.attention import (
        record_dispatch,
    )

    bh, q_pad, d_pad = q.shape
    record_dispatch(
        "flash_tiles", direction,
        "; ".join(
            f"{name} {bq}x{bk} grid {'x'.join(map(str, grid))} "
            f"vmem {vmem / 2**20:.1f} MiB"
            for name, (bq, bk), grid, vmem in launches
        ) + f"; of [{bh},{q_pad},{d_pad}] x [{bh},{k_pad},{d_pad}] "
        f"{q.dtype.name}{' causal' if causal else ''}",
        kernels={
            name: dict(block_q=bq, block_k=bk, grid=grid, vmem_bytes=vmem)
            for name, (bq, bk), grid, vmem in launches
        },
        q=q.shape, k_pad=k_pad,
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    query: jnp.ndarray,
    key: jnp.ndarray,
    value: jnp.ndarray,
    *,
    causal: bool = False,
    kv_valid: jnp.ndarray | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash attention over ``[B, H, S, d]`` streams.

    Query/key lengths may differ (fixing reference quirk Q8). Head dim is
    zero-padded to the 128-lane boundary; sequence dims to the tile size —
    padding is masked inside the kernel and sliced off the output.

    ``block_q`` / ``block_k`` are the sides of a score tile, the work of one
    grid step. Left at None they are chosen for each of the three kernels
    from the lengths, the padded head dim and the operands' item size
    (``_choose_tiling``: the largest tiles of 128 to 1024 a side that pad a
    side by at most an eighth and fit ``VMEM_BUDGET``; under a
    ``kernel_mesh`` from the shard's shapes). An integer is honoured in all
    three, clamped to the side.

    ``kv_valid`` (``[B, S_k]`` bool) masks invalid keys per batch row — the
    padding-mask case of the MT model (``make_padding_mask`` semantics),
    streamed through the kernel instead of materializing ``[B, Sq, Sk]``.

    Differentiable end to end: the forward streams through the kernel and
    saves per-row log-sum-exp statistics; the backward recomputes block
    probabilities from them in two more Pallas launches (flash-2 style dq
    and dk/dv kernels) — O(S) memory in both directions, which is what makes
    long-context *training* affordable. Below
    ``ops.attention.FLASH_MIN_SCORES`` score elements a head the backward
    falls back to the fused-XLA dense recompute (cheaper than two kernel
    launches at short sequence lengths). ``dot_product_attention``'s
    auto-dispatch sends such a site to the dense path in the forward too, so
    that hybrid is reached only by calling this function directly.
    """
    cfg = (causal, block_q, block_k, interpret)
    if kv_valid is None:
        return _flash_vjp_nomask(cfg, query, key, value)
    return _flash_vjp_masked(cfg, query, key, value, kv_valid)


def _dense_reference(query, key, value, causal, kv_valid):
    from machine_learning_apache_spark_tpu.ops.attention import (
        dot_product_attention,
    )

    # One source of truth for structured→dense mask semantics.
    return dot_product_attention(
        query, key, value, causal=causal, kv_valid=kv_valid, use_pallas=False
    )


def _use_pallas_bwd(q_len: int, kv_len: int) -> bool:
    """Under ``ops.attention.FLASH_MIN_SCORES`` scores a head the fused-XLA
    dense recompute is both affordable and faster than a second kernel
    launch pair; from it on the blockwise backward avoids materializing
    [S_q, S_k] chains entirely (the long-context training seam). The same
    number gates the forward in ``dot_product_attention``'s auto-dispatch,
    so only a caller that forces the kernel reaches the flash forward with
    the dense backward."""
    from machine_learning_apache_spark_tpu.ops.attention import flash_pays

    return flash_pays(q_len, kv_len)[0]


def _choose_bwd(q_len: int, kv_len: int) -> bool:
    """``_use_pallas_bwd`` for the custom_vjp forward rules, which run once
    per traced differentiated program: records the choice."""
    from machine_learning_apache_spark_tpu.ops.attention import (
        flash_pays,
        record_dispatch,
    )

    use, reason = flash_pays(q_len, kv_len)
    record_dispatch(
        "flash_backward",
        "pallas_flash_bwd" if use else "xla_dense_recompute",
        reason,
        q_len=q_len, kv_len=kv_len,
    )
    return use


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_vjp_nomask(cfg, query, key, value):
    return _flash_forward(query, key, value, None, *cfg)


def _flash_nomask_fwd(cfg, query, key, value):
    # The out/lse residuals are only kept when the pallas backward will read
    # them (shape-static decision); the short-sequence dense fallback keeps
    # the lean (q, k, v) residuals and skips the lse output entirely.
    if _choose_bwd(query.shape[2], key.shape[2]):
        out, lse = _flash_forward(
            query, key, value, None, *cfg, return_lse=True
        )
        return out, (query, key, value, out, lse)
    return _flash_vjp_nomask(cfg, query, key, value), (query, key, value, None, None)


def _flash_nomask_bwd(cfg, res, g):
    query, key, value, out, lse = res
    if _use_pallas_bwd(query.shape[2], key.shape[2]):
        return _flash_backward(cfg, query, key, value, None, out, lse, g)
    _, vjp = jax.vjp(
        lambda q, k, v: _dense_reference(q, k, v, cfg[0], None),
        query, key, value,
    )
    return vjp(g)


_flash_vjp_nomask.defvjp(_flash_nomask_fwd, _flash_nomask_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_vjp_masked(cfg, query, key, value, kv_valid):
    return _flash_forward(query, key, value, kv_valid, *cfg)


def _flash_masked_fwd(cfg, query, key, value, kv_valid):
    if _choose_bwd(query.shape[2], key.shape[2]):
        out, lse = _flash_forward(
            query, key, value, kv_valid, *cfg, return_lse=True
        )
        return out, (query, key, value, kv_valid, out, lse)
    return (
        _flash_vjp_masked(cfg, query, key, value, kv_valid),
        (query, key, value, kv_valid, None, None),
    )


def _flash_masked_bwd(cfg, res, g):
    query, key, value, kv_valid, out, lse = res
    if _use_pallas_bwd(query.shape[2], key.shape[2]):
        return (
            *_flash_backward(cfg, query, key, value, kv_valid, out, lse, g),
            None,
        )
    _, vjp = jax.vjp(
        lambda q, k, v: _dense_reference(q, k, v, cfg[0], kv_valid),
        query, key, value,
    )
    return (*vjp(g), None)


_flash_vjp_masked.defvjp(_flash_masked_fwd, _flash_masked_bwd)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
    has_kv_valid: bool, causal: bool, causal_offset: int,
    q_len: int, kv_len: int, q_pad: int, k_pad: int,
    block_q: int, block_k: int, num_k_blocks: int, scale: float,
):
    """dQ = Σ_j dS_ij @ K_j, streaming K/V blocks (flash-2 backward, q side).

    Probabilities are recomputed per block from the saved row normalizer
    (``lse``) — no [S_q, S_k] tensor is ever read or written.
    """
    if has_kv_valid:
        kv_valid_ref, dq_ref, dq_scr = refs
    else:
        kv_valid_ref = None
        dq_ref, dq_scr = refs
    i = pl.program_id(1)  # query block
    j = pl.program_id(2)  # key block (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    needed, unmasked = _tile_predicates(
        i, j, causal=causal, causal_offset=causal_offset, q_len=q_len,
        kv_len=kv_len, q_pad=q_pad, k_pad=k_pad, block_q=block_q,
        block_k=block_k, has_kv_valid=has_kv_valid,
    )

    def _block(masked: bool):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0]      # [block_q, 1]
        delta = delta_ref[0]  # [block_q, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        p = jnp.exp(s - lse)
        if masked:
            k_idx = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            q_idx = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            mask = (k_idx < kv_len) & (q_idx < q_len)
            if has_kv_valid:
                mask = mask & (kv_valid_ref[0] != 0)
            if causal:
                mask = mask & (k_idx <= q_idx + causal_offset)
            # Fully-masked rows carry lse == NEG_INF, where the exp above
            # overflowed to inf: the select drops it.
            mask = mask & (lse > NEG_INF * 0.5)
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _run_tile(needed, unmasked, _block)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
    has_kv_valid: bool, causal: bool, causal_offset: int,
    q_len: int, kv_len: int, q_pad: int, k_pad: int,
    block_q: int, block_k: int, num_q_blocks: int, scale: float,
):
    """dK_j = Σ_i dSᵀ_ij @ Q_i, dV_j = Σ_i Pᵀ_ij @ dO_i — the k/v side,
    streaming Q/dO blocks with scores computed transposed ([block_k,
    block_q]) so both accumulators live in k-block scratch."""
    if has_kv_valid:
        kv_valid_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        kv_valid_ref = None
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    j = pl.program_id(1)  # key block
    i = pl.program_id(2)  # query block (innermost, sequential)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed, unmasked = _tile_predicates(
        i, j, causal=causal, causal_offset=causal_offset, q_len=q_len,
        kv_len=kv_len, q_pad=q_pad, k_pad=k_pad, block_q=block_q,
        block_k=block_k, has_kv_valid=has_kv_valid,
    )

    def _block(masked: bool):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0]      # [1, block_q] (row layout over q columns)
        delta = delta_ref[0]  # [1, block_q]
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        p_t = jnp.exp(s_t - lse)
        if masked:
            k_idx = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0
            )
            q_idx = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1
            )
            mask = (k_idx < kv_len) & (q_idx < q_len)
            if has_kv_valid:
                # [block_k, 1] column layout
                mask = mask & (kv_valid_ref[0] != 0)
            if causal:
                mask = mask & (k_idx <= q_idx + causal_offset)
            mask = mask & (lse > NEG_INF * 0.5)
            p_t = jnp.where(mask, p_t, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds_t = p_t * (dp_t - delta)
        dk_scr[:] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _run_tile(needed, unmasked, _block)

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# -- per-shard launch --------------------------------------------------------
#
# A Mosaic custom call is opaque to the SPMD partitioner: under ``fit``'s
# implicit data parallelism (batch sharded over the mesh, XLA does the rest)
# lowering fails with "Mosaic kernels cannot be automatically partitioned.
# Please wrap the call in a shard_map". Attention is independent per
# (batch, head), so under an ``ops.attention.kernel_mesh(mesh)`` context —
# ``fit``/``evaluate`` enter it for the mesh they were given — the launchers
# run inside a ``shard_map`` over the mesh's data axis (batch) and model
# axis (heads): each device launches the kernel on its own
# [B/n, H/m, S, d] block and nothing is gathered. Without a context the
# kernel is called directly, which is right for a single-device program
# and for code already inside a fully-manual ``shard_map``.


def _per_shard(local_fn, operands, kinds, out_kinds):
    """``local_fn(*operands)``, per (batch, head) shard of the active kernel
    mesh. ``kinds`` name each operand's layout — ``"bhsd"`` ([B, H, S, d]),
    ``"bs"`` ([B, S]), ``"bhs"`` ([B, H, S]) — or None for an absent one."""
    from machine_learning_apache_spark_tpu.ops.attention import (
        active_kernel_mesh,
    )
    from machine_learning_apache_spark_tpu.parallel.mesh import (
        DATA_AXIS,
        MODEL_AXIS,
    )

    mesh = active_kernel_mesh()
    manual = frozenset(jax.sharding.get_abstract_mesh().manual_axes)
    if mesh is None or not frozenset(mesh.axis_names) - manual:
        return local_fn(*operands)
    batch, heads = operands[0].shape[:2]

    def axis_for(name, size):
        # An axis that does not divide the dim (a ragged eval tail) leaves
        # it whole: every shard then computes all of it.
        ok = name in mesh.shape and name not in manual
        return name if ok and size % mesh.shape[name] == 0 else None

    b, h = axis_for(DATA_AXIS, batch), axis_for(MODEL_AXIS, heads)
    specs = {
        "bhsd": P(b, h, None, None), "bs": P(b, None), "bhs": P(b, h, None),
        None: None,
    }
    out_specs = tuple(specs[k] for k in out_kinds)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=tuple(specs[k] for k in kinds),
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        # The kernel's outputs depend on nothing but its operands' shards;
        # there is no replication for the checker to infer through an
        # opaque custom call.
        check_vma=False,
    )(*operands)


def _flash_backward(cfg, query, key, value, kv_valid, out, lse, g):
    return _per_shard(
        functools.partial(_flash_backward_local, cfg),
        (query, key, value, kv_valid, out, lse, g),
        ("bhsd", "bhsd", "bhsd", None if kv_valid is None else "bs",
         "bhsd", "bhs", "bhsd"),
        ("bhsd", "bhsd", "bhsd"),
    )


def _flash_backward_local(cfg, query, key, value, kv_valid, out, lse, g):
    """Blockwise dq/dk/dv (flash-2): two kernel launches, O(S) memory.

    ``lse`` arrives [B, H, q_pad] from the forward: ``q_pad`` comes from
    ``_choose_tiling``, which pads a side by the lengths and the caller's
    ``block_q`` / ``block_k`` alone, never by the kernel, and both launchers
    hand it the same ones. ``delta = rowsum(dO ∘ O)`` is a cheap fused XLA
    reduction computed here, not a kernel.
    """
    causal, block_q, block_k, interpret = cfg
    b, h, q_len, d = query.shape
    kv_len = key.shape[2]
    scale = 1.0 / math.sqrt(d)
    d_pad = _round_up(d, _LANES)
    tiling = _choose_tiling(
        q_len, kv_len, d_pad, query.dtype.itemsize, block_q, block_k
    )
    q_pad, k_pad = tiling.q_pad, tiling.k_pad

    q = _pad_to(_pad_to(query, 2, q_pad), 3, d_pad)
    k = _pad_to(_pad_to(key, 2, k_pad), 3, d_pad)
    v = _pad_to(_pad_to(value, 2, k_pad), 3, d_pad)
    do = _pad_to(_pad_to(g, 2, q_pad), 3, d_pad).astype(query.dtype)
    bh = b * h
    q = q.reshape(bh, q_pad, d_pad)
    k = k.reshape(bh, k_pad, d_pad)
    v = v.reshape(bh, k_pad, d_pad)
    do = do.reshape(bh, q_pad, d_pad)

    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(bh, q_len)
    delta = _pad_to(delta, 1, q_pad)

    # Column ([.., q_pad, 1]) and row ([.., 1, q_pad]) layouts of the per-row
    # statistics: the dq kernel broadcasts them down k columns, the dkv
    # kernel across q columns — Mosaic-friendly 2D blocks either way.
    lse = lse.reshape(bh, q_pad)
    lse_col, delta_col = lse[:, :, None], delta[:, :, None]
    lse_row, delta_row = lse[:, None, :], delta[:, None, :]

    common = dict(
        causal=causal,
        causal_offset=kv_len - q_len,
        q_len=q_len,
        kv_len=kv_len,
        q_pad=q_pad,
        k_pad=k_pad,
        scale=scale,
        has_kv_valid=kv_valid is not None,
    )
    if kv_valid is not None:
        valid = _pad_to(kv_valid.astype(jnp.int32), 1, k_pad)

    # dq grid (bh, q blocks, k blocks): the key block of a causally skipped
    # step is the last one its query block needs, so the pipeline sees an
    # unchanged index and fetches nothing.
    block_q, block_k = tiling.dq
    num_q_blocks, num_k_blocks = q_pad // block_q, k_pad // block_k
    key_block = _last_needed_key_block(
        causal, kv_len - q_len, block_q, block_k, num_k_blocks
    )
    dq_operands = [q, k, v, do, lse_col, delta_col]
    dq_specs = [
        pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec(
            (1, block_k, d_pad), lambda b, i, j: (b, key_block(i, j), 0)
        ),
        pl.BlockSpec(
            (1, block_k, d_pad), lambda b, i, j: (b, key_block(i, j), 0)
        ),
        pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    if kv_valid is not None:
        dq_operands.append(valid[:, None, :])
        dq_specs.append(
            pl.BlockSpec(
                (1, 1, block_k),
                lambda b, i, j, h=h: (b // h, 0, key_block(i, j)),
            )
        )
    dq_vmem = _vmem_bytes("dq", block_q, block_k, d_pad, q.dtype.itemsize)
    dq_grid = (bh, num_q_blocks, num_k_blocks)
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, num_k_blocks=num_k_blocks,
            block_q=block_q, block_k=block_k, **common
        ),
        grid=dq_grid,
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((bh, q_pad, d_pad), query.dtype, *dq_operands),
        scratch_shapes=[pltpu.VMEM((block_q, d_pad), jnp.float32)],
        compiler_params=_compiler_params(dq_vmem),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*dq_operands)

    # dkv grid: key blocks in the middle (parallel), query blocks innermost
    # (sequential) so the dk/dv accumulators persist across the q sweep. The
    # causally skipped steps come first here: their query block is the first
    # one the key block needs.
    block_q, block_k = tiling.dkv
    num_q_blocks, num_k_blocks = q_pad // block_q, k_pad // block_k
    query_block = _first_needed_query_block(
        causal, kv_len - q_len, block_q, block_k, num_q_blocks
    )
    dkv_operands = [q, k, v, do, lse_row, delta_row]
    dkv_specs = [
        pl.BlockSpec(
            (1, block_q, d_pad), lambda b, j, i: (b, query_block(j, i), 0)
        ),
        pl.BlockSpec((1, block_k, d_pad), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d_pad), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec(
            (1, block_q, d_pad), lambda b, j, i: (b, query_block(j, i), 0)
        ),
        pl.BlockSpec(
            (1, 1, block_q), lambda b, j, i: (b, 0, query_block(j, i))
        ),
        pl.BlockSpec(
            (1, 1, block_q), lambda b, j, i: (b, 0, query_block(j, i))
        ),
    ]
    if kv_valid is not None:
        dkv_operands.append(valid[:, :, None])
        dkv_specs.append(
            pl.BlockSpec((1, block_k, 1), lambda b, j, i, h=h: (b // h, j, 0))
        )
    dkv_vmem = _vmem_bytes("dkv", block_q, block_k, d_pad, q.dtype.itemsize)
    dkv_grid = (bh, num_k_blocks, num_q_blocks)
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, num_q_blocks=num_q_blocks,
            block_q=block_q, block_k=block_k, **common
        ),
        grid=dkv_grid,
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d_pad), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((bh, k_pad, d_pad), key.dtype, *dkv_operands),
            _out_struct((bh, k_pad, d_pad), value.dtype, *dkv_operands),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d_pad), jnp.float32),
            pltpu.VMEM((block_k, d_pad), jnp.float32),
        ],
        compiler_params=_compiler_params(dkv_vmem),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*dkv_operands)
    _record_tiles(
        "backward", q, k_pad, causal,
        ("flash_bwd_dq", tiling.dq, dq_grid, dq_vmem),
        ("flash_bwd_dkv", tiling.dkv, dkv_grid, dkv_vmem),
    )

    dq = dq.reshape(b, h, q_pad, d_pad)[:, :, :q_len, :d]
    dk = dk.reshape(b, h, k_pad, d_pad)[:, :, :kv_len, :d]
    dv = dv.reshape(b, h, k_pad, d_pad)[:, :, :kv_len, :d]
    return dq, dk, dv


def _flash_forward(
    query, key, value, kv_valid, causal, block_q, block_k, interpret,
    return_lse: bool = False,
):
    def local(query, key, value, kv_valid):
        return _flash_forward_local(
            query, key, value, kv_valid, causal, block_q, block_k,
            interpret, return_lse,
        )

    return _per_shard(
        local,
        (query, key, value, kv_valid),
        ("bhsd", "bhsd", "bhsd", None if kv_valid is None else "bs"),
        ("bhsd", "bhs") if return_lse else ("bhsd",),
    )


def _flash_forward_local(
    query, key, value, kv_valid, causal, block_q, block_k, interpret,
    return_lse,
):
    b, h, q_len, d = query.shape
    kv_len = key.shape[2]
    scale = 1.0 / math.sqrt(d)
    d_pad = _round_up(d, _LANES)
    tiling = _choose_tiling(
        q_len, kv_len, d_pad, query.dtype.itemsize, block_q, block_k
    )
    q_pad, k_pad = tiling.q_pad, tiling.k_pad
    block_q, block_k = tiling.fwd

    q = _pad_to(_pad_to(query, 2, q_pad), 3, d_pad)
    k = _pad_to(_pad_to(key, 2, k_pad), 3, d_pad)
    v = _pad_to(_pad_to(value, 2, k_pad), 3, d_pad)

    bh = b * h
    q = q.reshape(bh, q_pad, d_pad)
    k = k.reshape(bh, k_pad, d_pad)
    v = v.reshape(bh, k_pad, d_pad)
    num_q_blocks = q_pad // block_q
    num_k_blocks = k_pad // block_k
    # The key block of a causally skipped step is the last one its query
    # block needs: the pipeline sees an unchanged index and fetches nothing.
    key_block = _last_needed_key_block(
        causal, kv_len - q_len, block_q, block_k, num_k_blocks
    )

    operands = [q, k, v]
    valid_specs = []
    if kv_valid is not None:
        if kv_valid.shape != (b, kv_len):
            raise ValueError(
                f"kv_valid must be [batch={b}, kv_len={kv_len}], "
                f"got {kv_valid.shape}"
            )
        # [B, 1, k_pad]: a singleton middle dim keeps the TPU block tiling
        # legal (block dim -2 == array dim -2); batch row = grid0 // heads.
        operands.append(
            _pad_to(kv_valid.astype(jnp.int32), 1, k_pad)[:, None, :]
        )
        valid_specs.append(
            pl.BlockSpec(
                (1, 1, block_k),
                lambda bh_i, i, j, h=h: (bh_i // h, 0, key_block(i, j)),
            )
        )

    kernel = functools.partial(
        _flash_kernel,
        has_kv_valid=kv_valid is not None,
        return_lse=return_lse,
        causal=causal,
        causal_offset=kv_len - q_len,
        kv_len=kv_len,
        k_pad=k_pad,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=num_k_blocks,
        scale=scale,
    )
    out_specs = [pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0))]
    out_shape = [_out_struct((bh, q_pad, d_pad), query.dtype, *operands)]
    if return_lse:
        # Column layout [B*H, q_pad, 1]: the kernel's running statistics
        # are (block_q, 1) columns, and a (1, block_q) block over a 2-D
        # [B*H, q_pad] array has a second-minor dim of 1 that neither
        # divides by 8 nor equals the array dim -- Mosaic rejects it.
        out_specs.append(
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
        )
        out_shape.append(
            _out_struct((bh, q_pad, 1), jnp.float32, *operands)
        )
    vmem = _vmem_bytes("fwd", block_q, block_k, d_pad, q.dtype.itemsize)
    grid = (bh, num_q_blocks, num_k_blocks)
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec(
                (1, block_k, d_pad), lambda b, i, j: (b, key_block(i, j), 0)
            ),
            pl.BlockSpec(
                (1, block_k, d_pad), lambda b, i, j: (b, key_block(i, j), 0)
            ),
            *valid_specs,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_pad), jnp.float32),
        ],
        compiler_params=_compiler_params(vmem),
        interpret=interpret,
        name="flash_fwd",
    )(*operands)
    _record_tiles(
        "forward", q, k_pad, causal, ("flash_fwd", tiling.fwd, grid, vmem)
    )

    out = res[0].reshape(b, h, q_pad, d_pad)[:, :, :q_len, :d]
    if return_lse:
        # lse leaves as [B, H, q_pad] so a per-shard launch can split its
        # batch and head dims; the backward flattens it again.
        return out, res[1].reshape(b, h, q_pad)
    return out


# -- ragged paged-attention decode kernel ------------------------------------
#
# One query vector per request row, K/V gathered page-by-page through a
# block table (Ragged Paged Attention, arxiv 2604.15464). The block table
# and per-request lengths ride in as *scalar-prefetch* operands: the
# index_map of the K/V page operands reads `tbl[r, p]`, so the page DMA is
# data-dependent — the grid walks (request, page) but the pages fetched are
# whatever the allocator handed that request, in order. Pages past a
# request's length (block-table zero padding → the null page) are skipped
# by `pl.when` and their lanes masked, so arbitrary raggedness — including
# fully-inactive rows with length 0 — runs in the one compiled program.


def _ragged_paged_kernel(
    tbl_ref,  # scalar prefetch: [R, P] int32 block table
    len_ref,  # scalar prefetch: [R] int32 cached lengths
    q_ref,
    k_ref,
    v_ref,
    *refs,
    has_scale: bool,
    has_cur: bool,
    num_heads: int,
    heads_padded: int,
    head_dim: int,
    page_size: int,
    num_page_steps: int,
    scale: float,
):
    refs = list(refs)
    ks_ref = refs.pop(0) if has_scale else None
    vs_ref = refs.pop(0) if has_scale else None
    cur_k_ref = refs.pop(0) if has_cur else None
    cur_v_ref = refs.pop(0) if has_cur else None
    o_ref = refs.pop(0)
    m_scr, l_scr, acc_scr = refs
    r = pl.program_id(0)  # request row
    p = pl.program_id(1)  # page step (innermost, sequential)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[r]

    def _scores(keys, width):
        # Per-head block-diagonal q·kᵀ: the page store keeps heads packed
        # in the lane dim ([page, H*dh]), so each head is a static lane
        # slice — no in-kernel reshape/transpose of the DMA'd page.
        rows = [
            jax.lax.dot_general(
                q_ref[0][h : h + 1, :],
                keys[:, h * head_dim : (h + 1) * head_dim],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in range(num_heads)
        ]
        if heads_padded > num_heads:
            rows.append(
                jnp.full(
                    (heads_padded - num_heads, width), NEG_INF, jnp.float32
                )
            )
        return jnp.concatenate(rows, axis=0) * scale  # [Hs, width]

    def _weighted_values(probs, values, width):
        rows = [
            jax.lax.dot_general(
                probs[h : h + 1, :],
                values[:, h * head_dim : (h + 1) * head_dim],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in range(num_heads)
        ]
        if heads_padded > num_heads:
            rows.append(
                jnp.zeros((heads_padded - num_heads, head_dim), jnp.float32)
            )
        return jnp.concatenate(rows, axis=0)  # [Hs, dh]

    def _fold(s, mask, values, width):
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Explicit zero where masked: a row whose running max is still
        # NEG_INF would otherwise see exp(0)=1 and silently average V.
        pr = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(pr, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _weighted_values(pr, values, width)
        m_scr[:] = m_cur

    @pl.when(p * page_size < length)
    def _page():
        k_idx = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        keys, values = k_ref[0], v_ref[0]
        if has_scale:
            # Dequantize the page *before* the dots — same order as the
            # XLA fallback, so kernel and fallback agree to float
            # rounding. Scales are per page-slot, broadcast over lanes.
            keys = keys.astype(jnp.float32) * ks_ref[0]
            values = values.astype(jnp.float32) * vs_ref[0]
        _fold(
            _scores(keys, page_size),
            k_idx < length,
            values,
            page_size,
        )

    @pl.when(p == num_page_steps - 1)
    def _finalize():
        if has_cur:
            # The current step's K/V — the causal diagonal — always valid,
            # folded once after the cached pages. Padded head rows carry
            # s == NEG_INF == m, so their weight exp(0) lands on zero
            # values and the l=1 denominator still emits zeros.
            _fold(
                _scores(cur_k_ref[0], 1),
                jnp.ones((1, 1), dtype=bool),
                cur_v_ref[0],
                1,
            )
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def ragged_paged_attention_kernel(
    query: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    cur_k: jnp.ndarray | None = None,
    cur_v: jnp.ndarray | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas form of ``ops.attention.ragged_paged_attention`` (see there
    for the contract). Grid ``(requests, page_steps)`` with the page axis
    sequential so the online-softmax scratch survives a request's sweep;
    K/V operands are one page per step, addressed through the
    scalar-prefetched block table — and so are the optional per-slot
    dequantization scales, which ride the *same* ``tbl[r, p]`` index map
    as their pages. On TPU this wants ``dh % 128 == 0`` and
    ``page_size % 8 == 0`` (``% 32`` for int8 pages — the dispatcher's
    gate); interpret mode (CPU tests) takes any shape."""
    num_rows, num_heads, head_dim = query.shape
    page_size, d_model = k_pages.shape[1], k_pages.shape[2]
    pages_per_req = block_table.shape[1]
    heads_padded = max(8, num_heads)
    if heads_padded > num_heads:
        query = jnp.pad(
            query, ((0, 0), (0, heads_padded - num_heads), (0, 0))
        )

    in_specs = [
        pl.BlockSpec(
            (1, heads_padded, head_dim), lambda r, p, tbl, lens: (r, 0, 0)
        ),
        pl.BlockSpec(
            (1, page_size, d_model),
            lambda r, p, tbl, lens: (tbl[r, p], 0, 0),
        ),
        pl.BlockSpec(
            (1, page_size, d_model),
            lambda r, p, tbl, lens: (tbl[r, p], 0, 0),
        ),
    ]
    operands = [
        block_table.astype(jnp.int32),
        lengths.astype(jnp.int32),
        query,
        k_pages,
        v_pages,
    ]
    if k_scale is not None:
        # Column layout [num_pages, page_size, 1]: one scale per sublane
        # of the page block it dequantizes, and every block dim equals
        # its array dim (a (1, page_size) block over [num_pages,
        # page_size] has an illegal second-minor dim of 1).
        operands += [
            k_scale.astype(jnp.float32)[:, :, None],
            v_scale.astype(jnp.float32)[:, :, None],
        ]
        in_specs += [
            pl.BlockSpec(
                (1, page_size, 1), lambda r, p, tbl, lens: (tbl[r, p], 0, 0)
            ),
            pl.BlockSpec(
                (1, page_size, 1), lambda r, p, tbl, lens: (tbl[r, p], 0, 0)
            ),
        ]
    if cur_k is not None:
        operands += [cur_k[:, None, :], cur_v[:, None, :]]
        in_specs += [
            pl.BlockSpec((1, 1, d_model), lambda r, p, tbl, lens: (r, 0, 0)),
            pl.BlockSpec((1, 1, d_model), lambda r, p, tbl, lens: (r, 0, 0)),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_rows, pages_per_req),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, heads_padded, head_dim), lambda r, p, tbl, lens: (r, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((heads_padded, 1), jnp.float32),
            pltpu.VMEM((heads_padded, 1), jnp.float32),
            pltpu.VMEM((heads_padded, head_dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _ragged_paged_kernel,
            has_scale=k_scale is not None,
            has_cur=cur_k is not None,
            num_heads=num_heads,
            heads_padded=heads_padded,
            head_dim=head_dim,
            page_size=page_size,
            num_page_steps=pages_per_req,
            scale=1.0 / math.sqrt(head_dim),
        ),
        grid_spec=grid_spec,
        out_shape=_out_struct(
            (num_rows, heads_padded, head_dim), query.dtype, *operands
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ragged_paged_decode",
    )(*operands)
    return out[:, :num_heads]

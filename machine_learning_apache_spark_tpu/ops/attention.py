"""Scaled dot-product attention — the framework's hot kernel.

The reference's innermost compute (``scaled_dot_product``,
``transformer.py:12-25``) is QKᵀ/√d → mask → softmax → ·V. Correct-semantics
build (SURVEY.md quirk Q9 fixed): boolean mask (True = attendable) applied as
``where(mask, scores, -inf)`` *before* softmax, no permutes, and query/key
lengths are independent (Q8 fixed).

Two implementations behind one signature:

- ``scaled_dot_product_attention`` — pure ``jnp``; XLA fuses the softmax chain
  and tiles the matmuls onto the MXU. Works on every backend.
- ``machine_learning_apache_spark_tpu.ops.pallas_attention.flash_attention`` —
  blockwise online-softmax Pallas kernel for TPU (never materializes the
  [S, S] score matrix).

``dot_product_attention`` chooses between them per site from the operand
shapes: on TPU a structured-mask site of ``FLASH_MIN_SCORES`` scores a head
or more runs the kernel, forward and backward; a shorter one runs the dense
path in both directions (the kernel's launches cost more than the score
matrix they avoid).

The blockwise structure is the design seam for ring/sequence-parallel
attention (SURVEY.md §5 long-context): the same per-block accumulator runs
under ``shard_map`` with K/V blocks rotating over ICI
(``parallel/ring_attention.py``).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu import telemetry

NEG_INF = -1e30  # finite -inf stand-in: keeps fully-masked rows NaN-free

# Scores a head (q_len * kv_len) from which a structured-mask site runs the
# flash kernel, forward and backward alike; under it the fused-XLA dense
# path is both affordable and faster than the kernel's launches. A sweep on
# a v5e (PERF.md section 6, PR 25; tools/attention_gate_sweep.py) read dense
# the faster well past this number at the sweep's token budget, but the gate
# sees scores a head, not rows: from here the float32 [B, H, Sq, Sk]
# temporaries can decide, and the kernel's backward takes O(S) memory.
FLASH_MIN_SCORES = 256 * 1024


def flash_pays(q_len: int, kv_len: int) -> tuple[bool, str]:
    """Whether a site of these lengths is at or over ``FLASH_MIN_SCORES``,
    and the comparison spelled out for ``record_dispatch``."""
    pays = q_len * kv_len >= FLASH_MIN_SCORES
    return pays, (
        f"{q_len}x{kv_len} scores {'>=' if pays else '<'} {FLASH_MIN_SCORES}"
    )


def record_dispatch(site: str, impl: str, reason: str, **shape) -> None:
    """Trace-time breadcrumb: which implementation an attention site
    compiled to, and why. The dispatch gates below choose silently; this
    runs in the Python body under ``jit``, so it leaves one
    ``ops.attention_dispatch`` annotation per site per traced program
    (never per call) for a bring-up or a flight dump to read."""
    telemetry.annotate(
        "ops.attention_dispatch", site=site, impl=impl, reason=reason, **shape
    )


# Active sequence-parallel context (a stack so contexts nest): while set,
# ``dot_product_attention`` routes self-attention through the ppermute ring
# over the mesh's "seq" axis — the model code never changes (SURVEY.md §5
# long-context seam).
_SEQ_PARALLEL_CTX: list[tuple] = []


@contextlib.contextmanager
def sequence_parallel(
    mesh,
    *,
    seq_axis: str = "seq",
    batch_axis: str = "data",
    method: str = "ring",
):
    """Route zoo self-attention through sequence-parallel attention on
    ``mesh`` — ``method="ring"`` (K/V chunks rotate via ``ppermute``; any
    head count) or ``method="ulysses"`` (head↔sequence ``all_to_all``;
    needs ``num_heads % seq_axis_size == 0`` — see
    ``parallel.ulysses_attention`` for the trade).

    Usage (a dp×sp mesh; no model change):

    >>> with sequence_parallel(mesh):
    ...     result = fit(state, loss_fn, loader, mesh=mesh, ...)

    Dispatch per attention site (see ``dot_product_attention``): structured-
    mask self-attention whose sequence length divides the ``seq_axis`` size
    goes through the selected mechanism; cross-attention, decode steps, and
    dense-mask sites fall through to their usual paths.
    """
    if seq_axis not in mesh.shape:
        raise ValueError(f"mesh {dict(mesh.shape)} has no '{seq_axis}' axis")
    if method not in ("ring", "ulysses"):
        raise ValueError(
            f"method must be 'ring' or 'ulysses', got {method!r}"
        )
    _SEQ_PARALLEL_CTX.append((mesh, seq_axis, batch_axis, method))
    try:
        yield
    finally:
        _SEQ_PARALLEL_CTX.pop()


def _active_seq_mesh():
    return _SEQ_PARALLEL_CTX[-1] if _SEQ_PARALLEL_CTX else None


# Mesh the surrounding jitted program spans (a stack so contexts nest).
# A Pallas kernel cannot be partitioned by XLA, so its launcher must know
# the mesh at trace time to run itself per shard
# (``ops.pallas_attention._per_shard``).
_KERNEL_MESH: list = []


@contextlib.contextmanager
def kernel_mesh(mesh):
    """While active, Pallas kernel launches traced under ``jit`` run per
    shard of ``mesh`` (batch over its data axis, heads over its model
    axis) instead of as one unpartitionable call. ``fit`` and ``evaluate``
    enter it for the mesh they are given; code that jits the zoo models
    over sharded batches itself (``bench.py``) does the same. ``None``
    (a mesh-less ``fit``) means direct launches."""
    _KERNEL_MESH.append(mesh)
    try:
        yield
    finally:
        _KERNEL_MESH.pop()


def active_kernel_mesh():
    return _KERNEL_MESH[-1] if _KERNEL_MESH else None


# Forced implementation override for ``dot_product_attention``'s auto
# dispatch (a stack so contexts nest). None = auto (on TPU, flash for
# structured masks from ``FLASH_MIN_SCORES`` scores a head; dense-XLA
# otherwise).
_FORCED_IMPL: list[str] = []


@contextlib.contextmanager
def attention_impl(impl: str):
    """Pin the structured-mask attention implementation inside the block:
    ``"dense"`` (materialized-[Sq,Sk] XLA path) or ``"flash"`` (blockwise
    Pallas kernel). Benchmarking/debugging hook — e.g. the long-context
    bench measures the flash kernel against the dense path it replaces
    (the reference's ``transformer.py:12-25`` core) at each sequence
    length. Sites the override cannot serve keep their rules: dense-mask
    calls never go to flash, and an active ``sequence_parallel`` context
    still wins.
    """
    if impl not in ("dense", "flash"):
        raise ValueError(f"impl must be 'dense' or 'flash', got {impl!r}")
    _FORCED_IMPL.append(impl)
    try:
        yield
    finally:
        _FORCED_IMPL.pop()


def _weights_from_scores(scores, d_k: int, mask, dtype) -> jnp.ndarray:
    scores = scores / jnp.sqrt(jnp.asarray(d_k, dtype=scores.dtype))
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    # Softmax in float32 regardless of compute dtype: bfloat16 exp/renorm
    # loses enough precision to hurt training at long sequence lengths.
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return weights.astype(dtype)


def multi_head_attention_weights(
    query: jnp.ndarray,
    key: jnp.ndarray,
    mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """``softmax(QKᵀ/√d)`` with boolean masking — the first half of
    ``scaled_dot_product`` (``transformer.py:17-24``), returned separately
    because the reference also returns the attention map."""
    scores = jnp.einsum("...qd,...kd->...qk", query, key)
    return _weights_from_scores(scores, query.shape[-1], mask, query.dtype)


def scaled_dot_product_attention(
    query: jnp.ndarray,
    key: jnp.ndarray,
    value: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    *,
    return_weights: bool = False,
):
    """Attention over ``[..., S, d]`` streams (typically ``[B, H, S, d]``).

    ``mask`` is boolean, True = attendable, broadcastable to
    ``[..., Sq, Sk]``. Query and key sequence lengths may differ (the
    cross-attention case the reference mis-handles, Q8).
    """
    weights = multi_head_attention_weights(query, key, mask)
    values = jnp.einsum("...qk,...kd->...qd", weights, value)
    if return_weights:
        return values, weights
    return values


def ragged_paged_attention(
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
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """One decode step of attention over a **paged** KV cache, ragged
    across the batch (Ragged Paged Attention, arxiv 2604.15464).

    Every request ``r`` attends its single query vector over the first
    ``lengths[r]`` cached positions, gathered page-by-page through its
    block table — so one compiled program serves any mix of sequence
    lengths and any batch occupancy (empty rows have ``lengths == 0``
    and a null block table).

    - ``query`` — ``[R, H, dh]``, one position per request row;
    - ``k_pages`` / ``v_pages`` — ``[num_pages, page_size, H*dh]``, the
      shared page store (page 0 is the never-allocated null page); may
      be ``int8`` when paired with ``k_scale``/``v_scale``;
    - ``block_table`` — ``[R, P]`` int32 page ids, zero-padded past each
      request's pages;
    - ``lengths`` — ``[R]`` int32 valid cached positions (0 = inactive);
    - ``k_scale`` / ``v_scale`` — optional ``[num_pages, page_size]``
      float32 dequantization scales for quantized page stores: slot
      ``(p, s)`` of the store dequantizes as ``pages[p, s] * scale[p, s]``.
      Scales ride the same block-table indirection as the pages, so a
      shared prefix page carries its scale to every reader;
    - ``cur_k`` / ``cur_v`` — optional ``[R, H*dh]``: the current step's
      K/V, attended unconditionally (the causal diagonal) *in addition*
      to the cached positions — this lets the caller run attention and
      the cache scatter in the same fused step without a read-after-write
      hazard on the page store. Always full-precision (never quantized).

    Dispatch mirrors ``dot_product_attention``: a Pallas TPU kernel
    whose block tables drive data-dependent page DMA when the layout
    allows it (``dh % 128 == 0``, ``page_size % 8 == 0`` for fp32 pages
    or ``page_size % 32 == 0`` for int8 pages — the int8 min-tile
    sublane count), otherwise a bit-equivalent gather + masked-softmax
    XLA path (the CPU tier-1 route, same fallback discipline as PR 7's
    native parsers). Both paths dequantize to float32 *before* the dot
    products, so kernel and fallback agree to float rounding.
    """
    num_rows, num_heads, head_dim = query.shape
    page_size = k_pages.shape[1]
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    if use_pallas is None:
        # int8 pages tile at (32, 128) on TPU, fp32 at (8, 128) — the
        # page_size divisibility gate follows the store dtype. bfloat16
        # pages (the chip's store: the model dtype) share the 8 gate:
        # Mosaic compiled 8-slot bf16 pages and agreed with the gather
        # path on a v5e (PERF.md "Bring-up").
        min_sublanes = 32 if k_pages.dtype == jnp.int8 else 8
        if jax.default_backend() != "tpu":
            reason = f"backend {jax.default_backend()}"
        elif head_dim % 128:
            reason = f"head_dim {head_dim} % 128 != 0"
        elif page_size % min_sublanes:
            reason = (
                f"page_size {page_size} % {min_sublanes} != 0 "
                f"({k_pages.dtype} pages)"
            )
        else:
            reason = ""
        use_pallas = not reason
        reason = reason or "tpu, lane-aligned heads, tile-aligned pages"
    else:
        reason = "caller-selected"
    record_dispatch(
        "ragged_paged_decode",
        "pallas_ragged_paged" if use_pallas else "xla_gather",
        reason,
        rows=num_rows, heads=num_heads, head_dim=head_dim,
        page_size=page_size, store=str(k_pages.dtype),
    )
    if use_pallas:
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            ragged_paged_attention_kernel,
        )

        return ragged_paged_attention_kernel(
            query, k_pages, v_pages, block_table, lengths,
            k_scale=k_scale, v_scale=v_scale,
            cur_k=cur_k, cur_v=cur_v, interpret=interpret,
        )
    # XLA fallback: gather the block-table pages into a dense [R, W, ...]
    # view and reuse the one masked-softmax core. Gathered-but-invalid
    # positions (page remainders, null pages) are masked, so they
    # contribute exactly +0.0 to the softmax sums.
    pages_per_req = block_table.shape[1]
    width = pages_per_req * page_size
    k = jnp.take(k_pages, block_table, axis=0)  # [R, P, page, D]
    v = jnp.take(v_pages, block_table, axis=0)
    if k_scale is not None:
        ks = jnp.take(k_scale, block_table, axis=0)  # [R, P, page]
        vs = jnp.take(v_scale, block_table, axis=0)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    k = k.reshape(num_rows, width, num_heads, head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(num_rows, width, num_heads, head_dim).transpose(0, 2, 1, 3)
    valid = jnp.arange(width)[None, :] < lengths[:, None]  # [R, W]
    if cur_k is not None:
        cur_k = cur_k.reshape(num_rows, num_heads, 1, head_dim)
        cur_v = cur_v.reshape(num_rows, num_heads, 1, head_dim)
        k = jnp.concatenate([k, cur_k], axis=2)
        v = jnp.concatenate([v, cur_v], axis=2)
        valid = jnp.concatenate(
            [valid, jnp.ones((num_rows, 1), dtype=bool)], axis=1
        )
    out = scaled_dot_product_attention(
        query[:, :, None, :], k, v, valid[:, None, None, :]
    )[:, :, 0, :]
    if cur_k is None:
        # A fully-masked row (inactive: length 0, no current token) must
        # emit zeros like the kernel's l==0 finalize path, not the dense
        # softmax's uniform average of garbage V.
        out = jnp.where((lengths > 0)[:, None, None], out, 0.0)
    return out


def dot_product_attention(
    query: jnp.ndarray,
    key: jnp.ndarray,
    value: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    *,
    causal: bool = False,
    kv_valid: jnp.ndarray | None = None,
    use_pallas: bool | None = None,
) -> jnp.ndarray:
    """Backend-dispatching attention entry point used by the model zoo.

    Masking comes in two forms:

    - dense ``mask`` (boolean, broadcastable to ``[..., Sq, Sk]``) — always
      takes the fused-XLA path (an arbitrary mask cannot stream through the
      blockwise kernel);
    - structured ``causal`` + ``kv_valid`` (``[B, S_k]`` per-key validity,
      the padding-mask case) — exactly the masks the zoo Transformer needs,
      which the Pallas flash kernel streams without ever materializing
      ``[B, Sq, Sk]``.

    ``use_pallas=None`` chooses for a structured-mask site from its operand
    shapes alone: on TPU, ``q_len * kv_len >= FLASH_MIN_SCORES`` runs the
    flash kernel (forward and Pallas backward); a shorter site runs the
    dense path in both directions; other backends always do. A
    structured-mask site on the dense path, chosen or forced, is
    rematerialized: it saves ``q``, ``k``, ``v`` and ``kv_valid`` for the
    backward and recomputes the probabilities, as the kernel's
    short-sequence backward always has, so no ``[B, H, Sq, Sk]`` array
    outlives the forward. Such a site averages the values over a query row
    whose keys are all masked, where the kernel emits zeros.

    Under an active ``sequence_parallel(mesh)`` context, structured-mask
    *self-attention* (Sq == Sk, divisible by the seq axis) dispatches to
    ``parallel.ring_attention`` instead — K/V chunks rotate over ICI and no
    device ever holds the full sequence. Other sites (cross-attention,
    KV-cache decode, dense masks) keep their usual paths.
    """
    ctx = _active_seq_mesh()
    if (
        ctx is not None
        and mask is None
        and query.shape == key.shape == value.shape
        and query.shape[2] % ctx[0].shape[ctx[1]] == 0
        # Batch must also fill the mesh's batch axis (a ragged eval tail
        # batch, deliberately run unsharded by train.loop.evaluate, falls
        # through to the dense path instead of crashing shard_map).
        and query.shape[0] % ctx[0].shape.get(ctx[2], 1) == 0
    ):
        mesh, seq_axis, batch_axis, method = ctx
        if method == "ulysses":
            # A head count the seq axis cannot divide is a model-config
            # error, not a fall-through case: silently running the ring (or
            # dense) would misrepresent which mechanism executed.
            if query.shape[1] % mesh.shape[seq_axis]:
                raise ValueError(
                    f"sequence_parallel(method='ulysses') needs num_heads "
                    f"({query.shape[1]}) divisible by the {seq_axis!r} axis "
                    f"({mesh.shape[seq_axis]}); use method='ring'"
                )
            from machine_learning_apache_spark_tpu.parallel.ulysses_attention import (
                ulysses_attention,
            )

            record_dispatch(
                "dot_product", "ulysses", "sequence_parallel context",
                q=query.shape, kv_len=key.shape[2],
            )
            return ulysses_attention(
                query, key, value, mesh,
                causal=causal, kv_valid=kv_valid,
                seq_axis=seq_axis, batch_axis=batch_axis,
            )
        from machine_learning_apache_spark_tpu.parallel.ring_attention import (
            ring_attention,
        )

        record_dispatch(
            "dot_product", "ring", "sequence_parallel context",
            q=query.shape, kv_len=key.shape[2],
        )
        return ring_attention(
            query, key, value, mesh,
            causal=causal, kv_valid=kv_valid,
            seq_axis=seq_axis, batch_axis=batch_axis,
        )
    if mask is not None:
        reason = "dense mask"
    elif use_pallas is not None:
        reason = "caller-selected"
    elif _FORCED_IMPL:
        reason = f"attention_impl({_FORCED_IMPL[-1]!r})"
        use_pallas = _FORCED_IMPL[-1] == "flash"
    elif jax.default_backend() != "tpu":
        reason = f"structured mask, backend {jax.default_backend()}"
        use_pallas = False
    else:
        use_pallas, reason = flash_pays(query.shape[2], key.shape[2])
    use_pallas = bool(use_pallas) and mask is None
    record_dispatch(
        "dot_product", "pallas_flash" if use_pallas else "xla_dense", reason,
        q=query.shape, kv_len=key.shape[2],
    )
    if use_pallas:
        from machine_learning_apache_spark_tpu.ops.pallas_attention import (
            flash_attention,
        )

        return flash_attention(
            query, key, value, causal=causal, kv_valid=kv_valid
        )
    dense = functools.partial(_dense_attention, causal=causal)
    if mask is None:
        # What the kernel's short-sequence backward always kept: q, k, v
        # and kv_valid, probabilities recomputed. Plain autodiff would save
        # a float32 and a compute-dtype [B, H, Sq, Sk] array a site.
        dense = jax.checkpoint(dense)
    return dense(query, key, value, mask, kv_valid)


@jax.custom_vjp
def _float32_scores(query: jnp.ndarray, key: jnp.ndarray) -> jnp.ndarray:
    """``QKᵀ`` kept in float32 whatever the compute dtype, as the flash
    kernel keeps it: the product accumulates in float32 anyway, and a site
    that the shape gate takes from the kernel must not round it to bfloat16
    ahead of the softmax. The backward rounds the scores' cotangent to the
    operands' dtype before its two products, again as the kernels do
    (``ds.astype(k.dtype)``): plain transposition would feed the MXU a
    float32 ``[..., Sq, Sk]`` operand. Leading dims of ``query`` and
    ``key`` must agree."""
    return jnp.einsum(
        "...qd,...kd->...qk", query, key,
        preferred_element_type=jnp.result_type(query, key, jnp.float32),
    )


def _float32_scores_fwd(query, key):
    return _float32_scores(query, key), (query, key)


def _float32_scores_bwd(res, g):
    query, key = res
    return (
        jnp.einsum("...qk,...kd->...qd", g.astype(key.dtype), key),
        jnp.einsum("...qk,...qd->...kd", g.astype(query.dtype), query),
    )


_float32_scores.defvjp(_float32_scores_fwd, _float32_scores_bwd)


def _dense_attention(query, key, value, mask, kv_valid, *, causal):
    """The fused-XLA path of ``dot_product_attention``: the structured masks
    folded into one dense boolean mask over the materialized scores, which
    stay float32 from the product on. (``scaled_dot_product_attention``,
    the paged decode's core, keeps the product in the compute dtype: one
    query row a request is no MXU product, and float32 scores there cost a
    float32 copy of every gathered page.)"""
    from machine_learning_apache_spark_tpu.ops.masks import (
        combine_masks,
        make_causal_mask,
    )

    if kv_valid is not None:
        mask = combine_masks(mask, kv_valid[:, None, None, :])
    if causal:
        mask = combine_masks(
            mask, make_causal_mask(query.shape[-2], key.shape[-2])
        )
    weights = _weights_from_scores(
        _float32_scores(query, key), query.shape[-1], mask, query.dtype
    )
    return jnp.einsum("...qk,...kd->...qd", weights, value)

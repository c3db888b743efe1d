"""DeepSeek Sparse Attention's token indexer over a paged store (DeepSeek-
V3.2-Exp): a small network scores every cached position for a query, and the
query's attention then reads the ``topk`` best-scored positions alone.

For the query at position ``t`` with index queries ``q_j`` (``heads`` of
``d``) and weights ``w_j``, and the index key ``k_s`` of every position ``s``:

    I(t, s) = sum_j w_j * ReLU(q_j . k_s)        for s <= t

and the selection is the ``min(topk, t + 1)`` positions of highest ``I``.

The keys live in a plane of ``d``-wide rows, ``[pages * page, d]``, on the
same block tables as the latent rows (``ops.latent_attention``), written in
place a position at a time. Scores are computed in passes of ``block``
positions and reduced over the heads inside each pass, so that no ``[queries,
heads, context]`` array is ever formed (538 MB a layer at 32 rows of 66k
positions in float32); passes that lie wholly past every query are not run.

The selection is exact. A position's float32 score is turned into an
order-preserving unsigned key; the threshold (the largest key ``v`` with at
least ``topk`` keys ``>= v``) is built bit by bit in 32 counting passes, and
the positions at or over it are compacted, in position order, into ``topk``
slots (a tie at the threshold keeps the earlier positions, as ``lax.top_k``
would). The counts that compaction needs are running sums taken as products
with triangular matrices of ones (``_tile_counts``): a row-wide cumulative
sum lowers on the TPU to a ``reduce-window`` as wide as the row.

The scores take one of two paths, chosen from what the site shows (no option
chooses), and each site's ``ops.dsa_index_dispatch`` record says which:

* **``pallas_paged``** — a block table a row (decode) on the TPU, ``d`` whole
  lanes, ``page`` whole bfloat16 tiles, bfloat16 operands:
  ``ops.pallas_dsa_index`` (``dsa_index_scan``), where each row reads its own
  pages through ``t`` and no further.
* **``xla_scan``** — everything else (``_kernel_refusal`` names why): the
  passes of ``paged_scores`` below, which gather every row's pages while the
  furthest row needs a pass. A prefill chunk's one shared table reads its
  keys once for all of its queries, so it has nothing to skip.

``pages_read`` counts what the path a decode site took fetched, beside what
the XLA scan fetches at the same step. The selection is plain XLA on both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from machine_learning_apache_spark_tpu import telemetry

NULL_PAGE = 0
_LANES = 128  # positions a running-sum tile


def record_dispatch(site: str, impl: str, reason: str, **shape) -> None:
    telemetry.annotate(
        "ops.dsa_index_dispatch", site=site, impl=impl, reason=reason, **shape
    )


def _backend() -> str:
    """The backend a site is traced for: it decides whether the site takes
    the kernel."""
    return jax.default_backend()


def _kernel_refusal(q, plane, tables, *, page: int, block: int) -> str | None:
    """Why this site keeps ``xla_scan``; ``None`` where it can be seen to
    take ``dsa_index_scan``."""
    if _backend() != "tpu":
        return f"backend {_backend()}"
    if tables.ndim != 2:
        return "one table for every query: its keys are read once for all"
    if q.dtype != jnp.bfloat16 or plane.dtype != jnp.bfloat16:
        return f"{q.dtype.name} queries over {plane.dtype.name} keys, not bfloat16"
    if plane.shape[-1] % _LANES:
        return f"index head dim {plane.shape[-1]} not a multiple of {_LANES}"
    if page % 16:
        return f"page {page} not a multiple of bfloat16's 16 sublanes"
    return None


def _check_pass(page: int, block: int) -> None:
    if block % page or block % _LANES:
        raise ValueError(f"a pass of {block} positions is not whole pages "
                         f"of {page} and tiles of {_LANES}")


def pages_read(q, plane, tables, t, *, page: int, block: int):
    """Pages of keys a decode site's scan fetches at ``t [N]``, and what the
    XLA scan fetches at the same step: ``(read, padded)`` int32. The XLA scan
    gathers ``block / page`` pages a row a pass, for every row, through the
    furthest row's position; the kernel ``t // page + 1`` a row."""
    n = tables.shape[0]
    padded = n * ((jnp.max(t) + block) // block) * (block // page)
    if _kernel_refusal(q, plane, tables, page=page, block=block):
        return padded, padded
    return jnp.sum(t // page + 1), padded


def index_weights(w, heads: int, head_dim: int):
    """The published scale of the per-head weights: ``heads^-0.5 *
    head_dim^-0.5``, float32."""
    return w.astype(jnp.float32) * (heads ** -0.5 * head_dim ** -0.5)


def score_block(q, w, keys):
    """``q [N, H, d]``, ``w [N, H]`` float32, ``keys [N or 1, B, d]`` ->
    ``sum_j w_j ReLU(q_j . k_s)`` ``[N, B]`` float32. The sum over heads is
    taken on the vector unit, in float32 (a float32 product would round its
    operands to bfloat16 on the TPU)."""
    spec = "nhd,nbd->nhb" if keys.shape[0] == q.shape[0] else "nhd,xbd->nhb"
    s = jnp.einsum(spec, q, keys, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)


def _padded_tables(tables, block_pages: int):
    pmax = tables.shape[-1]
    width = -(-pmax // block_pages) * block_pages
    pad = [(0, 0)] * (tables.ndim - 1) + [(0, width - pmax)]
    return jnp.pad(tables, pad, constant_values=NULL_PAGE)


def paged_scores(q, w, plane, tables, t, *, page: int, block: int):
    """Index scores of queries ``q [N, H, d]`` (weights ``w [N, H]``) at
    positions ``t [N]`` over the keys the block tables name: ``tables [N,
    Pmax]`` (a table a query: decode) or ``[Pmax]`` (one table for every
    query: a prefill chunk). Returns ``[N, T]`` float32, ``T`` = ``Pmax``
    rounded up to a pass, times ``page``; ``-inf`` past each query's
    position and in the passes not run."""
    _check_pass(page, block)
    bp = block // page
    shared = tables.ndim == 1
    tables = _padded_tables(tables, bp)
    n, width = q.shape[0], tables.shape[-1]
    by_page = plane.reshape(-1, page, plane.shape[-1])
    passes = (jnp.max(t) + block) // block  # through the furthest query

    def one_pass(i, scores):
        ids = jax.lax.dynamic_slice_in_dim(tables, i * bp, bp, axis=-1)
        keys = by_page[ids].reshape(1 if shared else n, block, -1)
        s = score_block(q, w, keys)
        pos = i * block + jnp.arange(block)
        s = jnp.where(pos[None, :] <= t[:, None], s, -jnp.inf)
        return jax.lax.dynamic_update_slice_in_dim(scores, s, i * block, 1)

    return jax.lax.fori_loop(
        0, passes, one_pass, jnp.full((n, width * page), -jnp.inf, jnp.float32)
    )


def _order_key(scores):
    """float32 -> uint32 in the same order; ``-inf`` (nothing to select)
    maps to 0, the least key."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    negative = (bits >> 31) == 1
    key = jnp.where(negative, ~bits, bits | jnp.uint32(0x80000000))
    return jnp.where(scores == -jnp.inf, jnp.uint32(0), key)


def _tile_counts(mask):
    """Running counts of ``mask [N, T]`` (``T`` a multiple of 128) as two
    products with triangular matrices of ones: ``within [N, T / 128, 128]``,
    the inclusive count inside each tile of 128 positions, and ``ends [N, T
    / 128]``, the inclusive count through each tile. Every operand is a
    small integer, exact in bfloat16, and the sums are exact in float32."""
    n, width = mask.shape
    tiles = width // _LANES
    upper = jnp.asarray(np.triu(np.ones((_LANES, _LANES))), jnp.bfloat16)
    m = mask.reshape(n, tiles, _LANES).astype(jnp.bfloat16)
    within = jnp.einsum("ntl,lm->ntm", m, upper, preferred_element_type=jnp.float32)
    totals = within[..., -1].astype(jnp.bfloat16)  # <= 128
    through = jnp.asarray(np.triu(np.ones((tiles, tiles))), jnp.bfloat16)
    ends = jnp.einsum("nt,tu->nu", totals, through, preferred_element_type=jnp.float32)
    return within, ends


def select_top(scores, k: int, rows):
    """The ``k`` best-scored positions of every row of ``scores [N, T]``
    (``-inf`` where nothing may be selected), in position order, and their
    rows in the plane, read from ``rows [N or 1, T]`` (``plane_rows``).
    Returns ``(positions [N, k] int32, plane rows [N, k] int32, valid [N,
    k] bool)``; a row with fewer than ``k`` selectable positions takes them
    all and pads with invalid slots (position and plane row 0).

    Compaction without a sort, and without a gather of single elements: slot
    ``j`` lies in the tile whose running count first passes ``j`` (a count
    of the tiles that end at or before it), and its lane there is the number
    of the tile's lanes whose running count (from the row's start) is at
    most ``j``. The tile's 128 counts and 128 plane rows are gathered for
    the slot as rows, and the lane picked out of them by a comparison. (On
    the chip, at 64 queries of 69,632 positions and 2,048 slots: every
    tile's lane of every rank, ``[N, tiles, 128, 128]``, cost 1.4 ms; a
    gather of one element a slot costs 0.47 ms, of a row of 128 a slot
    0.1.)"""
    n, width = scores.shape
    key = _order_key(scores)

    def bit(i, threshold):
        candidate = threshold | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= candidate[:, None], axis=-1) >= k
        return jnp.where(enough, candidate, threshold)

    threshold = jax.lax.fori_loop(0, 32, bit, jnp.zeros((n,), jnp.uint32))
    taken = (key >= threshold[:, None]) & (key > 0)
    within, ends = _tile_counts(taken)
    tiles = ends.shape[-1]
    counts = within + (ends - within[..., -1])[..., None]  # from the row's start
    slots = jnp.arange(k, dtype=jnp.float32)
    valid = slots[None, :] < ends[:, -1:]
    tile = jnp.sum(ends[:, None, :] <= slots[None, :, None], axis=-1, dtype=jnp.int32)
    tile = jnp.where(valid, tile, 0)
    at = jnp.arange(n, dtype=jnp.int32)[:, None] * tiles + tile
    lane = jnp.sum(
        counts.reshape(n * tiles, _LANES)[at] <= slots[None, :, None],
        axis=-1, dtype=jnp.int32,
    )
    by_tile = rows.reshape(-1, _LANES)[tile if rows.shape[0] == 1 else at]
    row = jnp.sum(
        jnp.where(jnp.arange(_LANES) == lane[..., None], by_tile, 0), axis=-1
    )
    return (jnp.where(valid, tile * _LANES + lane, 0), jnp.where(valid, row, 0),
            valid)


def plane_rows(tables, page: int):
    """The plane row of every position the block tables ``tables [N, P]``
    or ``[P]`` name: ``[N or 1, P * page]`` int32."""
    tables = tables.reshape(-1, tables.shape[-1])
    return (tables[..., None] * page + jnp.arange(page, dtype=tables.dtype)).reshape(
        tables.shape[0], -1
    )


def select(q, w, plane, tables, t, *, page: int, block: int, topk: int,
           site: str):
    """Scores and selection in one call: ``(positions [N, topk], plane rows
    [N, topk], valid [N, topk])`` of queries ``q [N, H, d]`` at ``t [N]``."""
    shape = dict(queries=q.shape[0], heads=q.shape[1], head_dim=q.shape[2],
                 topk=topk, pages=tables.shape[-1], positions_a_pass=block)
    _check_pass(page, block)
    refusal = _kernel_refusal(q, plane, tables, page=page, block=block)
    padded = _padded_tables(tables, block // page)
    if refusal:
        record_dispatch(site, "xla_scan", refusal, **shape)
        scores = paged_scores(q, w, plane, tables, t, page=page, block=block)
    else:
        from machine_learning_apache_spark_tpu.ops import pallas_dsa_index as k

        n, total = q.shape[0], padded.shape[-1] * page
        rows = k.rows_a_step(n)
        vmem = k.vmem_bytes(rows, q.shape[1], q.shape[2], block, total,
                            plane.dtype.itemsize)
        record_dispatch(
            site, "pallas_paged",
            f"dsa_index_scan: passes of {block} positions ({block // page} "
            f"pages of {page}), grid {n // rows} x {rows} rows, vmem "
            f"{vmem / 2**20:.1f} MiB reckoned", **shape,
        )
        scores = k.scan_scores(q, w, plane, padded, t, page=page, block=block,
                               interpret=_backend() != "tpu")
    return select_top(scores, topk, plane_rows(padded, page))

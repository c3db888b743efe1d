"""Block-sparse attention over a paged KV store (InfLLM-V2, as published
with MiniCPM4): a parameter-free selector picks, for every query and KV head,
the ``topk`` key blocks worth attending; softmax attention then runs over the
tokens of those blocks alone.

A block is ``block`` consecutive positions, and here it is one page of the
store, so a selection is a block table of its own. Per KV head ``g`` (its
``Hg`` query heads share one selection), for the query at position ``t``:

1. compressed keys ``kc_j = mean(k[stride j : stride j + 2 stride])``
   (kernel = two strides), visible iff the last position it covers is
   ``<= t``;
2. ``p_h = softmax_j(q_h . kc_j / sqrt(d))`` over the visible ``j`` (the
   exact softmax), ``r_j = sum_h p_h[j]`` over the group's heads;
3. block score ``b_i = max r_j`` over the compressed keys that overlap
   block ``i`` (with ``m = block / stride`` those are ``j = m i - 1 .. m i +
   m - 1``: a max-pool of kernel ``m + 1``, stride ``m``, padding 1);
4. forced (score +inf): the first ``init_blocks`` blocks, and the query's
   own block with the ``window_blocks - 1`` before it; blocks past the
   query's own are out; the ``topk`` highest are taken, forced ones counted
   among them. A *dense* request (short enough that its whole context is to
   be attended) forces every block up to its own;
5. causal softmax attention of the group's heads over those blocks' tokens.

The store keeps, beside K and V, a plane of **unit means**: the mean key of
each ``stride`` positions. Each plane is a matrix of ``d``-wide rows, ``[G *
pages * block, d]`` (``[G * pages * m, d]`` for the unit means), KV head
outermost: one layout serves the row scatter that writes a position and the
page gather that reads a block (``gather_pages``, ``page_rows``). A
compressed key is the mean of two adjacent units, so ``q . kc_j`` is the mean
of two unit scores and no window straddles a page: a page shared between
requests (a common prefix) holds nothing that depends on what follows it.

Everything here is plain XLA (gathers through the block table, masked
softmax); which path a site took is its ``ops.sparse_attention_dispatch``
record, and a Pallas kernel for the gather would be chosen there by shape and
backend.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from machine_learning_apache_spark_tpu import telemetry

NEG_INF = -1e30
NULL_PAGE = 0


def record_dispatch(site: str, impl: str, reason: str, **shape) -> None:
    telemetry.annotate(
        "ops.sparse_attention_dispatch", site=site, impl=impl, reason=reason,
        **shape,
    )


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The selector's sizes (the family's ``sparse_config``)."""

    block: int = 64
    stride: int = 16
    topk: int = 64
    window_blocks: int = 32
    init_blocks: int = 1
    dense_len: int = 8192

    def __post_init__(self):
        if self.block % self.stride:
            raise ValueError(
                f"block {self.block} is not a multiple of stride {self.stride}"
            )

    @property
    def units(self) -> int:
        """Units (strides) a block."""
        return self.block // self.stride

    @property
    def dense_blocks(self) -> int:
        """Blocks a dense request can reach."""
        return -(-self.dense_len // self.block)


def gather_pages(store, page_ids, rows_a_page: int):
    """Pages out of a plane ``[G * P * rows_a_page, d]``: ``page_ids [..., G,
    K]`` (a page id a KV head) -> ``[..., G, K, rows_a_page, d]``."""
    g = page_ids.shape[-2]
    pages = store.shape[0] // (g * rows_a_page)
    paged = store.reshape(g * pages, rows_a_page, store.shape[-1])
    return paged[jnp.arange(g)[:, None] * pages + page_ids]


def page_rows(store, g: int, rows_a_page: int, page_ids, within):
    """Row numbers in a plane ``[G * P * rows_a_page, d]`` of the rows
    ``within`` the pages ``page_ids`` (arrays that broadcast together),
    for every KV head: ``[G, ...]``."""
    pages = store.shape[0] // (g * rows_a_page)
    head = jnp.arange(g).reshape(g, *([1] * max(jnp.ndim(page_ids), jnp.ndim(within))))
    return (head * pages + page_ids) * rows_a_page + within


def masked_softmax(s, mask, axes):
    """Softmax of ``s`` over ``axes`` where ``mask``; nought elsewhere, and
    all nought where nothing is. The maximum is held behind an optimisation
    barrier: fused with its broadcast, the TPU compiler turns it into a
    ``reduce-window`` as wide as the row (read in the compiled prefill: 15 ms
    a layer a chunk at 4,192 scores a row)."""
    top = jax.lax.optimization_barrier(
        jnp.max(jnp.where(mask, s, NEG_INF), axis=axes, keepdims=True)
    )
    p = jnp.where(mask, jnp.exp(s - top), 0.0)
    return p / jnp.maximum(jnp.sum(p, axis=axes, keepdims=True), 1e-30)


def unit_means(k: jnp.ndarray, stride: int) -> jnp.ndarray:
    """Mean key of every ``stride`` positions: ``k [..., T, d]`` ->
    ``[..., T / stride, d]`` (float32 mean, ``k``'s dtype out)."""
    *lead, t, d = k.shape
    u = k.reshape(*lead, t // stride, stride, d).astype(jnp.float32)
    return jnp.mean(u, axis=-2).astype(k.dtype)


def block_scores(q, units, t, spec: SparseSpec):
    """Steps 1-3. ``q [N, G, Hg, d]``, ``units [N, G, U, d]`` or ``[G, U, d]``
    (shared by every query), ``t [N]`` the queries' positions. Returns
    ``b [N, G, U / m]`` float32; a block with no visible compressed key
    scores 0."""
    d = q.shape[-1]
    spec_str = "nghd,ngud->nghu" if units.ndim == 4 else "nghd,gud->nghu"
    s = jnp.einsum(spec_str, q, units, preferred_element_type=jnp.float32)
    # compressed key j = units j and j + 1
    s = 0.5 * (s + jnp.roll(s, -1, axis=-1)) * d ** -0.5
    n_units = s.shape[-1]
    j = jnp.arange(n_units)
    # kc_j covers positions [stride j, stride j + 2 stride)
    visible = (spec.stride * (j[None, :] + 2) - 1 <= t[:, None]) & (
        j[None, :] < n_units - 1
    )
    p = masked_softmax(s, visible[:, None, None, :], -1)
    r = jnp.sum(p, axis=2)  # [N, G, U]
    m = spec.units
    grouped = r.reshape(*r.shape[:-1], n_units // m, m)
    before = jnp.pad(grouped[..., :-1, -1], [(0, 0), (0, 0), (1, 0)])  # r[m i - 1]
    return jnp.maximum(jnp.max(grouped, axis=-1), before)


def select_blocks(b, t, spec: SparseSpec, width: int, dense=None):
    """Step 4. ``b [N, G, B]`` block scores, ``t [N]``, ``dense [N]`` bool or
    None. Returns ``(idx [N, G, width] int32, valid [N, G, width] bool)``:
    block indices by falling score; at most ``topk`` are valid for a sparse
    query, every block up to its own for a dense one."""
    n_blocks = b.shape[-1]
    i = jnp.arange(n_blocks)[None, :]
    own = (t // spec.block)[:, None]
    forced = (i < spec.init_blocks) | (i > own - spec.window_blocks)
    limit = jnp.full(t.shape, spec.topk, jnp.int32)
    if dense is not None:
        forced = forced | dense[:, None]
        limit = jnp.where(dense, width, limit)
    score = jnp.where(forced[:, None, :], jnp.inf, b)
    score = jnp.where((i <= own)[:, None, :], score, -jnp.inf)
    width = min(width, n_blocks)
    top, idx = jax.lax.top_k(score, width)
    valid = (top > -jnp.inf) & (
        jnp.arange(width)[None, None, :] < limit[:, None, None]
    )
    return idx.astype(jnp.int32), valid


def attend_selected(q, k_pages, v_pages, page_ids, idx, valid, t, block: int):
    """Step 5. ``q [N, G, Hg, d]``; ``k_pages`` / ``v_pages [G * P * block,
    d]``; ``page_ids [N, G, K]`` the store's page of each selected block,
    ``idx`` its block index (for the causal mask), ``valid`` whether it
    counts; ``t [N]``. Returns ``[N, G, Hg, d]`` float32."""
    d = q.shape[-1]
    page_ids = jnp.where(valid, page_ids, NULL_PAGE)
    k = gather_pages(k_pages, page_ids, block)  # [N, G, K, block, d]
    v = gather_pages(v_pages, page_ids, block)
    pos = idx[..., None] * block + jnp.arange(block)  # [N, G, K, block]
    mask = valid[..., None] & (pos <= t[:, None, None, None])
    s = jnp.einsum(
        "nghd,ngkpd->nghkp", q, k, preferred_element_type=jnp.float32
    ) * d ** -0.5
    p = masked_softmax(s, mask[:, :, None], (-2, -1))
    return jnp.einsum(
        "nghkp,ngkpd->nghd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )


def sparse_decode(q, k_pages, v_pages, unit_pages, tables, t, dense,
                  spec: SparseSpec, *, site: str = "sparse_decode"):
    """One position a row over the paged store. ``q [R, G, Hg, d]``,
    ``unit_pages [G * P * m, d]``, ``tables [R, Pmax]`` the rows' block tables,
    ``t [R]`` positions, ``dense [R]`` bool. Returns ``(o [R, G, Hg, d]
    float32, selected [R, G, topk] int32)``; ``selected`` holds the block
    indices taken (-1 where fewer than ``topk`` were)."""
    r, g = q.shape[:2]
    record_dispatch(
        site, "xla_gather", "the one path", rows=r, kv_heads=g,
        heads_a_group=q.shape[2], head_dim=q.shape[3], topk=spec.topk,
        pages_a_row=tables.shape[1],
    )
    with jax.named_scope("lm.sparse_attn.select"):
        units = gather_pages(
            unit_pages, jnp.broadcast_to(tables[:, None, :], (r, g, tables.shape[1])),
            spec.units,
        )  # [R, G, Pmax, m, d]
        units = units.reshape(r, g, -1, units.shape[-1])
        b = block_scores(q, units, t, spec)

    def attend(width):
        with jax.named_scope("lm.sparse_attn.select"):
            idx, valid = select_blocks(b, t, spec, width, dense)
            pages = jnp.take_along_axis(tables[:, None, :], idx, axis=2)
        o = attend_selected(q, k_pages, v_pages, pages, idx, valid, t, spec.block)
        k = min(spec.topk, idx.shape[-1])
        chosen = jnp.where(valid, idx, -1)[..., :k]
        if k < spec.topk:
            chosen = jnp.pad(
                chosen, [(0, 0), (0, 0), (0, spec.topk - k)], constant_values=-1
            )
        return o, chosen

    wide = min(spec.dense_blocks, tables.shape[1])
    if wide <= spec.topk:
        return attend(spec.topk)
    # Sparse rows take topk blocks; a dense row may hold more, so the wide
    # gather runs only in a step that has one.
    return jax.lax.cond(
        jnp.any(dense), lambda: attend(wide), lambda: attend(spec.topk)
    )


def sparse_prefill(q, k_pages, v_pages, unit_pages, table, t, dense,
                   spec: SparseSpec, *, real=None,
                   site: str = "sparse_prefill"):
    """A chunk of one request's queries over the paged store, whose K, V and
    unit means up to the chunk's end are already written. ``q [C, G, Hg,
    d]``, ``table [Pmax]``, ``t [C]`` positions, ``dense`` a bool scalar,
    ``real`` how many of the chunk's queries are the prompt's (all of them
    when None). Queries are taken a page (``spec.block``) at a time,
    selection and gather alike, and the pages that hold padding alone are
    not run: a short question in a long chunk pays for its own queries.
    Returns ``o [C, G, Hg, d]`` float32, zeros in the pages not run."""
    c, g = q.shape[:2]
    qb = min(spec.block, c)
    record_dispatch(
        site, "xla_gather", "the one path", queries=c, kv_heads=g,
        heads_a_group=q.shape[2], head_dim=q.shape[3], topk=spec.topk,
        pages=table.shape[0], queries_a_pass=qb,
    )
    if c % qb:
        raise ValueError(f"chunk {c} is not a multiple of the page ({qb})")
    n_blocks = c // qb if real is None else (jnp.asarray(real) + qb - 1) // qb
    d = q.shape[-1]

    def over_blocks(attend):
        """``attend(q block, t block) -> o block`` over the blocks that hold
        a real query."""
        def body(i, out):
            cut = lambda x: jax.lax.dynamic_slice_in_dim(x, i * qb, qb, 0)  # noqa: E731
            return jax.lax.dynamic_update_slice_in_dim(
                out, attend(cut(q), cut(t)), i * qb, 0
            )

        return jax.lax.fori_loop(
            0, n_blocks, body, jnp.zeros(q.shape, jnp.float32)
        )

    def sparse():
        with jax.named_scope("lm.sparse_attn.select"):
            units = gather_pages(
                unit_pages, jnp.broadcast_to(table, (g, table.shape[0])), spec.units
            ).reshape(g, -1, unit_pages.shape[-1])

        def attend(qx, tx):
            with jax.named_scope("lm.sparse_attn.select"):
                b = block_scores(qx, units, tx, spec)
                idx, valid = select_blocks(b, tx, spec, spec.topk)
                pages = table[idx]
            return attend_selected(
                qx, k_pages, v_pages, pages, idx, valid, tx, spec.block
            )

        return over_blocks(attend)

    def plain():
        # Every block up to the query's own: one gather serves every query.
        width = min(spec.dense_blocks, table.shape[0])
        first = jnp.broadcast_to(table[:width], (g, width))
        k = gather_pages(k_pages, first, spec.block).reshape(g, -1, d)
        v = gather_pages(v_pages, first, spec.block).reshape(g, -1, d)
        pos = jnp.arange(width * spec.block)

        def attend(qx, tx):
            s = jnp.einsum(
                "nghd,gsd->nghs", qx, k, preferred_element_type=jnp.float32
            ) * d ** -0.5
            p = masked_softmax(
                s, (pos[None, :] <= tx[:, None])[:, None, None, :], -1
            )
            return jnp.einsum(
                "nghs,gsd->nghd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32,
            )

        return over_blocks(attend)

    return jax.lax.cond(dense, plain, sparse)

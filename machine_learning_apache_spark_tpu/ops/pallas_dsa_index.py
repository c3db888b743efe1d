"""The token indexer's decode scan as one Pallas TPU kernel (``dsa_index_scan``):
``ops.dsa_index.paged_scores`` for a block table a row, reading each row's
own pages up to its own position and no further (``ops.dsa_index`` has the
scores, the gate that sends a site here and the selection that follows; it
imports this module only where a site takes the kernel, so a program that
never does pays nothing for Pallas at import).

The XLA scan runs a pass of ``block`` positions for every row as long as the
furthest row needs one, and gathers every row's pages in each. Here the grid
is the rows, 8 a step, and a row runs the ``t // block + 1`` passes that hold
its positions ``0 .. t``; in each it copies the pages that hold them out of
the plane in HBM, addressed through the scalar-prefetched block tables, into
one of two VMEM buffers, the next pass's (or the next row's first pass's)
copies in flight while the current pass is scored. Where the table names
consecutive pages of the plane (a document prefilled at once lies so), one
copy takes the longest power of two of them up to ``MAX_COPY``
(``consecutive_runs``); elsewhere a copy is a page. A row at ``t = 0`` (a
row not active, or one at its first position) copies one page; the passes
past a row's position copy nothing and stay ``-inf``.

Scoring a pass is ``score_block``'s arithmetic, ``chunk`` positions at a
time: ``[heads, d] x [chunk, d]^T`` on the MXU with float32 accumulation,
ReLU, the float32 weights, and the sum over heads on the VPU in float32;
``-inf`` past the row's position. A pass's buffer may hold a stale page past
the position (its copy was not issued): its column is replaced by ``-inf``,
and a column depends on its own key alone.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))  # x y^T
#: Positions scored at a time inside a pass: a ``[64, 512]`` float32 product
#: is 32 vector registers.
CHUNK = 512
#: Most consecutive pages of the plane one copy takes (a power of two): at
#: the benchmark's decode site 8 read as fast as 64 over documents' runs of
#: pages and 1.5 x faster over scattered pages, where every page pays the
#: choice of its copy's size (``tools/dsa_index_sweep.py``, on a TPU v5e).
MAX_COPY = 8


def vmem_bytes(rows: int, heads: int, d: int, block: int, total: int,
               itemsize: int) -> int:
    """VMEM a grid step holds, reckoned: the two key buffers, and the
    pipeline's two buffers of the query, weight and score blocks (a block's
    last dimension padded to whole lanes)."""
    lanes = lambda n: -(-n // 128) * 128  # noqa: E731
    keys = 2 * block * lanes(d) * itemsize
    blocks = rows * (heads * lanes(d) * itemsize + heads * 128 * 4) + rows * total * 4
    return keys + 2 * blocks


def _scan_kernel(tables_ref, runs_ref, t_ref, q_ref, w_ref, plane_ref, out_ref,
                 keys, sems, *, rows: int, page: int, block: int, chunk: int,
                 sizes: list[int]):
    first = pl.program_id(0) * rows
    per_pass = block // page

    def needed(r):  # the passes that hold positions 0 .. t
        return t_ref[r] // block + 1

    def pages_in(r, i):
        return jnp.minimum(per_pass, (t_ref[r] - i * block) // page + 1)

    def copy(r, i, j, slot, size):
        return pltpu.make_async_copy(
            plane_ref.at[pl.ds(tables_ref[r, i * per_pass + j] * page, size * page)],
            keys.at[slot, pl.ds(j * page, size * page)],
            sems.at[slot],
        )

    def fetch(r, i, slot):
        pages = pages_in(r, i)

        def start(j):  # the longest power of two of consecutive pages at j
            run = jnp.minimum(runs_ref[r, i * per_pass + j], pages - j)
            size = 1
            for s in sizes[1:]:
                size = jnp.where(run >= s, s, size)
            for s in sizes:
                @pl.when(size == s)
                def _(s=s):
                    copy(r, i, j, slot, s).start()
            return j + size

        jax.lax.while_loop(lambda j: j < pages, start, 0)

    def wait(r, i, slot):
        # A wait takes its descriptor's bytes off the semaphore: a whole
        # pass at once, or a part of one a page at a time.
        pages = pages_in(r, i)

        @pl.when(pages == per_pass)
        def _():
            pltpu.make_async_copy(
                plane_ref.at[pl.ds(0, block)], keys.at[slot], sems.at[slot]
            ).wait()

        @pl.when(pages < per_pass)
        def _():
            def one(j, carry):
                pltpu.make_async_copy(
                    plane_ref.at[pl.ds(0, page)], keys.at[slot, pl.ds(0, page)],
                    sems.at[slot],
                ).wait()
                return carry

            jax.lax.fori_loop(0, pages, one, 0)

    out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, jnp.float32)
    fetch(first, 0, 0)
    done = 0  # passes run in this grid step: the slot's parity
    for g in range(rows):
        r, q, w = first + g, q_ref[g], w_ref[g]  # [heads, d], [heads, 1] f32

        def one_pass(i, done, r=r, g=g, q=q, w=w):
            slot = done % 2

            @pl.when(i + 1 < needed(r))
            def _():
                fetch(r, i + 1, 1 - slot)

            if g + 1 < rows:  # the next row's first pass under this one's last

                @pl.when(i + 1 == needed(r))
                def _():
                    fetch(r + 1, 0, 1 - slot)

            wait(r, i, slot)
            t = t_ref[r]
            for c in range(0, block, chunk):
                s = jax.lax.dot_general(
                    q, keys[slot, pl.ds(c, chunk), :], _NT,
                    preferred_element_type=jnp.float32,
                )
                s = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
                pos = i * block + c + jax.lax.broadcasted_iota(
                    jnp.int32, (1, chunk), 1
                )
                at = pl.multiple_of(i * block + c, chunk)
                out_ref[g:g + 1, pl.ds(at, chunk)] = jnp.where(pos <= t, s, -jnp.inf)
            return done + 1

        done = jax.lax.fori_loop(0, needed(r), one_pass, done)


def rows_a_step(n: int) -> int:
    """Rows a grid step: 8, one float32 tile of scores, where ``n`` allows."""
    return 8 if n % 8 == 0 else n


def consecutive_runs(tables):
    """``[N, width]`` int32: how many pages from each entry on are the
    plane's consecutive pages (``tables[r, j + k] == tables[r, j] + k``)."""
    width = tables.shape[1]
    at = jnp.arange(width, dtype=jnp.int32)
    ends = jnp.concatenate(
        [tables[:, 1:] != tables[:, :-1] + 1,
         jnp.ones((tables.shape[0], 1), bool)], axis=1,
    )
    last = jax.lax.cummin(jnp.where(ends, at, width), axis=1, reverse=True)
    return last - at + 1


def scan_scores(q, w, plane, tables, t, *, page: int, block: int,
                interpret: bool = False):
    """``paged_scores`` of queries ``q [N, H, d]`` (weights ``w [N, H]``
    float32) at ``t [N]`` over the keys ``plane [pages * page, d]`` that
    ``tables [N, width]`` names (``width`` whole passes of ``block //
    page`` pages; ``block`` whole tiles of 128 positions): ``[N, width *
    page]`` float32, ``-inf`` past each row's position."""
    n, heads, d = q.shape
    tables = tables.astype(jnp.int32)
    width = tables.shape[1]
    total = width * page
    per_pass = block // page
    rows = rows_a_step(n)
    kernel = functools.partial(
        _scan_kernel, rows=rows, page=page, block=block,
        chunk=math.gcd(CHUNK, block),
        sizes=[1 << k for k in range(min(MAX_COPY, per_pass).bit_length())],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // rows,),
            in_specs=[
                pl.BlockSpec((rows, heads, d), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((rows, heads, 1), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((rows, total), lambda b, *_: (b, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, d), plane.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, total), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="dsa_index_scan",
    )(tables, consecutive_runs(tables), t.astype(jnp.int32), q,
      w.astype(jnp.float32)[..., None], plane)

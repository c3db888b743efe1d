"""Paged decode runtime — the device half of the ragged serving engine.

Where a rectangle decoder (``greedy_translate_cached``) compiles one
program per padded shape and pays ``batch x width`` prefill plus
``batch x max_new_tokens`` decode slots for every batch, this runtime
keeps **two page stores** on device, each ``[layers, 2, num_pages, page_size, d_model]``:

- the **self store** holds generated-token K/V. It is small (worst case
  ``max_active x ceil(max_new_tokens/page_size)`` pages) because it is
  the launch program's scan carry — every decode step scatters into it,
  and on backends without buffer donation (CPU) a carry is copied per
  step, so its size is the per-step overhead.
- the **mem store** holds prompt cross-attention K/V and the prefix
  cache. It is written by prefill and **read-only during decode**, so
  the launch program never carries or copies it — the cache can grow
  large without taxing the decode loop.

With ``kv_dtype="int8"`` the mem store's payload is int8 with per-page
absmax scales (EQuARX-style, arxiv 2506.17615) kept in a parallel
``[layers, 2, num_pages, page_size]`` float32 plane addressed by the
same block tables — 4 fp32 bytes shrink to 1 int8 byte + 4/d_model
scale bytes per element, so the same HBM budget holds ~4x the pages
and the pages-limited max-concurrency ceiling rises with it. The
attention kernel dequantizes per gathered page before its dots
(``ops.attention.ragged_paged_attention``); scales travel with their
pages through PrefixCache hits/evictions because they live at the same
page index. The SELF store can follow via ``quantize_self=True``
(per-slot scales, written by the decode scatter).

Exactly two kinds of compiled program run over them:

- **prefill** (one per chunk count): encode a prompt padded to the next
  ``prefill_chunk`` multiple, project every decoder layer's cross-attn
  K/V (``Transformer.prefill_paged``), and scatter them into the
  request's memory pages. Short prompts pay a short program — chunk
  padding, not bucket padding — and a ``PrefixCache`` hit skips the
  program entirely.
- **launch** (exactly one): ``steps_per_launch`` greedy decode steps via
  ``lax.scan`` over ``Transformer.decode_step_paged``, serving every
  occupied row regardless of its prompt length or generation depth —
  block tables and per-row lengths make raggedness a *data* property, so
  any batch occupancy/length mix reuses the same XLA program and the
  zero-recompile invariant holds across arbitrary traffic.

Host state (block tables, cursors, row<->request maps) is plain numpy,
mutated only by the engine's decode thread; the device stores are jax
arrays threaded through the jitted programs (donated off-CPU). Page
accounting delegates to one ``KVPagePool`` per store: rows allocate
their first self page at admission and **grow one page at a time** as
the cursor crosses page boundaries, free everything on EOS/expiry via
the request id, and share refcounted prefix pages (mem pool) through
the cache.

Decode discipline (kept bit-consistent with the one-shot decoder's scan,
``greedy_translate_cached``): each step
scatters the new K/V at the row's *old* cursor, emits
``argmax`` (pad forced for finished rows), then advances the cursor for
unfinished rows only. A row finishes on emitting EOS, on exhausting the
``max_new_tokens`` budget, or on emitting pad (the one-shot decoder can
decode *through* an emitted pad because its dense mask hides interior
holes; length-addressed block tables cannot represent a hole, so the
paged path treats an emitted pad as terminal — in practice an untrained-corner
behaviour that greedy decoding does not produce).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from machine_learning_apache_spark_tpu.parallel.mesh import on_one_device
from machine_learning_apache_spark_tpu.serving.kv_pages import (
    NULL_PAGE,
    KVPagePool,
    PrefixCache,
)
from machine_learning_apache_spark_tpu.utils.logging import get_logger
from machine_learning_apache_spark_tpu.utils.profiling import annotate

log = get_logger(__name__)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@dataclasses.dataclass
class LaunchResult:
    """What one launch produced, for the engine's bookkeeping."""

    #: rows that finished this launch: (request, content token ids —
    #: sos/eos/pad excluded, row index, whether EOS was actually emitted)
    completed: list
    #: requests whose FIRST token arrived this launch (TTFT stamp)
    first_emits: list
    #: real tokens emitted this launch (EOS included; pads excluded)
    real_tokens: int
    #: decode-step slots the program computed (max_active x steps)
    computed_slots: int
    steps: int
    n_active: int


class PagedDecodeRuntime:
    """Page store + compiled programs + per-row host state.

    Single-threaded by contract: every method is called from the
    engine's decode thread (the pools it owns are internally locked, so
    introspection from other threads stays safe).
    """

    def __init__(
        self,
        model,
        params,
        *,
        max_active: int,
        max_src: int,
        max_new_tokens: int,
        page_size: int = 8,
        prefill_chunk: int = 8,
        steps_per_launch: int = 4,
        num_pages: int | None = None,
        prefix_cache_size: int = 32,
        kv_dtype: str = "float32",
        quantize_self: bool = False,
        sos_id: int,
        eos_id: int,
        pad_id: int,
    ):
        cfg = model.cfg
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if prefill_chunk % page_size != 0:
            raise ValueError(
                f"prefill_chunk ({prefill_chunk}) must be a multiple of "
                f"page_size ({page_size}) so memory pages fill exactly"
            )
        if max_new_tokens > cfg.max_len:
            raise ValueError(
                f"max_new_tokens {max_new_tokens} exceeds max_len "
                f"{cfg.max_len}: decode positions would have no encoding"
            )
        if kv_dtype not in ("float32", "int8"):
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r} (expected 'float32' or "
                "'int8')"
            )
        self.model = model
        # One runtime lives on one device. Params trained under a mesh
        # arrive replicated over all of its devices; left there, every
        # program would run on all of them, and the stores — committed to
        # the params' devices by the first call — would change sharding
        # between warm-up and the first request: a recompile per program.
        self.params, self.device = on_one_device(params)
        self.max_active = max_active
        self.max_new_tokens = max_new_tokens
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.steps_per_launch = steps_per_launch
        self.sos_id, self.eos_id, self.pad_id = sos_id, eos_id, pad_id
        # Quantized memory plane (EQuARX-style per-page absmax int8,
        # arxiv 2506.17615): the MEM store quantizes first — it dominates
        # footprint (prompt cross-KV + prefix-cache residents) and is
        # read-only during decode, so it quantizes once at prefill. The
        # small SELF scan-carry store follows only on request
        # (``quantize_self``): its per-step scatter must also write
        # per-slot scales, and the capacity win is marginal.
        self.kv_dtype = kv_dtype
        self.quantize_self = bool(quantize_self)
        self._mem_quant = kv_dtype == "int8"
        self._self_quant = self._mem_quant and self.quantize_self

        # Geometry: self pages cover the max_new_tokens budget; memory
        # pages cover the largest chunk-padded prompt. The self store is
        # always sized at worst case (it is small, and the launch carry
        # must never starve mid-decode); ``num_pages`` bounds the MEM
        # store — the big one, holding prompts and the prefix cache.
        self.self_pages = -(-max_new_tokens // page_size)
        self.max_chunks = -(-max_src // prefill_chunk)
        self.mem_pages = self.max_chunks * prefill_chunk // page_size
        self.num_self_pages = 1 + max_active * self.self_pages
        if num_pages is None:
            # Worst case all rows full-length prompts, plus the prefix
            # cache at capacity, plus the reserved null page.
            num_pages = (
                1 + (max_active + prefix_cache_size) * self.mem_pages
            )
        elif num_pages < 1 + self.mem_pages:
            raise ValueError(
                f"num_pages={num_pages} cannot hold even one full prompt "
                f"({self.mem_pages} pages + the reserved null page)"
            )
        self.num_pages = num_pages

        self._self_shape = (
            cfg.num_layers, 2, self.num_self_pages, page_size, cfg.d_model
        )
        self._mem_shape = (
            cfg.num_layers, 2, num_pages, page_size, cfg.d_model
        )
        self._self_store_dtype = (
            jnp.int8 if self._self_quant else cfg.dtype
        )
        self._mem_store_dtype = jnp.int8 if self._mem_quant else cfg.dtype
        # Per-slot dequantization scales, same block-table addressing as
        # the payload: slot (p, s) dequantizes as pages[p, s] * scale[p, s].
        # MEM scales are per *page* (one absmax per page, broadcast over
        # its slots); SELF scales are per slot (each decode step scatters
        # one position, so rescaling the whole page would corrupt the
        # int8 already written).
        self._self_scale_shape = (
            cfg.num_layers, 2, self.num_self_pages, page_size
        )
        self._mem_scale_shape = (cfg.num_layers, 2, num_pages, page_size)
        self.self_scale = self.mem_scale = None
        self._zero_stores()

        # Dtype-aware byte accounting: a page costs its payload plus (for
        # quantized stores) one fp32 scale per slot, across every layer's
        # k and v planes.
        d = cfg.d_model
        self.mem_page_bytes = cfg.num_layers * 2 * page_size * (
            d * np.dtype(self._mem_store_dtype).itemsize
            + (4 if self._mem_quant else 0)
        )
        self.self_page_bytes = cfg.num_layers * 2 * page_size * (
            d * np.dtype(self._self_store_dtype).itemsize
            + (4 if self._self_quant else 0)
        )
        self.self_pool = KVPagePool(
            self.num_self_pages, page_bytes=self.self_page_bytes
        )
        self.mem_pool = KVPagePool(
            num_pages, page_bytes=self.mem_page_bytes
        )
        self.prefix_cache = PrefixCache(self.mem_pool, prefix_cache_size)
        self.prefix_cache_size = prefix_cache_size

        # Donation lets each program write the store in place; CPU jax
        # does not implement it, so gate to keep the logs clean there.
        self._donate = jax.default_backend() != "cpu"
        self._prefill_fns = {
            c: self._make_prefill(c) for c in range(1, self.max_chunks + 1)
        }
        self._launch_fn = self._make_launch()

        self._reset_host_state()

    def _zero_stores(self) -> None:
        """Fresh zero payload + scale arrays on the runtime's device —
        identical shapes/dtypes/placement to the live ones, so compiled
        programs stay valid."""
        zeros = functools.partial(jnp.zeros, device=self.device)
        self.kv_self = zeros(self._self_shape, self._self_store_dtype)
        self.kv_mem = zeros(self._mem_shape, self._mem_store_dtype)
        if self._self_quant:
            self.self_scale = zeros(self._self_scale_shape, jnp.float32)
        if self._mem_quant:
            self.mem_scale = zeros(self._mem_scale_shape, jnp.float32)

    def _reset_host_state(self) -> None:
        R, Ps, Pm = self.max_active, self.self_pages, self.mem_pages
        self._self_tbl = np.full((R, Ps), NULL_PAGE, np.int32)
        self._mem_tbl = np.full((R, Pm), NULL_PAGE, np.int32)
        self._mem_len = np.zeros(R, np.int32)
        self._cursor = np.zeros(R, np.int32)
        self._token = np.full(R, self.pad_id, np.int32)
        self._finished = np.ones(R, bool)
        self._self_alloc = np.zeros(R, np.int32)  # self pages held per row
        self._req_of_row = [None] * R
        self._emitted: list[list[int]] = [[] for _ in range(R)]
        self._awaiting_first = np.zeros(R, bool)

    # -- compiled programs ---------------------------------------------------
    def _make_prefill(self, chunks: int):
        model = self.model
        layers = model.cfg.num_layers
        width = chunks * self.prefill_chunk
        n_pages = width // self.page_size
        page, d = self.page_size, model.cfg.d_model
        mem_quant = self._mem_quant

        def project(params, src):
            _, var = model.apply(
                {"params": params}, src,
                method="prefill_paged", mutable=["paged"],
            )
            sown = var["paged"]["decoder"]
            k = jnp.stack([
                sown[f"layer_{i}"]["cross_attn"]["k_mem"][0][0]
                for i in range(layers)
            ])
            v = jnp.stack([
                sown[f"layer_{i}"]["cross_attn"]["v_mem"][0][0]
                for i in range(layers)
            ])
            kv = jnp.stack([k, v], axis=1)  # [L, 2, width, d]
            return kv.reshape(layers, 2, n_pages, page, d)

        # The program's name is what a device trace's modules line reads
        # (``jit_paged_prefill_c2``): prefill's device time apart from the
        # launch's.
        name = f"paged_prefill_c{chunks}"
        if not mem_quant:
            def fn(params, kv_mem, src, mem_table):
                kv = project(params, src)
                return kv_mem.at[:, :, mem_table].set(
                    kv.astype(kv_mem.dtype)
                )

            fn.__name__ = fn.__qualname__ = name
            donate = (1,) if self._donate else ()
            return jax.jit(fn, donate_argnums=donate)

        def fn(params, kv_mem, mem_scale, src, mem_table):
            kv = project(params, src)
            # Per-page absmax quantization (the zero1 comms scheme, minus
            # the N-way-sum headroom — pages are never summed): one scale
            # per (layer, k/v, page), broadcast to the page's slots so
            # the kernel's per-slot dequant addressing stays uniform
            # between the MEM and SELF stores.
            absmax = jnp.max(jnp.abs(kv), axis=(3, 4))  # [L, 2, n_pages]
            s = jnp.maximum(absmax / 127.0, jnp.float32(1e-30))
            q = jnp.clip(
                jnp.round(kv / s[..., None, None]), -127, 127
            ).astype(jnp.int8)
            kv_mem = kv_mem.at[:, :, mem_table].set(q)
            slot_s = jnp.broadcast_to(
                s[..., None], (layers, 2, n_pages, page)
            )
            mem_scale = mem_scale.at[:, :, mem_table].set(slot_s)
            return kv_mem, mem_scale

        fn.__name__ = fn.__qualname__ = name
        donate = (1, 2) if self._donate else ()
        return jax.jit(fn, donate_argnums=donate)

    def _make_launch(self):
        model = self.model
        layers = model.cfg.num_layers
        page, Ps = self.page_size, self.self_pages
        T, mnt = self.steps_per_launch, self.max_new_tokens
        eos, pad = self.eos_id, self.pad_id
        self_quant = self._self_quant

        def paged_launch(params, kv_self, kv_mem, token, cursor, finished,
                         self_tbl, mem_tbl, mem_len, self_scale, mem_scale):
            # Only the self store (and, when self-quantized, its scale
            # plane) rides the scan carry: the mem store and its scales
            # are read-only during decode, so they enter as closed-over
            # operands and are never copied per step. For fp32 stores the
            # scale arguments are None — an empty pytree, so the compiled
            # program is unchanged from the unquantized build.
            def step(carry, _):
                kv_self, self_scale, token, cursor, finished = carry
                logits, var = model.apply(
                    {"params": params}, token[:, None], kv_self, kv_mem,
                    self_tbl, cursor, mem_tbl, mem_len, cursor[:, None],
                    self_scale, mem_scale,
                    method="decode_step_paged", mutable=["paged"],
                )
                sown = var["paged"]["decoder"]
                k = jnp.stack([
                    sown[f"layer_{i}"]["self_attn"]["k_new"][0]
                    for i in range(layers)
                ])
                v = jnp.stack([
                    sown[f"layer_{i}"]["self_attn"]["v_new"][0]
                    for i in range(layers)
                ])
                knv = jnp.stack([k, v], axis=1)  # [L, 2, R, d]
                # Scatter at the old cursor; frozen rows write the null
                # page (harmless by reservation).
                pidx = jnp.minimum(cursor // page, Ps - 1)
                pids = jnp.take_along_axis(
                    self_tbl, pidx[:, None], axis=1
                )[:, 0]
                pids = jnp.where(finished, NULL_PAGE, pids)
                offs = cursor % page
                if self_quant:
                    # Per-slot quantization: this step writes exactly one
                    # slot per row, so its scale lands next to it — the
                    # int8 already on the page keeps its own scales.
                    absmax = jnp.max(jnp.abs(knv), axis=-1)  # [L, 2, R]
                    s = jnp.maximum(
                        absmax / 127.0, jnp.float32(1e-30)
                    )
                    q = jnp.clip(
                        jnp.round(knv / s[..., None]), -127, 127
                    )
                    kv_self = kv_self.at[:, :, pids, offs, :].set(
                        q.astype(kv_self.dtype)
                    )
                    self_scale = self_scale.at[:, :, pids, offs].set(s)
                else:
                    kv_self = kv_self.at[:, :, pids, offs, :].set(
                        knv.astype(kv_self.dtype)
                    )
                emit = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
                emit = jnp.where(finished, pad, emit)
                cursor = cursor + jnp.where(finished, 0, 1).astype(jnp.int32)
                finished = (
                    finished
                    | (emit == eos)
                    | (emit == pad)
                    | (cursor >= mnt)
                )
                return (kv_self, self_scale, emit, cursor, finished), emit

            carry, emits = jax.lax.scan(
                step,
                (kv_self, self_scale, token, cursor, finished),
                None,
                length=T,
            )
            kv_self, self_scale, token, cursor, finished = carry
            return kv_self, self_scale, token, cursor, finished, emits

        donate = ((1, 9) if self_quant else (1,)) if self._donate else ()
        return jax.jit(paged_launch, donate_argnums=donate)

    def jit_fns(self) -> list:
        """Every jitted program, for the engine's compile counting."""
        return [*self._prefill_fns.values(), self._launch_fn]

    def warmup(self) -> int:
        """Compile every prefill width and the launch program against the
        live stores (null-page targets, no rows active) so steady state
        never pays a trace. Returns the program count."""
        seed = np.array([self.sos_id, self.eos_id], np.int32)
        for c, fn in self._prefill_fns.items():
            width = c * self.prefill_chunk
            src = np.full((1, width), self.pad_id, np.int32)
            src[0, : len(seed)] = seed
            tbl = np.full(width // self.page_size, NULL_PAGE, np.int32)
            if self._mem_quant:
                self.kv_mem, self.mem_scale = fn(
                    self.params, self.kv_mem, self.mem_scale, src, tbl
                )
            else:
                self.kv_mem = fn(self.params, self.kv_mem, src, tbl)
        out = self._launch_fn(
            self.params, self.kv_self, self.kv_mem, self._token,
            self._cursor, self._finished, self._self_tbl, self._mem_tbl,
            self._mem_len, self.self_scale, self.mem_scale,
        )
        self.kv_self = out[0]
        if self._self_quant:
            self.self_scale = out[1]
        jax.block_until_ready(self.kv_self)
        # Warmup scribbled on the null pages; reset the stores for
        # hygiene (same shapes and dtypes, so no recompile).
        self._zero_stores()
        return len(self._prefill_fns) + 1

    # -- admission -----------------------------------------------------------
    def _acquire_mem_pages(self, n: int, owner) -> list[int] | None:
        pages = self.mem_pool.try_acquire(n, owner)
        if pages is None:
            # Pressure valve: cached prefixes are a luxury, live requests
            # are not.
            self.prefix_cache.evict_until_free(n)
            pages = self.mem_pool.try_acquire(n, owner)
        return pages

    def prefill_cost(self, ids) -> int:
        """Prefill positions admitting ``ids`` would compute: none for a
        prefix-cache hit (pages attach, no program runs), the chunk-padded
        prompt width otherwise. Racy against eviction: a stale zero only
        means one admission round briefly exceeds the engine's budget."""
        if self.prefix_cache.contains(tuple(ids)):
            return 0
        return _round_up(max(len(ids), 1), self.prefill_chunk)

    def admit(self, req, row: int):
        """Place ``req`` on ``row``: attach (cache hit) or prefill (miss)
        its memory pages, allocate its first self page, and arm the row
        for decode. Returns ``(kind, padded_width, real_len)`` with kind
        in {"hit", "miss"} — a hit computes nothing, so its width is 0 —
        or None if the page pool cannot hold the request right now (the
        caller requeues; no references are leaked, and a miss's finished
        prefill survives in the cache for the retry)."""
        ids = list(req.ids)
        key = tuple(ids)
        width = _round_up(max(len(ids), 1), self.prefill_chunk)
        n_mem = width // self.page_size
        entry = self.prefix_cache.get(key, owner=req.id)
        if entry is None:
            pages = self._acquire_mem_pages(n_mem, req.id)
            if pages is None:
                return None
            src = np.full((1, width), self.pad_id, np.int32)
            src[0, : len(ids)] = ids
            fn = self._prefill_fns[width // self.prefill_chunk]
            if self._mem_quant:
                self.kv_mem, self.mem_scale = fn(
                    self.params, self.kv_mem, self.mem_scale, src,
                    np.asarray(pages, np.int32),
                )
            else:
                self.kv_mem = fn(
                    self.params, self.kv_mem, src,
                    np.asarray(pages, np.int32),
                )
            self.prefix_cache.put(key, pages, n_pages=n_mem,
                                  src_len=len(ids))
            kind, computed = "miss", width
        else:
            pages = entry["pages"]
            kind, computed = "hit", 0
        first = self.self_pool.try_acquire(1, req.id)
        if first is None:
            # Drop this request's references; a miss's pages stay alive
            # under the cache's own reference — the work is not lost.
            self.mem_pool.release_owner(req.id)
            return None
        self._req_of_row[row] = req
        self._emitted[row] = []
        self._awaiting_first[row] = True
        self._self_tbl[row, :] = NULL_PAGE
        self._self_tbl[row, 0] = first[0]
        self._self_alloc[row] = 1
        self._mem_tbl[row, :] = NULL_PAGE
        self._mem_tbl[row, : len(pages)] = pages
        self._mem_len[row] = len(ids)
        self._cursor[row] = 0
        self._token[row] = self.sos_id
        self._finished[row] = False
        return kind, computed, len(ids)

    def grow(self) -> list[int]:
        """Lazy self-page growth: before a launch, extend every active
        row's block table to cover the cursors the next
        ``steps_per_launch`` steps can reach. The self pool is sized at
        worst case, so starvation is impossible by construction; the
        starved-row return stays as the engine's defensive contract (it
        must fail such rows before launching, or their writes would land
        on the null page and corrupt reads of it)."""
        starved = []
        for r in range(self.max_active):
            req = self._req_of_row[r]
            if req is None or self._finished[r]:
                continue
            last = min(
                int(self._cursor[r]) + self.steps_per_launch - 1,
                self.max_new_tokens - 1,
            )
            need = last // self.page_size + 1
            have = int(self._self_alloc[r])
            if need <= have:
                continue
            got = self.self_pool.try_acquire(need - have, req.id)
            if got is None:
                starved.append(r)
                continue
            self._self_tbl[r, have:need] = got
            self._self_alloc[r] = need
        return starved

    # -- decode --------------------------------------------------------------
    def any_active(self) -> bool:
        return any(r is not None for r in self._req_of_row)

    def active_count(self) -> int:
        return sum(r is not None for r in self._req_of_row)

    def launch(self) -> LaunchResult:
        """Run one compiled multi-step decode over every row and fold the
        emitted tokens into per-row transcripts. Three spans say what the
        launch's host time is made of: the dispatch, the wait for the chip
        (until every output is on the host) and the fold."""
        with annotate("serving.launch.dispatch"):
            out = self._launch_fn(
                self.params, self.kv_self, self.kv_mem, self._token,
                self._cursor, self._finished, self._self_tbl, self._mem_tbl,
                self._mem_len, self.self_scale, self.mem_scale,
            )
        self.kv_self = out[0]
        if self._self_quant:
            self.self_scale = out[1]
        with annotate("serving.launch.wait"):
            emits = np.asarray(jax.block_until_ready(out[5]))
            # np.array (copy): host state is mutated by admit/retire, and
            # a bare asarray view of a jax buffer is read-only.
            self._token = np.array(out[2])
            self._cursor = np.array(out[3])
            self._finished = np.array(out[4])
        completed, first_emits, real, rows = [], [], 0, 0
        with annotate("serving.launch.fold") as phase:
            for r in range(self.max_active):
                req = self._req_of_row[r]
                if req is None:
                    continue
                rows += 1
                saw_eos = False
                for e in emits[:, r]:
                    e = int(e)
                    if e == self.pad_id:
                        break
                    if self._awaiting_first[r]:
                        self._awaiting_first[r] = False
                        first_emits.append(req)
                    real += 1
                    if e == self.eos_id:
                        saw_eos = True
                        break
                    self._emitted[r].append(e)
                if self._finished[r]:
                    completed.append((req, self._emitted[r], r, saw_eos))
            phase.set(rows=rows, real_tokens=real, completed=len(completed))
        return LaunchResult(
            completed=completed,
            first_emits=first_emits,
            real_tokens=real,
            computed_slots=self.max_active * self.steps_per_launch,
            steps=self.steps_per_launch,
            n_active=rows,
        )

    # -- retirement / containment -------------------------------------------
    def retire(self, row: int):
        """Free a finished (or failed) row: drop every page reference the
        request holds — its self pages free now, shared prefix pages only
        once the cache and other holders let go. Returns the request."""
        req = self._req_of_row[row]
        if req is None:
            return None
        self._req_of_row[row] = None
        self._emitted[row] = []
        self._awaiting_first[row] = False
        self._finished[row] = True
        self._token[row] = self.pad_id
        self._cursor[row] = 0
        self._self_tbl[row, :] = NULL_PAGE
        self._mem_tbl[row, :] = NULL_PAGE
        self._mem_len[row] = 0
        self._self_alloc[row] = 0
        self.self_pool.release_owner(req.id)
        self.mem_pool.release_owner(req.id)
        return req

    def active_requests(self) -> list:
        return [r for r in self._req_of_row if r is not None]

    def active_rows(self) -> list:
        """``(row, request)`` pairs for every occupied row — the engine's
        between-launch deadline sweep walks this to :meth:`retire` expired
        rows without reaching into private row state."""
        return [
            (row, req)
            for row, req in enumerate(self._req_of_row)
            if req is not None
        ]

    def reset(self) -> list:
        """Quarantine path: the store's contents are suspect, so drop
        everything — returns the requests that were active (the caller
        fails them). Fresh zero store keeps the compiled programs valid
        (same shapes), so recovery costs zero recompiles."""
        active = self.active_requests()
        self.self_pool = KVPagePool(
            self.num_self_pages, page_bytes=self.self_page_bytes
        )
        self.mem_pool = KVPagePool(
            self.num_pages, page_bytes=self.mem_page_bytes
        )
        self.prefix_cache = PrefixCache(self.mem_pool, self.prefix_cache_size)
        self._reset_host_state()
        self._zero_stores()
        return active

    # -- introspection -------------------------------------------------------
    def stats(self) -> dict:
        return {
            "num_pages": self.num_pages,
            "num_self_pages": self.num_self_pages,
            "page_size": self.page_size,
            "kv_dtype": self.kv_dtype,
            "quantize_self": self.quantize_self,
            "mem_page_bytes": self.mem_page_bytes,
            "self_page_bytes": self.self_page_bytes,
            "mem_pages_in_use": self.mem_pool.in_use,
            "self_pages_in_use": self.self_pool.in_use,
            "mem_occupancy": round(self.mem_pool.occupancy, 4),
            "self_occupancy": round(self.self_pool.occupancy, 4),
            "mem_high_water": self.mem_pool.high_water,
            "self_high_water": self.self_pool.high_water,
            "mem_bytes_in_use": self.mem_pool.bytes_in_use,
            "self_bytes_in_use": self.self_pool.bytes_in_use,
            "mem_bytes_high_water": self.mem_pool.bytes_high_water,
            "self_bytes_high_water": self.self_pool.bytes_high_water,
            "mem_bytes_capacity": self.mem_pool.bytes_capacity,
            "self_bytes_capacity": self.self_pool.bytes_capacity,
            "prefix_cache": self.prefix_cache.stats(),
            "active_rows": self.active_count(),
        }

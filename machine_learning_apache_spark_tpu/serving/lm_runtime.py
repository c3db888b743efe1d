"""Decoder-only paged runtime — the device half of the serving engine for a
language model: the second implementation of the interface ``ServingEngine``
drives (``admit``, ``grow``, ``launch``, ``retire``, ``reset``, ``warmup``,
``jit_fns``, ``stats``, ``prefill_cost``, the pools), beside the
encoder-decoder ``PagedDecodeRuntime``.

Where that runtime keeps two page stores (a decoder's own K/V and the
encoder's memory), a decoder-only model has **one**: the causal chunked
prefill writes the very pages decode reads.

**The model seam.** The runtime holds no model of its own: the module that
defines the configuration's class (``models.sala_lm``, ``models.dsa_lm``)
gives ``new_cache``, ``prefill_chunk``, ``decode_step`` (logits, the new
cache, the step's selections and a dict of the counters ``COUNTS`` names),
``page_bytes``, ``state_planes``, ``selected_share`` and ``PAIRED_COUNTS``,
and the config
gives ``page_size`` and ``is_dense``. A model's cache may hold two kinds of
state side by side under one manager:

- **pages** of ``page_size`` positions (``models.sala_lm``: K, V and the
  selector's unit means; ``models.dsa_lm``: latent rows and index keys),
  addressed by a block table a row, allocated from one refcounted
  ``KVPagePool``;
- a **state** a row, where ``state_planes`` names any (``models.sala_lm``'s
  linear-attention layers: a fixed float32 ``[heads, d, d]`` a layer
  whatever the context's length), slot ``row`` of the cache's ``states``.

Prefix reuse needs both: a ``SnapshotCache`` entry is the pages of a
prompt's first positions (shared read-only by reference) and, for a model
with a state, a copy of the state after them, kept in a snapshot plane
``[slots, heads, d, d]`` a layer. The runtime takes a snapshot at the last
page-aligned position of a long prefill; a later prompt that begins with the
same ids attaches the pages, restores any state into its row
(``serving.state_restore``) and prefills the rest. A model without a state
resumes from the pages alone.

Compiled programs, all of fixed shapes (so zero recompiles whatever the
traffic): ``paged_prefill`` (one chunk of one request, run chunk after chunk
however long the prompt), ``paged_launch`` (``steps_per_launch`` greedy steps
over every row) and, for a model with a state, ``state_save`` and
``state_restore`` (a row's states to and from a snapshot slot). A launch sums
the model's counters over its steps into ``counters`` and onto its fold span;
a pair named in ``PAIRED_COUNTS`` that differs in a launch is counted in
``launches_unequal``. ``launch(logits_of=rows)`` is the one exception: the
launch that also hands back those rows' logits and selections is a program of
its own, compiled when first asked for (a check's tool; the serving loop
never asks).

A prompt of ``n`` ids is prefilled up to its last id, which the launch's
first step consumes: every token a request emits comes out of the launch.
Single-threaded by contract, as ``PagedDecodeRuntime``.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from machine_learning_apache_spark_tpu.parallel.mesh import on_one_device
from machine_learning_apache_spark_tpu.serving.kv_pages import (
    NULL_PAGE,
    KVPagePool,
    SnapshotCache,
)
from machine_learning_apache_spark_tpu.serving.paged_runtime import LaunchResult
from machine_learning_apache_spark_tpu.utils.profiling import annotate

#: What a finished row emits; no token id is negative.
NO_TOKEN = -1


class LMDecodeRuntime:
    def __init__(
        self,
        cfg,
        params,
        *,
        max_active: int,
        max_context: int,
        max_new_tokens: int,
        prefill_chunk: int = 512,
        steps_per_launch: int = 8,
        num_pages: int | None = None,
        snapshot_capacity: int = 16,
    ):
        page = cfg.page_size
        if prefill_chunk % page:
            raise ValueError(
                f"prefill_chunk ({prefill_chunk}) must be a multiple of the "
                f"page size ({page})"
            )
        if max_new_tokens >= max_context:
            raise ValueError(
                f"max_new_tokens {max_new_tokens} leaves no room for a "
                f"prompt in a context of {max_context}"
            )
        self.cfg = cfg
        self.params, self.device = on_one_device(params)
        self.max_active, self.max_context = max_active, max_context
        self.max_new_tokens = max_new_tokens
        self.page_size, self.prefill_chunk = page, prefill_chunk
        self.steps_per_launch = steps_per_launch
        self.pages_per_row = -(-max_context // page)
        # A chunk's pages are sliced out of the row's table wherever the
        # chunk starts, so the table is a chunk longer than the context.
        self.table_width = self.pages_per_row + prefill_chunk // page
        if num_pages is None:
            num_pages = 1 + (max_active + snapshot_capacity) * self.pages_per_row
        elif num_pages < 1 + self.pages_per_row:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one full context "
                f"({self.pages_per_row} pages + the reserved null page)"
            )
        self.num_pages = num_pages
        self.snapshot_capacity = snapshot_capacity
        self.page_bytes = self.model.page_bytes(cfg)
        self.state_bytes_per_row = sum(
            int(np.prod(shape)) * 4 for shape in self._states
        )
        self._donate = jax.default_backend() != "cpu"
        self._prefill_fn = self._make_prefill()
        self._launch_fn = self._make_launch()
        self._save_fn, self._restore_fn = (
            self._make_state_copies() if self._states else (None, None)
        )
        self._logits_launch_fn = None  # made when first asked for
        self.captured = None  # what the last ``launch(logits_of=)`` handed back
        self._fresh()

    @property
    def model(self):
        """The model's module: the one that defines the config's class."""
        return sys.modules[type(self.cfg).__module__]

    @property
    def _states(self) -> list:
        """Shapes of the state a row keeps beside the pages, if any."""
        return self.model.state_planes(self.cfg)

    # -- state ----------------------------------------------------------------
    def _fresh(self) -> None:
        """Pools, host state and zeroed device planes: the shapes of the
        live ones, so compiled programs stay valid."""
        cfg = self.cfg
        self.mem_pool = KVPagePool(self.num_pages, page_bytes=self.page_bytes)
        self.prefix_cache = SnapshotCache(
            self.mem_pool, self.snapshot_capacity, self.page_size
        )
        self.cache = self.model.new_cache(
            cfg, rows=self.max_active, num_pages=self.num_pages,
            device=self.device,
        )
        self.snapshots = [
            jnp.zeros(
                (self.prefix_cache.num_slots, *shape), jnp.float32,
                device=self.device,
            )
            for shape in self._states
        ]
        r = self.max_active
        self._tables = np.full((r, self.table_width), NULL_PAGE, np.int32)
        self._alloc = np.zeros(r, np.int32)  # leading table entries that are set
        self._pos = np.zeros(r, np.int32)
        self._count = np.zeros(r, np.int32)
        self._token = np.zeros(r, np.int32)
        self._finished = np.ones(r, bool)
        self._dense = np.zeros(r, bool)
        self._last_pos = np.zeros(r, np.int32)
        self._req_of_row = [None] * r
        self._emitted: list[list[int]] = [[] for _ in range(r)]
        self._awaiting_first = np.zeros(r, bool)
        self.counters = dict(
            prompt_tokens=0, resumed_tokens=0, prefill_chunks=0,
            snapshots_taken=0, selected_share_sum=0.0, selected_share_n=0,
            launches_unequal=0,
        )

    # -- compiled programs ------------------------------------------------------
    def _make_prefill(self):
        cfg, model = self.cfg, self.model

        def paged_prefill(params, cache, tokens, table, row, start, length, dense):
            return model.prefill_chunk(
                params, cfg, cache, tokens, table, row, start, length, dense
            )

        return jax.jit(
            paged_prefill, donate_argnums=(1,) if self._donate else ()
        )

    def _make_launch(self):
        """The launch program. Given one more operand, row numbers
        ``logits_of [n]``, it also returns those rows' ``logits [steps, n,
        V]`` and ``selected [steps, sparse layers, n, kv heads, topk]``:
        another compiled program, kept apart from the serving one."""
        cfg, model = self.cfg, self.model
        steps, max_new = self.steps_per_launch, self.max_new_tokens
        eos, names = cfg.eos_id, model.COUNTS

        def paged_launch(params, cache, token, pos, count, finished, tables,
                         dense, logits_of=None):
            def step(carry, _):
                cache, token, pos, count, finished = carry
                active = ~finished
                logits, cache, chosen, counts = model.decode_step(
                    params, cfg, cache, token, pos, tables, active, dense
                )
                emit = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                emit = jnp.where(finished, NO_TOKEN, emit)
                # key positions attended over context positions, a row
                share = model.selected_share(cfg, chosen, pos, dense)
                share_sum = jnp.sum(jnp.where(active, share, 0.0))
                step_n = active.astype(jnp.int32)
                pos, count = pos + step_n, count + step_n
                finished = finished | (count >= max_new)
                if eos is not None:
                    finished = finished | (emit == eos)
                token = jnp.where(active, emit, token)
                read = () if logits_of is None else (
                    logits[logits_of], chosen[:, logits_of]
                )
                return (cache, token, pos, count, finished), (
                    emit, share_sum, jnp.sum(step_n),
                    *(counts[name] for name in names), *read
                )

            carry, outs = jax.lax.scan(
                step, (cache, token, pos, count, finished), None, length=steps
            )
            cache, token, pos, count, finished = carry
            emits, share_sum, share_n, *rest = outs
            summed, read = rest[:len(names)], rest[len(names):]
            host = jnp.stack([token, pos, count, finished.astype(jnp.int32)])
            stats = jnp.stack([
                jnp.sum(share_sum), jnp.sum(share_n).astype(jnp.float32),
                *(jnp.sum(c).astype(jnp.float32) for c in summed),
            ])
            return cache, emits, host, stats, *read

        return jax.jit(paged_launch, donate_argnums=(1,) if self._donate else ())

    def _make_state_copies(self):
        def state_save(snapshots, states, row, slot):
            return [
                jax.lax.dynamic_update_index_in_dim(
                    snap, jax.lax.dynamic_index_in_dim(state, row, keepdims=False),
                    slot, 0,
                )
                for snap, state in zip(snapshots, states)
            ]

        def state_restore(states, snapshots, slot, row):
            return [
                jax.lax.dynamic_update_index_in_dim(
                    state, jax.lax.dynamic_index_in_dim(snap, slot, keepdims=False),
                    row, 0,
                )
                for snap, state in zip(snapshots, states)
            ]

        donate = (0,) if self._donate else ()
        return (
            jax.jit(state_save, donate_argnums=donate),
            jax.jit(state_restore, donate_argnums=donate),
        )

    def jit_fns(self) -> list:
        return [self._prefill_fn, self._launch_fn] + (
            [self._save_fn, self._restore_fn] if self._states else []
        )

    def warmup(self) -> int:
        """Compile the programs against the live planes (null-page targets,
        slot 0, no row active). Returns the program count."""
        if self._states:
            self.snapshots = self._save_fn(
                self.snapshots, self.cache["states"], np.int32(0), np.int32(0)
            )
            self._restore(0, 0)
        self._run_chunk(
            np.zeros(self.prefill_chunk, np.int32), self._tables[0], 0, 0, 1, False
        )
        self._dispatch_launch()
        jax.block_until_ready(self.cache)
        return len(self.jit_fns())

    # -- admission --------------------------------------------------------------
    def _run_chunk(self, tokens, table, row, start, length, dense) -> None:
        self.cache = self._prefill_fn(
            self.params, self.cache, tokens, table, np.int32(row),
            np.int32(start), np.int32(length), np.bool_(dense),
        )
        self.counters["prefill_chunks"] += 1

    def _restore(self, slot: int, row: int) -> None:
        with annotate("serving.state_restore") as span:
            span.set(slot=slot, row=row)
            self.cache["states"] = self._restore_fn(
                self.cache["states"], self.snapshots, np.int32(slot), np.int32(row)
            )

    def _acquire(self, n: int, owner) -> list[int] | None:
        pages = self.mem_pool.try_acquire(n, owner)
        if pages is None:
            self.prefix_cache.evict_until_free(n)
            pages = self.mem_pool.try_acquire(n, owner)
        return pages

    def prefill_cost(self, ids) -> int:
        """Chunk-padded positions admitting ``ids`` would compute."""
        ids = np.asarray(ids, np.int32)
        # A snapshot may cover all but the last id: the launch consumes it.
        rest = len(ids) - 1 - self.prefix_cache.match_length(ids, len(ids) - 1)
        return -(-rest // self.prefill_chunk) * self.prefill_chunk

    def admit(self, req, row: int):
        """Place ``req`` on ``row``: attach the longest snapshot its prompt
        begins with (pages by reference, the state copied into the row),
        prefill the rest chunk by chunk into pages of its own, and arm the
        row. Returns ``(kind, computed, real)`` (``kind`` "hit" where a
        snapshot was resumed; ``computed`` the chunk-padded positions
        prefilled, ``real`` the positions among them) or None where the pool
        cannot hold the request now."""
        ids = np.asarray(req.ids, np.int32)
        n, chunk, page = len(ids), self.prefill_chunk, self.page_size
        entry = self.prefix_cache.lookup(ids, n - 1, owner=req.id)
        resumed = entry["length"] if entry else 0
        shared = entry["pages"] if entry else []
        # Own pages now: the prompt's rest and the first launch's steps.
        first_launch_end = min(n - 1 + self.steps_per_launch, n - 1 + self.max_new_tokens)
        need = -(-first_launch_end // page) - len(shared)
        own = self._acquire(max(need, 0), req.id)
        if own is None:
            self.mem_pool.release_owner(req.id)
            return None
        table = self._tables[row]
        table[:] = NULL_PAGE
        table[: len(shared)] = shared
        table[len(shared): len(shared) + len(own)] = own
        self._alloc[row] = len(shared) + len(own)
        dense = self.cfg.is_dense(n + self.max_new_tokens)
        if self._states:
            self._restore(entry["slot"] if entry else 0, row)

        # Prefill positions resumed .. n - 2. A long one stops at the last
        # page boundary first, where its snapshot is taken.
        end = n - 1
        boundary = end // page * page
        snapshot_at = boundary if boundary > resumed and (
            end - resumed >= chunk or boundary == end
        ) else None
        computed, start = 0, resumed
        for stop in sorted({snapshot_at, end} - {None}):
            while start < stop:
                tokens = np.zeros(chunk, np.int32)
                real = min(chunk, stop - start)
                tokens[:real] = ids[start: start + real]
                self._run_chunk(tokens, table, row, start, stop, dense)
                start += real
                computed += chunk
            if stop == snapshot_at:
                self._take_snapshot(ids[:stop], table, row)
        self.counters["prompt_tokens"] += n
        self.counters["resumed_tokens"] += resumed

        self._req_of_row[row] = req
        self._emitted[row] = []
        self._awaiting_first[row] = True
        self._pos[row] = n - 1
        self._last_pos[row] = n - 1 + self.max_new_tokens - 1
        self._count[row] = 0
        self._token[row] = ids[-1]
        self._finished[row] = False
        self._dense[row] = dense
        return ("hit" if entry else "miss"), computed, end - resumed

    def _take_snapshot(self, ids, table, row: int) -> None:
        slot = self.prefix_cache.reserve_slot()
        if slot is None:
            return
        if self._states:
            self.snapshots = self._save_fn(
                self.snapshots, self.cache["states"], np.int32(row), np.int32(slot)
            )
        pages = [int(p) for p in table[: len(ids) // self.page_size]]
        if self.prefix_cache.put(ids, pages, slot):
            self.counters["snapshots_taken"] += 1

    def grow(self) -> list[int]:
        """Before a launch, extend every active row's table over the
        positions its next ``steps_per_launch`` steps write. Rows the pool
        cannot serve, even after evicting snapshots, are returned for the
        engine to fail."""
        starved = []
        for r, req in enumerate(self._req_of_row):
            if req is None or self._finished[r]:
                continue
            last = min(int(self._pos[r]) + self.steps_per_launch - 1,
                       int(self._last_pos[r]))
            need = last // self.page_size + 1
            have = int(self._alloc[r])
            if need <= have:
                continue
            got = self._acquire(need - have, req.id)
            if got is None:
                starved.append(r)
                continue
            self._tables[r, have:need] = got
            self._alloc[r] = need
        return starved

    # -- decode -----------------------------------------------------------------
    def any_active(self) -> bool:
        return any(r is not None for r in self._req_of_row)

    def active_count(self) -> int:
        return sum(r is not None for r in self._req_of_row)

    def active_requests(self) -> list:
        return [r for r in self._req_of_row if r is not None]

    def active_rows(self) -> list:
        return [(i, r) for i, r in enumerate(self._req_of_row) if r is not None]

    def _dispatch_launch(self, logits_of=None):
        fn, extra = self._launch_fn, ()
        if logits_of is not None:
            if self._logits_launch_fn is None:
                self._logits_launch_fn = self._make_launch()
            fn, extra = self._logits_launch_fn, (np.asarray(logits_of, np.int32),)
        out = fn(
            self.params, self.cache, self._token, self._pos, self._count,
            self._finished, self._tables, self._dense, *extra,
        )
        self.cache = out[0]
        return out

    def launch(self, logits_of=None) -> LaunchResult:
        """One compiled multi-step decode over every row, folded into the
        rows' transcripts; the same three spans as the encoder-decoder
        runtime's launch. With ``logits_of`` (row numbers) the launch runs
        through the program that also reads those rows' logits and
        selections, left on the device in ``self.captured``."""
        with annotate("serving.launch.dispatch"):
            out = self._dispatch_launch(logits_of)
        with annotate("serving.launch.wait"):
            emits = np.asarray(jax.block_until_ready(out[1]))
            host = np.array(out[2])
            share = np.asarray(out[3])
        self.captured = tuple(out[4:]) or None
        self._token, self._pos, self._count = host[0], host[1], host[2]
        self._finished = host[3].astype(bool)
        self.counters["selected_share_sum"] += float(share[0])
        self.counters["selected_share_n"] += int(share[1])
        counts = dict(zip(self.model.COUNTS, (int(c) for c in share[2:])))
        for name, value in counts.items():
            self.counters[name] = self.counters.get(name, 0) + value
        self.counters["launches_unequal"] += int(any(
            counts[a] != counts[b] for a, b in self.model.PAIRED_COUNTS
        ))
        completed, first_emits, real, rows = [], [], 0, 0
        eos = self.cfg.eos_id
        with annotate("serving.launch.fold") as phase:
            for r, req in enumerate(self._req_of_row):
                if req is None:
                    continue
                rows += 1
                saw_eos = False
                for e in emits[:, r]:
                    e = int(e)
                    if e == NO_TOKEN:
                        break
                    if self._awaiting_first[r]:
                        self._awaiting_first[r] = False
                        first_emits.append(req)
                    real += 1
                    if e == eos:
                        saw_eos = True
                        break
                    self._emitted[r].append(e)
                if self._finished[r]:
                    completed.append((req, self._emitted[r], r, saw_eos))
            # positions the rows' contexts held, summed: what the launch's
            # attention and selector had to read
            context = int(sum(
                self._pos[r] for r, q in enumerate(self._req_of_row) if q is not None
            ))
            phase.set(rows=rows, real_tokens=real, completed=len(completed),
                      context=context, **counts)
        return LaunchResult(
            completed=completed, first_emits=first_emits, real_tokens=real,
            computed_slots=self.max_active * self.steps_per_launch,
            steps=self.steps_per_launch, n_active=rows,
        )

    # -- retirement / containment ---------------------------------------------------
    def retire(self, row: int):
        req = self._req_of_row[row]
        if req is None:
            return None
        self._req_of_row[row] = None
        self._emitted[row] = []
        self._awaiting_first[row] = False
        self._finished[row] = True
        self._tables[row, :] = NULL_PAGE
        self._alloc[row] = 0
        self._pos[row] = self._count[row] = self._token[row] = 0
        self.mem_pool.release_owner(req.id)
        return req

    def reset(self) -> list:
        """Quarantine: everything on the device is suspect. Fresh planes of
        the same shapes keep the compiled programs."""
        active = self.active_requests()
        self._fresh()
        return active

    # -- introspection ----------------------------------------------------------------
    def stats(self) -> dict:
        pool = self.mem_pool
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "page_bytes": self.page_bytes,
            "pages_in_use": pool.in_use,
            "occupancy": round(pool.occupancy, 4),
            "high_water": pool.high_water,
            "bytes_in_use": pool.bytes_in_use,
            "bytes_capacity": pool.bytes_capacity,
            "state_bytes_per_row": self.state_bytes_per_row,
            "state_bytes": self.state_bytes_per_row * (
                self.max_active + self.prefix_cache.num_slots
            ),
            "prefix_cache": self.prefix_cache.stats(),
            "active_rows": self.active_count(),
            **self.counters,
        }

"""Serving engine — the paged decode loop behind ``Translator.serve()`` and
``LanguageModel.serve()``.

One loop, two runtimes: the engine owns the queue, the batcher, the row pool,
the metrics and the phase spans, and drives the device through the runtime
its bundle builds (``bundle.make_runtime``): ``PagedDecodeRuntime`` for the
encoder-decoder ``Translator``, ``LMDecodeRuntime`` for the decoder-only
``LanguageModel``. A runtime offers ``warmup``, ``jit_fns``, ``prefill_cost``,
``admit``, ``grow``, ``launch``, ``retire``, ``reset``, ``stats``, the
``active_*`` views, and its ``mem_pool`` / ``prefix_cache`` for the live
plane; the bundle offers ``encode`` (a request's text or ids to prompt ids),
``decode`` (emitted ids to the caller's result), ``max_positions`` and
``make_runtime``.

Caller threads tokenize and ``submit()`` into a bounded admission queue;
one background worker turns the queue into device work, cycle by cycle:

    queue -> token-budget admission -> chunked prefill
          -> one ragged launch program -> retire

- **admit**: the oldest pending requests take free cache rows, FIFO, as
  far as the prefill token budget reaches (``TokenBudgetBatcher``); a
  prompt whose prefix KV is cached attaches its pages and costs nothing;
- **prefill**: each admitted prompt is encoded at its chunk-padded
  width into pages of the device's KV store (``PagedDecodeRuntime``);
- **launch**: ONE compiled program runs ``steps_per_launch`` decode
  steps over every row, whatever the occupancy or length mix;
- **retire**: finished rows hand their translation to the caller's
  future and give back row and pages.

The eager path stays thin — tokenize, place, dispatch — and everything
hot is an already-compiled XLA program. ``warmup()`` compiles the whole
set (one prefill per chunk count, one launch), so steady state runs with
zero recompiles; ``recompiles_after_warmup`` watches the runtime's jit
caches and is the demo/bench acceptance gate.
"""

from __future__ import annotations

import threading
import time
from typing import Sequence

from machine_learning_apache_spark_tpu import telemetry
from machine_learning_apache_spark_tpu.telemetry import (
    tracectx as _tracectx,
)
from machine_learning_apache_spark_tpu.utils import env as envcfg
from machine_learning_apache_spark_tpu.serving.batcher import (
    TokenBudgetBatcher,
)
from machine_learning_apache_spark_tpu.serving.kv_slots import KVSlotPool
from machine_learning_apache_spark_tpu.serving.metrics import ServingMetrics
from machine_learning_apache_spark_tpu.serving.queue import (
    DeadlineExceeded,
    RequestQueue,
    ServeRequest,
)
from machine_learning_apache_spark_tpu.utils.faults import maybe_fault
from machine_learning_apache_spark_tpu.utils.logging import get_logger
from machine_learning_apache_spark_tpu.utils.profiling import annotate

log = get_logger(__name__)


class EngineStopped(RuntimeError):
    """The engine shut down before this request completed."""


class InternalError(RuntimeError):
    """The engine failed this request internally (its decode batch raised).

    The failure is *contained*: only the requests on the active rows of
    the quarantined launch see this, the decode loop keeps serving, and —
    because the page store resets to the same shapes — recovery triggers
    zero recompiles.
    The original exception rides along as ``__cause__``.
    """


class _HealthWindow:
    """The /healthz quarantine-recovery window, shared between the decode
    worker (writes) and HTTP scrape threads (reads). Both timestamps move
    under one lock so a reader always sees a (quarantine, ok-batch) pair
    that actually coexisted. The previous two-bare-loads read was pair-
    consistent only by accident of CPython's bytecode-level GIL switching
    (no call between the loads); any refactor inserting one — or a
    free-threaded build — could pair a fresh ok-batch time with a stale
    quarantine time and report "recovered" mid-degraded-window. The lock
    makes the guarantee structural; ``tests/test_analysis_races.py``
    hammers it from 4 threads."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._last_quarantine_t: float | None = None  # guarded-by: self._lock
        self._last_ok_batch_t: float | None = None  # guarded-by: self._lock

    def note_quarantine(self, t: float) -> None:
        with self._lock:
            self._last_quarantine_t = t

    def note_ok_batch(self, t: float) -> None:
        with self._lock:
            self._last_ok_batch_t = t

    def snapshot(self) -> tuple[float | None, float | None]:
        """A consistent (last_quarantine_t, last_ok_batch_t) pair."""
        with self._lock:
            return self._last_quarantine_t, self._last_ok_batch_t

    def recovered(self) -> bool:
        """False while the most recent quarantine has not yet been
        followed by a successful batch."""
        lq, lok = self.snapshot()
        return lq is None or (lok is not None and lok > lq)


class ServingEngine:
    """Continuous-batching server over a ``Translator`` or a
    ``LanguageModel`` (the bundle builds the runtime; the loop is one): a
    page-table KV store, chunk-padded prefill, refcounted prefix sharing, immediate
    FIFO admission and ONE compiled ragged decode program for any batch
    occupancy/length mix. Decoding is greedy and token-identical to
    ``Translator.__call__``; beam search is offline only
    (``Translator.__call__(method="beam")``).

    >>> with translator.serve(max_active=8, boundaries=(16, 32)) as eng:
    ...     futs = [eng.submit(s) for s in texts]
    ...     outs = [f.result(timeout=30) for f in futs]

    Knobs (see docs/SERVING.md) and what each bounds: ``boundaries`` the
    prompt length (the largest is the page store's prompt width, the
    smallest the default prefill chunk); ``max_active`` the rows decoding
    at once (``max_batch`` is its default and an older name for it);
    ``max_new_tokens`` the tokens a request may emit; ``max_queue_depth``
    the backpressure point; ``default_deadline_s`` a request's life;
    ``page_size``/``num_pages`` the KV granularity/budget;
    ``prefill_chunk``+``prefill_budget`` the prefill work between two
    launches; ``steps_per_launch`` the decode steps per dispatch;
    ``prefix_cache_size`` the shared-prefix entries; ``kv_dtype``
    (``"float32"`` default / ``"int8"`` quantized pages with per-page
    scales, env ``MLSPARK_SERVE_KV_DTYPE``) and ``quantize_self`` the
    store's precision.
    """

    def __init__(
        self,
        translator,
        *,
        boundaries: Sequence[int] = (16, 32, 64),
        max_batch: int = 8,
        max_queue_depth: int = 64,
        max_new_tokens: int | None = None,
        default_deadline_s: float | None = None,
        kv_dtype: str | None = None,
        quantize_self: bool = False,
        page_size: int = 8,
        prefill_chunk: int | None = None,
        steps_per_launch: int = 4,
        max_active: int | None = None,
        num_pages: int | None = None,
        prefix_cache_size: int = 32,
        prefill_budget: int | None = None,
        clock=time.monotonic,
    ):
        max_positions = translator.max_positions
        boundaries = tuple(sorted(boundaries))
        if boundaries[-1] > max_positions:
            raise ValueError(
                f"largest boundary {boundaries[-1]} exceeds the model's "
                f"max_len {max_positions}; positions past max_len have no "
                "encoding"
            )
        # Quantized KV store: arg > env > default.
        if kv_dtype is None:
            kv_dtype = envcfg.get_str("MLSPARK_SERVE_KV_DTYPE")
        if kv_dtype not in ("float32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'float32' or 'int8', got {kv_dtype!r} "
                "(check MLSPARK_SERVE_KV_DTYPE)"
            )
        self.kv_dtype = kv_dtype
        self.quantize_self = bool(quantize_self)
        self.translator = translator
        self.boundaries = boundaries
        self.max_batch = max_batch
        self.max_new_tokens = (
            max_positions - 1 if max_new_tokens is None else max_new_tokens
        )
        self.clock = clock
        self.metrics = ServingMetrics(clock=clock)
        self.queue = RequestQueue(
            max_queue_depth, default_deadline_s=default_deadline_s,
            clock=clock, on_expire=self.metrics.on_expire,
            on_slo=self.metrics.on_slo,
        )
        self.max_active = max_active or max_batch
        if prefill_chunk is None:
            prefill_chunk = max(
                page_size, boundaries[0] // page_size * page_size
            )
        self.prefill_chunk = prefill_chunk
        # Chunked-prefill pacing: at most this many chunk-padded
        # prompt tokens prefill between consecutive decode launches,
        # so admission bursts can't stall in-flight rows' next token.
        self.prefill_budget = (
            prefill_budget
            if prefill_budget is not None
            else 2 * -(-boundaries[-1] // prefill_chunk) * prefill_chunk
        )
        self.runtime = translator.make_runtime(
            max_active=self.max_active,
            max_src=boundaries[-1],
            max_new_tokens=self.max_new_tokens,
            page_size=page_size,
            prefill_chunk=prefill_chunk,
            steps_per_launch=steps_per_launch,
            num_pages=num_pages,
            prefix_cache_size=prefix_cache_size,
            kv_dtype=kv_dtype,
            quantize_self=quantize_self,
        )
        # The row pool: one slot = one cache row in the launch program.
        self.pool = KVSlotPool(self.max_active)
        self.paged_batcher = TokenBudgetBatcher(
            self.queue, chunk=prefill_chunk
        )
        self._compiles_at_warmup: int | None = None
        self._stop = threading.Event()
        self._worker: threading.Thread | None = None
        # Monotonic sequence over dispatched launches — the
        # ``decode_batch`` fault-injection coordinate (worker thread
        # only; no lock needed).
        self._batch_seq = 0
        # Health model for /healthz: the engine is DEGRADED while its most
        # recent quarantine is more recent than its most recent successful
        # batch — i.e. it has contained a fault and not yet proven it can
        # decode again. Worker-thread writes, scrape-thread reads.
        self._health = _HealthWindow()

    # -- lifecycle -----------------------------------------------------------
    def start(self, *, warmup: bool = True) -> "ServingEngine":
        if self._worker is not None:
            raise RuntimeError("engine already started")
        if warmup:
            self.warmup()
        self._stop.clear()
        self._worker = threading.Thread(
            target=self._serve_loop, name="serving-engine", daemon=True
        )
        self._worker.start()
        # The live plane: contribute this engine's state to /statusz,
        # /healthz, and /metrics, and (idempotently) start the HTTP
        # server — a no-op with zero threads unless MLSPARK_TELEMETRY_HTTP
        # is set and telemetry is on.
        telemetry.register_status_provider("serving", self._status_snapshot)
        telemetry.register_health_provider("serving", self._health_snapshot)
        # First-class residency section for the fleet router's
        # affinity table: bounded MRU digests of the prompts whose
        # prefix KV this replica already holds (fleet/affinity.py
        # scrapes sections.prefix_cache off /statusz).
        telemetry.register_status_provider(
            "prefix_cache", self.runtime.prefix_cache.stats
        )
        telemetry.register_live_gauge(
            "serving", "queue_depth_live", lambda: self.queue.depth
        )
        telemetry.register_live_gauge(
            "serving", "kv_page_occupancy",
            lambda: self.runtime.mem_pool.occupancy,
        )
        telemetry.register_live_gauge(
            "serving", "kv_mem_bytes_in_use",
            lambda: self.runtime.mem_pool.bytes_in_use,
        )
        telemetry.register_live_gauge(
            "serving", "active_rows",
            lambda: self.runtime.active_count(),
        )
        telemetry.start_http_server()
        telemetry.beacon_update(phase="serving")
        return self

    def stop(self, *, timeout: float = 30.0) -> None:
        if self._worker is None:
            return
        telemetry.unregister_provider("serving")
        telemetry.unregister_provider("prefix_cache")
        self._stop.set()
        with self.queue.cond:
            self.queue.cond.notify_all()
        self._worker.join(timeout)
        self._worker = None
        n = self.queue.fail_all(EngineStopped("serving engine stopped"))
        if n:
            # Counted into ``failed`` so the conservation law balances
            # across shutdown: stop-drained requests are terminal too.
            self.metrics.on_failure(n)
            log.info("engine stop failed %d queued requests", n)

    def __enter__(self) -> "ServingEngine":
        if self._worker is None:
            self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- warmup / compile accounting ----------------------------------------
    def warmup(self) -> int:
        """Precompile every program a live request could need — one
        prefill per chunk count plus the single ragged launch — so no
        request ever pays a compile. Returns the program count."""
        with annotate("serve_warmup_paged"):
            n = self.runtime.warmup()
        self._compiles_at_warmup = self.compile_count()
        log.info(
            "warmup compiled %d paged programs (max_active=%d, page_size=%d)",
            n, self.max_active, self.runtime.page_size,
        )
        return n

    def compile_count(self) -> int:
        """Total compiled programs across every jitted callable the
        engine owns: the runtime's prefill and launch programs."""
        from machine_learning_apache_spark_tpu.utils.compilation_cache import (
            jit_cache_size,
        )

        return sum(jit_cache_size(f) for f in self.runtime.jit_fns())

    @property
    def recompiles_after_warmup(self) -> int | None:
        """Programs compiled since ``warmup()`` — 0 in healthy steady
        state (the demo/bench acceptance gate); None before warmup."""
        if self._compiles_at_warmup is None:
            return None
        return self.compile_count() - self._compiles_at_warmup

    # -- live plane providers (called from HTTP scrape threads) --------------
    def _health_snapshot(self) -> dict:
        """/healthz check: worker thread alive, and not in the degraded
        window between a quarantine and the next successful batch."""
        worker = self._worker
        worker_alive = worker is not None and worker.is_alive()
        recovered = self._health.recovered()
        return {
            "healthy": worker_alive and recovered,
            "worker_alive": worker_alive,
            "quarantine_recovered": recovered,
            "kv_dtype": self.kv_dtype,
            "queue_depth": self.queue.depth,
            "loop_restarts": self.metrics.loop_restarts,
            "quarantined": self.metrics.quarantined,
        }

    def _status_snapshot(self) -> dict:
        """/statusz section: the engine's full live state — config,
        conservation ledger, latency summary, page-pool stats, slowest-
        request exemplars."""
        return {
            "kv_dtype": self.kv_dtype,
            "boundaries": list(self.boundaries),
            "max_batch": self.max_batch,
            "max_active": self.max_active,
            "max_new_tokens": self.max_new_tokens,
            "queue_depth": self.queue.depth,
            "recompiles_after_warmup": self.recompiles_after_warmup,
            "ledger": self.metrics.ledger(),
            "metrics": self.metrics.summary(),
            "slowest_requests": self.metrics.request_exemplars(),
            "page_pool": self.runtime.stats(),
        }

    # -- request path --------------------------------------------------------
    def submit(
        self,
        text: str,
        *,
        deadline_s: float | None = None,
        tier: str | None = None,
    ) -> ServeRequest:
        """Tokenize and admit one request; returns its ``ServeRequest``
        (``.result(timeout)`` blocks for the translation). Raises
        ``Backpressure`` at capacity and ``ValueError`` for inputs past
        the largest boundary — both *before* the request costs decode work.

        Distributed tracing: a context already active on the calling
        thread (a replica handling a routed request) is adopted; a bare
        local submit mints its own, so standalone engines trace too.
        ``tier`` tags the request's SLO class for the burn-rate gauges.
        """
        if self._worker is None:
            raise RuntimeError("engine not started (use start() or `with`) ")
        ids = self.translator.encode(text)
        if len(ids) > self.boundaries[-1]:
            raise ValueError(
                f"input tokenizes to {len(ids)} ids, beyond the largest "
                f"prompt boundary {self.boundaries[-1]}; raise boundaries "
                "or shorten the input"
            )
        # Count the attempt BEFORE the queue decides: the conservation law
        # (metrics.check_conservation) needs every admission attempt in
        # ``submitted`` so rejected ones balance against ``rejected``.
        self.metrics.on_submit()
        ctx = _tracectx.current() or _tracectx.mint()
        with _tracectx.use(ctx), telemetry.span("serving.submit"):
            try:
                req = self.queue.submit(
                    text, ids, deadline_s=deadline_s, tier=tier
                )
            except Exception:
                self.metrics.on_reject()
                raise
        return req

    # -- the decode loop -----------------------------------------------------
    def _serve_loop(self) -> None:
        """Supervisor: keep a decode loop alive until ``stop()``.

        Two containment rings (docs/FAULT_TOLERANCE.md). Inner: a launch
        or admission that raises is quarantined — the active rows'
        requests fail with ``InternalError``, everything queued keeps
        flowing. Outer: if the loop itself dies (batcher bug, quarantine
        path raising), it is
        restarted here rather than leaving a silently dead engine whose
        submitters all block until their deadlines; ``loop_restarts``
        counts how often that safety net caught something.
        """
        while not self._stop.is_set():
            try:
                self._paged_loop()
            except Exception:  # noqa: BLE001 — a dead loop, not a dead engine
                if self._stop.is_set():
                    break
                log.exception("decode loop died; restarting")
                self.metrics.on_loop_restart()

    # -- the paged decode loop ----------------------------------------------
    def _paged_loop(self) -> None:
        """Continuous paged serving: admit FIFO requests into free cache
        rows (chunk-budgeted prefill), launch ``steps_per_launch`` ragged
        decode steps over every occupied row, retire rows as they finish.
        A raised launch or admission quarantines the active set only
        (the inner containment ring).

        An engine with no active row blocks in ``serving.idle_wait`` until
        work arrives; every other iteration is one ``serving.cycle``."""
        while not self._stop.is_set():
            try:
                taken = None
                if not self.runtime.any_active():
                    taken = self._paged_idle_wait()
                    if not taken:
                        continue
                self._paged_cycle(taken)
            except Exception as e:  # noqa: BLE001 — contain, keep serving
                self._paged_quarantine(e)
        self._paged_fail_active(EngineStopped("serving engine stopped"))

    def _paged_take(self, timeout: float) -> list[ServeRequest]:
        return self.paged_batcher.take(
            max_requests=self.pool.free,
            token_budget=self.prefill_budget,
            timeout=timeout,
            cost_fn=self._admission_cost,
        )

    def _paged_idle_wait(self) -> list[ServeRequest]:
        """Block until the queue hands over work (returned, for the cycle
        to place) or the engine stops ([]). One span however long the
        engine stays idle — a poll that comes back empty writes nothing,
        so a quiet replica's flight-recorder tail keeps its history."""
        with annotate("serving.idle_wait"):
            while not self._stop.is_set():
                taken = self._paged_take(timeout=0.05)
                if taken:
                    return taken
                # No arrival will run the submit-side sweep, so burn
                # deadlines down directly — a queued request must expire
                # on time even on a quiet engine.
                self.queue.expire_now()
        return []

    def _paged_cycle(self, taken: list[ServeRequest] | None) -> None:
        """One iteration with work: the deadline sweep, admission, then —
        if any row is occupied — grow, launch and retire. Every phase is
        a child span of ``serving.cycle``, so the cycle's duration less
        the union of its children is the loop's own time."""
        with annotate("serving.cycle") as cycle:
            with annotate("serving.expire") as phase:
                phase.set(expired=self.queue.expire_overdue())
            with annotate("serving.admit") as phase:
                self._paged_admit(phase, taken)
            if self._stop.is_set() or not self.runtime.any_active():
                cycle.set(launched=0)
                return
            seq = self._batch_seq
            self._batch_seq += 1
            cycle.set(seq=seq, launched=int(self._paged_step(seq)))

    def _admission_cost(self, req) -> int:
        """Prefill positions admitting ``req`` will actually compute, as its
        runtime prices them (a cached prefix costs nothing)."""
        return self.runtime.prefill_cost(req.ids)

    def _paged_admit(self, phase, taken: list[ServeRequest] | None) -> None:
        """Move pending requests onto free rows, bounded by the prefill
        token budget (chunked-prefill pacing). On page-pool pressure the
        untaken tail goes back to the queue head — transient, not an
        error. ``taken`` is what the idle wait already took, or None to
        take now; the round's counts go onto ``phase`` (``serving.admit``)
        and its token slots into the ledger once, as sums."""
        if taken is None:
            taken = self._paged_take(timeout=0.0)
        if not taken:
            # Empty admit round: see ``_paged_idle_wait``.
            self.queue.expire_now()
            phase.set(taken=0)
            return
        hits = misses = real_sum = computed_sum = requeued = 0
        try:
            for req in taken:
                if self._stop.is_set():
                    break
                row = self.pool.try_acquire(req.id)
                if row is None:  # unreachable: take() is bounded by free rows
                    break
                res = self.runtime.admit(req, row)
                if res is None:
                    # Page pool full even after cache eviction: give the
                    # row back and retry once in-flight rows free pages.
                    self.pool.release_owner(req.id)
                    break
                kind, computed, real = res
                req.admit_time = self.clock()
                req.trace.mark(
                    "admit", req.admit_time,
                    kind=kind, prefill_tokens=computed, row=row,
                )
                if kind == "hit":
                    hits += 1
                else:
                    misses += 1
                    real_sum += real
                computed_sum += computed
            # Taken and not placed (engine stopping, pool pressure): back
            # to the head of the queue, in order.
            back = taken[hits + misses:]
            self.queue.requeue_front(back)
            requeued = len(back)
        finally:
            # Also when a prefill raised: what was dispatched is counted.
            if hits + misses:
                self.metrics.on_token_slots(
                    real=real_sum, padded=computed_sum
                )
            phase.set(
                taken=len(taken), hits=hits, misses=misses,
                prefill_tokens=computed_sum, requeued=requeued,
                queue_depth=self.queue.depth,
            )

    def _paged_step(self, seq: int) -> bool:
        """One fault-injection point, one page-growth pass and deadline
        sweep (``serving.grow``), one compiled launch (``serving.batch``),
        then host-side retirement of every finished row
        (``serving.retire``). False where the sweep left no row to
        launch."""
        maybe_fault("decode_batch", batch=seq)
        with annotate("serving.grow") as phase:
            starved = self.runtime.grow()
            for row in starved:
                req = self.runtime.retire(row)
                self.pool.release_owner(req.id)
                if not req.future.done():
                    req.trace.mark(
                        "failed", self.clock(), reason="pages_exhausted"
                    )
                    req.future.set_exception(InternalError(
                        "kv page pool exhausted mid-decode; size num_pages "
                        "for the worst case (the default does)"
                    ))
                    self.metrics.on_failure(1)
                    self.metrics.on_trace(req)
            # Deadline sweep between launches: a row whose deadline passed
            # (or was force-expired by /v1/cancel) retires NOW, freeing its
            # pages and launch slot instead of decoding tokens no one will
            # read. Same retire path as completion, outcome ``expired`` —
            # the conservation ledger closes either way.
            now = self.clock()
            n_reaped = 0
            for row, req in self.runtime.active_rows():
                if not req.expired(now):
                    continue
                self.runtime.retire(row)
                self.pool.release_owner(req.id)
                n_reaped += 1
                if not req.future.done():
                    req.trace.mark("expire", now, reason="in_flight")
                    req.future.set_exception(DeadlineExceeded(
                        f"request {req.id} expired mid-decode after "
                        f"{now - req.submit_time:.3f}s"
                    ))
                    self.metrics.on_expire(1, in_flight=True)
                    self.metrics.on_slo(req.tier, True)
                    self.metrics.on_trace(req)
            if n_reaped:
                telemetry.annotate(
                    "serving.expire_in_flight", mode="paged", count=n_reaped
                )
            phase.set(starved=len(starved), reaped=n_reaped)
        active = self.runtime.active_requests()
        n_active = len(active)
        if n_active == 0:
            return False
        t0 = self.clock()
        with telemetry.span(
            "serving.batch", mode="paged", rows=n_active,
            steps=self.runtime.steps_per_launch, seq=seq,
        ), annotate("serve_decode_paged"):
            result = self.runtime.launch()
        decode_done = self.clock()
        decode_s = decode_done - t0
        with annotate("serving.retire") as phase:
            for req in active:
                req.trace.note_launch(seq)
            for req in result.first_emits:
                req.decode_done_time = decode_done
                req.trace.mark("first_token", decode_done)
            decode = self.translator.decode
            n_completed = 0
            for req, ids, row, saw_eos in result.completed:
                self.runtime.retire(row)
                self.pool.release_owner(req.id)
                req.trace.mark("complete", decode_done, tokens=len(ids))
                req.future.set_result(decode(ids))
                n_completed += 1
                now = self.clock()
                self.metrics.on_complete(
                    queue_wait=(req.admit_time or req.submit_time)
                    - req.submit_time,
                    ttft=(req.decode_done_time or now) - req.submit_time,
                    total=now - req.submit_time,
                )
                self.metrics.on_trace(req)
                self.metrics.on_slo(
                    req.tier, req.deadline is not None and now > req.deadline
                )
            # Token ledger: len(content)+1 per request. Real emits count
            # EOS when emitted; a budget-exhausted row gets its implicit
            # stop token here.
            new_tokens = result.real_tokens + sum(
                1 for *_ , saw_eos in result.completed if not saw_eos
            )
            self.metrics.on_token_slots(
                real=result.real_tokens, padded=result.computed_slots
            )
            if n_completed:
                self.queue.note_serviced(n_completed, decode_s)
            self.metrics.on_batch(
                n_requests=n_active,
                max_batch=self.max_active,
                decode_s=decode_s,
                new_tokens=new_tokens,
                queue_depth=self.queue.depth,
                slot_occupancy=self.runtime.mem_pool.occupancy,
            )
            # A launch completed without raising: the degraded window (if
            # any) is over — /healthz flips back to ok.
            self._health.note_ok_batch(decode_done)
            phase.set(completed=n_completed, tokens=new_tokens)
        return True

    def _paged_quarantine(self, exc: Exception) -> None:
        """Contain a failed launch/admission: the page store's contents
        are suspect, so every active request fails with ``InternalError``
        and the store resets (same shapes — zero recompiles); everything
        still queued keeps flowing."""
        if self._stop.is_set():
            return
        self._health.note_quarantine(self.clock())
        active = self.runtime.reset()
        log.info("quarantining paged launch of %d: %r", len(active), exc)
        telemetry.annotate(
            "serving.quarantine", mode="paged", requests=len(active),
            error=type(exc).__name__,
        )
        n = 0
        traces = []
        for req in active:
            self.pool.release_owner(req.id)
            if not req.future.done():
                req.trace.mark(
                    "failed", self.clock(), reason="quarantine",
                    error=type(exc).__name__,
                )
                err = InternalError(
                    f"decode batch failed internally ({type(exc).__name__});"
                    " only the active paged rows are affected"
                )
                err.__cause__ = exc
                req.future.set_exception(err)
                n += 1
                traces.append(req.trace.to_dict())
                self.metrics.on_trace(req)
        self.metrics.on_quarantine(n)
        self.metrics.on_failure(n)
        # The flight dump carries each quarantined request's full trace
        # timeline — postmortems see where every victim's time went, not
        # just how many there were.
        telemetry.dump_flight(
            f"serving.quarantine:{type(exc).__name__}",
            extra={
                "mode": "paged", "requests_failed": n,
                "request_traces": traces,
            },
        )

    def _paged_fail_active(self, exc: Exception) -> None:
        """Engine stopping with rows mid-decode: fail them terminally so
        the admission ledger still balances."""
        n = 0
        for req in self.runtime.reset():
            self.pool.release_owner(req.id)
            if not req.future.done():
                req.trace.mark("failed", self.clock(), reason="engine_stop")
                req.future.set_exception(exc)
                n += 1
        if n:
            self.metrics.on_failure(n)
            log.info("engine stop failed %d in-flight paged rows", n)

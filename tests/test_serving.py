"""serving/: admission queue, admission picker, KV slot pool, metrics,
and the end-to-end engine — the request-level layer over the compiled
decode core (docs/SERVING.md).

Unit tests drive queue/picker/slots with a fake clock (no sleeps where
avoidable); the e2e class serves real concurrent requests through a tiny
untrained Transformer on CPU and pins the two serving invariants: results
identical to the one-shot ``Translator`` path, and zero recompiles after
warmup.
"""

import threading
import time

import numpy as np
import pytest

from machine_learning_apache_spark_tpu.serving import (
    Backpressure,
    DeadlineExceeded,
    Histogram,
    KVSlotPool,
    RequestQueue,
    ServingEngine,
)
from machine_learning_apache_spark_tpu.serving.metrics import percentile

pytestmark = pytest.mark.serving


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TestRequestQueue:
    def test_backpressure_at_capacity_with_retry_after(self):
        q = RequestQueue(max_depth=2)
        q.submit("a", [1, 2])
        q.submit("b", [3])
        with pytest.raises(Backpressure) as ei:
            q.submit("c", [4])
        assert ei.value.retry_after > 0
        assert ei.value.depth == 2
        assert q.rejected == 1
        # service-time feedback moves the hint
        before = ei.value.retry_after
        q.note_serviced(1, 10.0)
        with pytest.raises(Backpressure) as ei2:
            q.submit("c", [4])
        assert ei2.value.retry_after > before

    def test_expired_requests_fail_and_free_capacity(self):
        clock = FakeClock()
        q = RequestQueue(max_depth=1, clock=clock)
        r = q.submit("a", [1], deadline_s=5.0)
        clock.advance(6.0)
        # the expired head must not hold the door shut
        r2 = q.submit("b", [2], deadline_s=5.0)
        with pytest.raises(DeadlineExceeded):
            r.result(timeout=0)
        assert q.expired == 1 and q.depth == 1
        assert not r2.future.done()

    def test_default_deadline_applies(self):
        clock = FakeClock()
        q = RequestQueue(max_depth=4, default_deadline_s=1.0, clock=clock)
        r = q.submit("a", [1])
        clock.advance(2.0)
        assert q.expire_overdue() == 1
        with pytest.raises(DeadlineExceeded):
            r.result(timeout=0)

    def test_expire_now_sweeps_without_traffic(self):
        # The /v1/cancel + empty-admit-round hook: deadlines burn down
        # even when no arriving submit triggers the admission-side sweep.
        clock = FakeClock()
        q = RequestQueue(max_depth=4, clock=clock)
        r1 = q.submit("a", [1], deadline_s=1.0)
        r2 = q.submit("b", [2], deadline_s=10.0)
        assert q.expire_now() == 0  # nothing overdue yet
        clock.advance(2.0)
        assert q.expire_now() == 1  # no submit needed to reap r1
        with pytest.raises(DeadlineExceeded):
            r1.result(timeout=0)
        assert not r2.future.done()
        assert q.expired == 1 and q.depth == 1
        # a force-expired deadline (the remote-cancel mechanic) reaps too
        r2.deadline = clock() - 0.001
        assert q.expire_now() == 1
        with pytest.raises(DeadlineExceeded):
            r2.result(timeout=0)
        assert q.expired == 2 and q.depth == 0

    def test_fail_all_drains(self):
        q = RequestQueue(max_depth=4)
        rs = [q.submit(str(i), [i]) for i in range(3)]
        assert q.fail_all(RuntimeError("down")) == 3
        for r in rs:
            with pytest.raises(RuntimeError, match="down"):
                r.result(timeout=0)
        assert q.depth == 0


class TestKVSlotPool:
    def test_acquire_release_occupancy(self):
        pool = KVSlotPool(4)
        s0 = pool.try_acquire(owner_id=10)
        s1 = pool.try_acquire(owner_id=11)
        assert {s0, s1} == {0, 1} and pool.in_use == 2
        assert pool.occupancy == 0.5 and pool.high_water == 2
        pool.release(s0)
        assert pool.in_use == 1 and pool.holder(s1) == 11
        assert pool.release_owner(11) == 1
        assert pool.free == 4 and pool.total_released == 2

    def test_exhaustion_and_blocking_acquire(self):
        pool = KVSlotPool(2)
        pool.acquire_many([1, 2], timeout=0)
        assert pool.try_acquire(3) is None
        assert pool.acquire_many([3], timeout=0.01) is None
        # a release from another thread unblocks the waiter
        def free_later():
            time.sleep(0.05)
            pool.release_owner(1)

        t = threading.Thread(target=free_later)
        t.start()
        got = pool.acquire_many([3], timeout=2.0)
        t.join()
        assert got is not None and pool.holder(got[0]) == 3

    def test_all_or_nothing_and_impossible_batch(self):
        pool = KVSlotPool(2)
        with pytest.raises(ValueError, match="never fit"):
            pool.acquire_many([1, 2, 3])
        pool.try_acquire(9)
        # 2 wanted, 1 free → nothing granted
        assert pool.acquire_many([1, 2], timeout=0.01) is None
        assert pool.in_use == 1

    def test_release_unheld_slot_raises(self):
        pool = KVSlotPool(1)
        with pytest.raises(ValueError, match="not held"):
            pool.release(0)
        assert pool.release_owner(42) == 0  # idempotent by-owner free


class TestMetrics:
    def test_percentile_nearest_rank(self):
        assert percentile([], 50) is None
        assert percentile([3.0], 99) == 3.0
        xs = [float(i) for i in range(1, 101)]
        assert percentile(xs, 50) == 50.0
        assert percentile(xs, 99) == 99.0
        assert percentile(xs, 0) == 1.0 and percentile(xs, 100) == 100.0
        with pytest.raises(ValueError):
            percentile(xs, 101)

    def test_histogram_summary(self):
        h = Histogram("x")
        assert h.summary() == {"count": 0}
        for v in (1.0, 2.0, 3.0, 4.0):
            h.record(v)
        s = h.summary()
        assert s["count"] == 4 and s["mean"] == 2.5 and s["max"] == 4.0

    def test_serving_metrics_ledger(self):
        from machine_learning_apache_spark_tpu.serving import ServingMetrics

        clock = FakeClock()
        m = ServingMetrics(clock=clock)
        for _ in range(3):
            m.on_submit()
        m.on_reject()
        m.on_expire()
        clock.advance(2.0)
        m.on_batch(n_requests=2, max_batch=4, decode_s=0.5, new_tokens=20,
                   queue_depth=1, slot_occupancy=0.25)
        m.on_complete(queue_wait=0.1, ttft=0.6, total=0.7)
        s = m.summary()
        assert s["submitted"] == 3 and s["rejected"] == 1 and s["expired"] == 1
        assert s["tokens_out"] == 20 and s["tokens_per_sec"] == 10.0
        assert s["batch_occupancy"]["p50"] == 0.5
        assert m.log_summary()["completed"] == 1

    def test_conservation_check(self):
        from machine_learning_apache_spark_tpu.serving import ServingMetrics
        from machine_learning_apache_spark_tpu.serving.metrics import (
            ConservationError,
        )

        m = ServingMetrics()
        for _ in range(4):
            m.on_submit()
        m.on_complete(queue_wait=0.1, ttft=0.2, total=0.3)
        m.on_reject()
        m.on_expire()
        # 4 submitted = 1 completed + 1 rejected + 1 expired + 1 in flight
        ledger = m.check_conservation(in_flight=1)
        assert ledger["submitted"] == 4 and ledger["in_flight"] == 1
        # ... but claiming zero in flight leaks one request: must raise
        with pytest.raises(ConservationError, match="conservation violated"):
            m.check_conservation(in_flight=0)


def test_jit_cache_size_counts_programs():
    """The compile counter behind ``recompiles_after_warmup``: one entry
    per traced signature."""
    import jax
    import jax.numpy as jnp

    from machine_learning_apache_spark_tpu.utils.compilation_cache import (
        jit_cache_size,
    )

    f = jax.jit(lambda x: x + 1)
    n0 = jit_cache_size(f)
    f(jnp.zeros((2,)))
    f(jnp.zeros((2,)))  # same shape: no new program
    assert jit_cache_size(f) == n0 + 1
    f(jnp.zeros((3,)))
    assert jit_cache_size(f) == n0 + 2


@pytest.fixture(scope="module")
def tiny_translator(make_tiny_translator):
    """Untrained tiny MT bundle over 64 sentence pairs."""
    return make_tiny_translator(64)


class TestEngineE2E:
    def test_concurrent_round_trip_matches_oneshot(self, tiny_translator):
        """32 concurrent clients through the engine produce exactly the
        one-shot ``Translator.__call__`` outputs (chunk padding and row
        sharing must be semantics-free), with zero recompiles after
        warmup."""
        t, texts = tiny_translator
        texts = texts[:32]
        with t.serve(
            boundaries=(8, 16), max_batch=4, max_new_tokens=8,
        ) as eng:
            futs = [eng.submit(s) for s in texts]
            outs = [f.result(timeout=120) for f in futs]
            assert eng.recompiles_after_warmup == 0
            assert eng.metrics.completed == 32
            assert eng.pool.in_use == 0  # every slot freed on EOS
            eng.metrics.check_conservation(in_flight=0)
        assert outs == t(texts, max_new_tokens=8)

    def test_queue_rejects_when_saturated(self, tiny_translator):
        t, texts = tiny_translator
        eng = t.serve(
            boundaries=(8, 16), max_batch=2, max_queue_depth=2,
            max_new_tokens=4, start=False,
        )
        eng.start(warmup=False)  # cold engine: first batch compiles slowly,
        try:                     # so the queue genuinely backs up
            hits = 0
            for i in range(40):
                try:
                    eng.submit(texts[i % len(texts)])
                except Backpressure as e:
                    hits += 1
                    assert e.retry_after > 0
            assert hits > 0
            assert eng.metrics.rejected == hits
        finally:
            eng.stop()
        # every attempt accounted: rejected at the door, completed before
        # stop, or failed by it — nothing vanishes
        eng.metrics.check_conservation(in_flight=0)

    def test_deadline_expiry_frees_slots_and_fails_future(
        self, tiny_translator
    ):
        t, texts = tiny_translator
        eng = t.serve(
            boundaries=(8, 16), max_batch=2, max_new_tokens=4, start=False
        )
        eng.start(warmup=False)
        try:
            # deadline_s=0 is expired the instant it lands: the batcher's
            # sweep must fail it without decoding it or taking a slot
            req = eng.submit(texts[0], deadline_s=0.0)
            with pytest.raises(DeadlineExceeded):
                req.result(timeout=30)
            assert eng.pool.in_use == 0
            deadline = time.monotonic() + 10
            while eng.metrics.expired < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert eng.metrics.expired == 1
        finally:
            eng.stop()

    def test_oversized_input_rejected_at_submit(self, tiny_translator):
        t, _ = tiny_translator
        with t.serve(boundaries=(8,), max_batch=2, max_new_tokens=4) as eng:
            with pytest.raises(ValueError, match="largest prompt boundary"):
                eng.submit("w " * 30)

    def test_stop_fails_queued_requests(self, tiny_translator):
        from machine_learning_apache_spark_tpu.serving.engine import (
            EngineStopped,
        )

        t, texts = tiny_translator
        short = [s for s in texts if len(s.split()) <= 5][:3]
        eng = t.serve(
            boundaries=(8,), max_batch=8, max_new_tokens=4, start=False,
        )
        eng.start(warmup=False)
        reqs = [eng.submit(s) for s in short]
        eng.stop()
        # a cold engine is still compiling its first program: whether a
        # request was still queued or already on a row, it must fail
        # loudly, never hang
        for r in reqs:
            with pytest.raises(EngineStopped):
                r.result(timeout=5)
        ledger = eng.metrics.check_conservation(in_flight=0)
        assert ledger["submitted"] == 3 and ledger["failed"] == 3


class TestKVPagePool:
    def test_round_trip_never_hands_out_null_page(self):
        from machine_learning_apache_spark_tpu.serving import (
            NULL_PAGE,
            KVPagePool,
        )

        pool = KVPagePool(8)
        assert pool.capacity == 7
        pages = pool.try_acquire(3, "a")
        assert pages is not None and len(pages) == 3
        assert NULL_PAGE not in pages
        assert pool.in_use == 3 and pool.high_water == 3
        assert pool.release_owner("a") == 3
        assert pool.in_use == 0 and pool.free == 7
        assert pool.total_acquired == 3 and pool.total_released == 3
        # idempotent: an owner with no refs frees zero
        assert pool.release_owner("a") == 0

    def test_try_acquire_insufficient_returns_none(self):
        from machine_learning_apache_spark_tpu.serving import KVPagePool

        pool = KVPagePool(4)  # 3 allocatable
        assert pool.try_acquire(4, "a") is None
        assert pool.in_use == 0  # all-or-nothing: no partial grant

    def test_refcounted_prefix_pages_survive_owner_release(self):
        from machine_learning_apache_spark_tpu.serving import KVPagePool

        pool = KVPagePool(8)
        shared = pool.try_acquire(2, "req1")
        pool.add_ref(shared, "req2")
        assert all(pool.refcount(p) == 2 for p in shared)
        # first holder leaves: pages must stay allocated for the second
        assert pool.release_owner("req1") == 0
        assert pool.in_use == 2
        assert all(pool.refcount(p) == 1 for p in shared)
        assert pool.release_owner("req2") == 2
        assert pool.in_use == 0

    def test_add_ref_rejects_unallocated_and_null(self):
        from machine_learning_apache_spark_tpu.serving import (
            NULL_PAGE,
            KVPagePool,
        )

        pool = KVPagePool(8)
        with pytest.raises(ValueError, match="not allocated"):
            pool.add_ref([5], "x")
        with pytest.raises(ValueError, match="not allocated"):
            pool.add_ref([NULL_PAGE], "x")

    def test_blocking_acquire_is_fifo_fair(self):
        """A waiting all-or-nothing grant must not be starved by later
        try_acquire calls skimming pages as they free."""
        from machine_learning_apache_spark_tpu.serving import KVPagePool

        pool = KVPagePool(4)  # 3 allocatable
        pool.try_acquire(3, "hog")
        got = []
        waiter = threading.Thread(
            target=lambda: got.append(pool.acquire(3, "first", timeout=10))
        )
        waiter.start()
        deadline = time.monotonic() + 5
        while not pool._tickets and time.monotonic() < deadline:
            time.sleep(0.001)
        # a later non-blocking grab yields to the queued waiter
        assert pool.try_acquire(1, "sneak") is None
        pool.release_owner("hog")
        waiter.join(timeout=10)
        assert got and got[0] is not None and len(got[0]) == 3
        assert pool.pages_of("first") == got[0]

    def test_acquire_validation(self):
        from machine_learning_apache_spark_tpu.serving import KVPagePool

        pool = KVPagePool(4)
        with pytest.raises(ValueError, match="never fit"):
            pool.acquire(4, "a")
        with pytest.raises(ValueError, match=">= 0"):
            pool.try_acquire(-1, "a")
        pool.try_acquire(3, "hold")
        assert pool.acquire(1, "b", timeout=0.01) is None  # times out

    def test_byte_accounting_tracks_dtype_page_cost(self):
        """With ``page_bytes`` set (the runtime passes its dtype-aware
        per-page cost, scale planes included) the pool reports live and
        high-water byte figures; without it the byte gauges stay None
        rather than lying."""
        from machine_learning_apache_spark_tpu.serving import KVPagePool

        pool = KVPagePool(8, page_bytes=576)
        assert pool.page_bytes == 576
        assert pool.bytes_capacity == 7 * 576
        pool.try_acquire(3, "a")
        assert pool.bytes_in_use == 3 * 576
        assert pool.bytes_high_water == 3 * 576
        pool.release_owner("a")
        assert pool.bytes_in_use == 0
        assert pool.bytes_high_water == 3 * 576  # high-water sticks
        with pytest.raises(ValueError, match="page_bytes"):
            KVPagePool(8, page_bytes=0)
        bare = KVPagePool(8)
        assert bare.page_bytes is None
        assert bare.bytes_in_use is None
        assert bare.bytes_high_water is None
        assert bare.bytes_capacity is None


class TestPrefixCache:
    def _mk(self, num_pages=16, capacity=4):
        from machine_learning_apache_spark_tpu.serving import (
            KVPagePool,
            PrefixCache,
        )

        pool = KVPagePool(num_pages)
        return pool, PrefixCache(pool, capacity)

    def test_hit_attaches_requester_ref(self):
        pool, cache = self._mk()
        pages = pool.try_acquire(2, "req1")
        assert cache.put((1, 2, 3), pages, width=8)
        pool.release_owner("req1")
        # cache ref keeps the prefix alive after the prefiller left
        assert pool.in_use == 2
        entry = cache.get((1, 2, 3), owner="req2")
        assert entry is not None and entry["pages"] == pages
        assert entry["width"] == 8
        assert all(pool.refcount(p) == 2 for p in pages)
        assert cache.stats()["hits"] == 1
        assert cache.get((9,), owner="req3") is None
        assert cache.stats()["misses"] == 1

    def test_eviction_frees_only_unreferenced_pages(self):
        pool, cache = self._mk(capacity=1)
        a = pool.try_acquire(1, "r1")
        cache.put(("a",), a)
        cache.get(("a",), owner="r1-decode")  # a live request attaches
        b = pool.try_acquire(1, "r2")
        cache.put(("b",), b)  # capacity 1: evicts ("a",)
        assert len(cache) == 1 and cache.stats()["evictions"] == 1
        # evicted entry's page survives until every holder releases
        assert pool.refcount(a[0]) >= 1
        pool.release_owner("r1")
        pool.release_owner("r1-decode")
        assert pool.refcount(a[0]) == 0

    def test_evict_until_free_pressure_valve(self):
        pool, cache = self._mk(num_pages=6, capacity=8)  # 5 allocatable
        for key in ("a", "b", "c"):
            pages = pool.try_acquire(1, key)
            cache.put((key,), pages)
            pool.release_owner(key)
        assert pool.free == 2
        cache.evict_until_free(4)
        assert pool.free >= 4
        assert len(cache) == 1  # LRU shed, newest survives

    def test_flush_drops_everything(self):
        pool, cache = self._mk()
        for key in ("a", "b"):
            pages = pool.try_acquire(1, key)
            cache.put((key,), pages)
            pool.release_owner(key)
        assert cache.flush() == 2
        assert len(cache) == 0 and pool.in_use == 0

    def test_zero_capacity_disables(self):
        pool, cache = self._mk(capacity=0)
        pages = pool.try_acquire(1, "r")
        assert cache.put(("a",), pages) is False
        pool.release_owner("r")
        assert pool.in_use == 0  # no silent cache ref was taken

    def test_stats_reports_resident_footprint(self):
        """The cache's stats carry its page/byte footprint — the number
        the capacity-planning gauges scrape — priced at the pool's
        dtype-aware page cost when one was declared."""
        from machine_learning_apache_spark_tpu.serving import (
            KVPagePool,
            PrefixCache,
        )

        pool = KVPagePool(16, page_bytes=2048)
        cache = PrefixCache(pool, 4)
        for key in ("a", "b"):
            pages = pool.try_acquire(2, key)
            cache.put((key,), pages)
            pool.release_owner(key)
        st = cache.stats()
        assert st["resident_pages"] == 4
        assert st["resident_bytes"] == 4 * 2048
        pool2, cache2 = self._mk()  # no page_bytes: pages count, bytes None
        pages = pool2.try_acquire(1, "x")
        cache2.put(("x",), pages)
        pool2.release_owner("x")
        assert cache2.stats()["resident_pages"] == 1
        assert cache2.stats()["resident_bytes"] is None

    def test_contains_is_side_effect_free(self):
        pool, cache = self._mk()
        pages = pool.try_acquire(1, "r")
        cache.put(("a",), pages)
        pool.release_owner("r")
        before = cache.stats()
        assert cache.contains(("a",)) is True
        assert cache.contains(("nope",)) is False
        after = cache.stats()
        # no hit/miss accounting, no LRU bump, no reference attached
        assert after == before
        assert all(pool.refcount(p) == 1 for p in pages)


class TestTokenBudgetBatcher:
    def _mk(self, chunk=4, clock=None):
        from machine_learning_apache_spark_tpu.serving import (
            TokenBudgetBatcher,
        )

        q = RequestQueue(max_depth=64, clock=clock or time.monotonic)
        return q, TokenBudgetBatcher(q, chunk=chunk)

    def test_cost_rounds_to_chunk_grid(self):
        _, b = self._mk(chunk=4)
        assert b.cost([1]) == 4
        assert b.cost([1, 2, 3, 4]) == 4
        assert b.cost([1] * 5) == 8
        assert b.cost([]) == 4  # empty prompt still costs one chunk

    def test_fifo_prefix_under_budget(self):
        q, b = self._mk(chunk=4)
        q.submit("long", list(range(10)))  # cost 12
        q.submit("s1", [1, 2, 3])  # cost 4
        q.submit("s2", [4, 5, 6])  # cost 4
        taken = b.take(max_requests=8, token_budget=16)
        assert [r.text for r in taken] == ["long", "s1"]
        # never skips the big head in favour of cheap ones behind it
        taken = b.take(max_requests=8, token_budget=16)
        assert [r.text for r in taken] == ["s2"]

    def test_head_always_granted(self):
        q, b = self._mk(chunk=4)
        q.submit("huge", list(range(12)))  # cost 12 > budget
        taken = b.take(max_requests=8, token_budget=4)
        assert [r.text for r in taken] == ["huge"]

    def test_max_requests_and_empty_timeout(self):
        q, b = self._mk()
        q.submit("a", [1])
        q.submit("b", [2])
        assert b.take(max_requests=0, token_budget=100) == []
        taken = b.take(max_requests=1, token_budget=100)
        assert [r.text for r in taken] == ["a"]
        b.take(max_requests=8, token_budget=100)  # drains "b"
        t0 = time.monotonic()
        assert b.take(max_requests=8, token_budget=100, timeout=0.05) == []
        assert time.monotonic() - t0 < 2.0

    def test_cost_fn_override_prices_admission(self):
        # The engine prices prefix-cache hits at zero: a budget that
        # admits one cold prompt admits any number of cached ones.
        q, b = self._mk(chunk=4)
        for i in range(4):
            q.submit(f"hit{i}", [i])  # default cost 4 each
        q.submit("miss", list(range(6)))  # cost 8
        taken = b.take(
            max_requests=8, token_budget=8,
            cost_fn=lambda r: 0 if r.text.startswith("hit") else 8,
        )
        assert [r.text for r in taken] == [
            "hit0", "hit1", "hit2", "hit3", "miss"
        ]
        # default pricing would have stopped after two chunk-4 prompts
        q2, b2 = self._mk(chunk=4)
        for i in range(4):
            q2.submit(f"hit{i}", [i])
        taken = b2.take(max_requests=8, token_budget=8)
        assert len(taken) == 2

    def test_expired_swept_not_taken(self):
        clock = FakeClock()
        q, b = self._mk(clock=clock)
        dead = q.submit("dead", [1], deadline_s=1.0)
        clock.advance(2.0)
        q.submit("live", [2], deadline_s=10.0)
        taken = b.take(max_requests=8, token_budget=100)
        assert [r.text for r in taken] == ["live"]
        with pytest.raises(DeadlineExceeded):
            dead.result(timeout=0)


class TestKVSlotPoolFairness:
    def test_blocked_batch_not_starved_by_try_acquire(self):
        pool = KVSlotPool(2)
        pool.try_acquire(100)
        pool.try_acquire(101)
        got = []
        waiter = threading.Thread(
            target=lambda: got.append(
                pool.acquire_many([200, 201], timeout=10)
            )
        )
        waiter.start()
        deadline = time.monotonic() + 5
        while not pool._tickets and time.monotonic() < deadline:
            time.sleep(0.001)
        pool.release_owner(100)
        # one slot free, but it belongs to the queued batch — a latecomer
        # must not skim it
        assert pool.try_acquire(300) is None
        pool.release_owner(101)
        waiter.join(timeout=10)
        assert got and got[0] is not None and len(got[0]) == 2
        assert pool.in_use == 2


class TestPagedEngine:
    def test_mesh_trained_params_serve_from_one_device(self, tiny_translator):
        """Params trained under a mesh arrive replicated over all of its
        devices. The paged runtime lives on one: left on the mesh, the
        stores change sharding after the first call and every program
        recompiles once serving starts (found by chip_smoke on 4 chips)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from machine_learning_apache_spark_tpu.inference import Translator
        from machine_learning_apache_spark_tpu.parallel import (
            data_parallel_mesh,
        )

        t, texts = tiny_translator
        replicated = jax.device_put(
            t.params, NamedSharding(data_parallel_mesh(), P())
        )
        on_mesh = Translator(t.model, replicated, t.src_pipe, t.trg_pipe)
        with on_mesh.serve(
            boundaries=(8, 16), max_batch=4, max_new_tokens=8,
        ) as eng:
            outs = [r.result(timeout=120) for r in
                    [eng.submit(s) for s in texts[:6]]]
            assert eng.recompiles_after_warmup == 0
            assert len(eng.runtime.kv_mem.sharding.device_set) == 1
            assert eng.runtime.kv_mem.sharding.device_set == {
                eng.runtime.device
            }
        assert outs == t(texts[:6], max_new_tokens=8)

    def test_zero_recompiles_across_ragged_occupancies(self, tiny_translator):
        """The paged tentpole invariant: after warmup, every wave shape —
        occupancy 1..max_active, short and long prompts interleaved,
        repeat prompts hitting the prefix cache — runs the same compiled
        programs."""
        t, texts = tiny_translator
        short = [s for s in texts if len(s.split()) <= 5]
        long_ = [s for s in texts if len(s.split()) >= 7]
        with t.serve(
            boundaries=(8, 16), max_batch=4, max_new_tokens=8,
        ) as eng:
            waves = [
                short[:1],                  # single row
                long_[:3],                  # partial, long prompts
                short[:2] + long_[3:5],     # full, mixed lengths
                short[:1],                  # repeat: prefix-cache hit
            ]
            expect = []
            for wave in waves:
                outs = [f.result(timeout=120) for f in
                        [eng.submit(s) for s in wave]]
                expect.append((wave, outs))
            assert eng.recompiles_after_warmup == 0
            assert eng.runtime.mem_pool.in_use >= 0
            m = eng.metrics
            assert 0 < m.real_tokens <= m.padded_tokens
            assert 0.0 <= m.padding_waste < 1.0
            stats = eng.runtime.stats()
            assert stats["prefix_cache"]["hits"] >= 1
            eng.metrics.check_conservation(in_flight=0)
        for wave, outs in expect:
            assert outs == t(wave, max_new_tokens=8)

    @pytest.mark.parametrize(
        "max_active,steps_per_launch",
        [(1, 1), (1, 4), (2, 2), (4, 1), (4, 3), (8, 4)],
    )
    def test_paged_matches_oneshot_across_launch_shapes(
        self, tiny_translator, max_active, steps_per_launch
    ):
        """Whatever the launch program's shape — one row or eight, one
        decode step a dispatch or four, a generation that ends inside a
        launch or on its edge — the engine's answers are the one-shot
        greedy decoder's, token for token."""
        t, texts = tiny_translator
        short = [s for s in texts if len(s.split()) <= 5][:6]
        long_ = [s for s in texts if len(s.split()) >= 7][:6]
        mixed = [s for pair in zip(short, long_) for s in pair]
        assert len(mixed) == 12
        with t.serve(
            boundaries=(8, 16), max_active=max_active,
            steps_per_launch=steps_per_launch, max_new_tokens=8,
        ) as eng:
            outs = [f.result(timeout=120) for f in
                    [eng.submit(s) for s in mixed]]
            assert eng.recompiles_after_warmup == 0
            eng.metrics.check_conservation(in_flight=0)
        assert outs == t(mixed, method="greedy", max_new_tokens=8)

    def test_engine_owns_only_the_runtimes_programs(self, tiny_translator):
        """One loop, one program set: what ``warmup()`` compiled is all
        the engine counts, and nothing of a second decoder is built."""
        t, _ = tiny_translator
        eng = t.serve(boundaries=(8, 16), max_batch=2, max_new_tokens=4,
                      start=False)
        n = eng.warmup()
        assert n == len(eng.runtime.jit_fns()) == eng.compile_count()
        assert eng.recompiles_after_warmup == 0
        assert not hasattr(eng, "_decoders")
        assert not hasattr(eng, "batcher")

    @pytest.mark.parametrize(
        "knob,value",
        [
            ("kv_mode", "paged"), ("method", "greedy"), ("max_wait_s", 0.01),
            ("num_slots", 8), ("beam_size", 2), ("length_penalty", 0.6),
        ],
    )
    def test_removed_serving_knobs_are_rejected(
        self, tiny_translator, knob, value
    ):
        """The padded engine's knobs went with it: passing one fails as
        any unknown keyword does, instead of being accepted and ignored."""
        t, _ = tiny_translator
        with pytest.raises(TypeError, match=knob):
            t.serve(boundaries=(8,), max_batch=2, start=False,
                    **{knob: value})

    def test_kv_dtype_validation_and_env_override(self, tiny_translator):
        t, _ = tiny_translator
        import os

        with pytest.raises(ValueError, match="kv_dtype"):
            t.serve(boundaries=(8,), max_batch=2, kv_dtype="int4",
                    start=False)
        os.environ["MLSPARK_SERVE_KV_DTYPE"] = "int8"
        try:
            eng = t.serve(boundaries=(8,), max_batch=2, start=False)
            assert eng.kv_dtype == "int8"
            assert eng.runtime.stats()["kv_dtype"] == "int8"
            # explicit argument beats the env contract
            eng = t.serve(boundaries=(8,), max_batch=2,
                          kv_dtype="float32", start=False)
            assert eng.kv_dtype == "float32"
        finally:
            del os.environ["MLSPARK_SERVE_KV_DTYPE"]

    def test_int8_engine_zero_recompiles_and_tracks_fp32(
        self, tiny_translator
    ):
        """The quantized plane rides the same compiled programs: after
        warmup an int8 engine (both stores quantized) serves every wave
        shape — including prefix-cache hits, whose scales must travel
        with the shared pages — with zero recompiles, honest dtype-aware
        byte accounting, and near-oracle greedy outputs."""
        t, texts = tiny_translator
        short = [s for s in texts if len(s.split()) <= 5]
        long_ = [s for s in texts if len(s.split()) >= 7]
        waves = [
            short[:1],                  # single row
            long_[:3],                  # partial, long prompts
            short[:2] + long_[3:5],     # full, mixed lengths
            short[:1],                  # repeat: prefix-cache hit
        ]
        with t.serve(
            boundaries=(8, 16), max_batch=4,
            max_new_tokens=8, kv_dtype="int8",
            quantize_self=True,
        ) as eng:
            got = []
            for wave in waves:
                outs = [f.result(timeout=120) for f in
                        [eng.submit(s) for s in wave]]
                got.append((wave, outs))
            assert eng.recompiles_after_warmup == 0
            stats = eng.runtime.stats()
            assert stats["kv_dtype"] == "int8"
            assert stats["quantize_self"] is True
            assert stats["prefix_cache"]["hits"] >= 1
            # int8 page + fp32 scale per slot < fp32 page
            fp32_eng = t.serve(boundaries=(8, 16), max_batch=4,
                               start=False)
            fp32_page = fp32_eng.runtime.stats()["mem_page_bytes"]
            assert stats["mem_page_bytes"] < fp32_page / 2
            assert stats["mem_bytes_high_water"] > 0
            eng.metrics.check_conservation(in_flight=0)
        # Scale travel: the cache-hit repeat of wave 0 decoded from
        # shared pages + shared scales — byte-identical outputs.
        assert got[3][1] == got[0][1]
        # Accuracy oracle: greedy token agreement with the fp32 path.
        matched = total = 0
        for wave, outs in got:
            oracle = t(wave, max_new_tokens=8)
            for a, b in zip(oracle, outs):
                ta = t.trg_pipe.ragged([a])[0]
                tb = t.trg_pipe.ragged([b])[0]
                agree = 0
                for x, y in zip(ta, tb):
                    if x != y:
                        break
                    agree += 1
                matched += agree
                total += max(len(ta), len(tb))
        assert total > 0 and matched / total >= 0.9

    def test_paged_pages_freed_on_completion(self, tiny_translator):
        t, texts = tiny_translator
        with t.serve(
            boundaries=(8, 16), max_batch=4, max_new_tokens=8,
            prefix_cache_size=0,
        ) as eng:
            [f.result(timeout=120) for f in
             [eng.submit(s) for s in texts[:8]]]
            assert eng.pool.in_use == 0  # decode rows
            # no prefix cache: every request's pages fully returned
            assert eng.runtime.mem_pool.in_use == 0
            assert eng.runtime.self_pool.in_use == 0


def test_serve_bench_smoke_subprocess(tmp_path):
    """tools/serve_bench.py --smoke is the tier-1 CI entry: fresh
    process, engine-vs-one-shot parity gate, the int8 accuracy (token
    match) and capacity (equal-byte ceiling) gates, and short paged +
    paged-int8 sweeps with the zero-recompile and conservation gates."""
    import json
    import os
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "serve_bench.json"
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(repo_root, "tools", "serve_bench.py"),
            "--smoke", "--out", str(out),
        ],
        capture_output=True, text=True, timeout=480,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    art = json.loads(out.read_text())
    assert art["ok"] is True
    assert art["gates"] == {
        "parity": True,
        "token_match": True,
        "int8_ceiling": True,
        "zero_recompiles": True,
        "conservation": True,
        "midload_scrape": True,
    }
    assert art["parity"]["identical"] is True
    assert art["token_match"]["token_match_rate"] >= 0.99
    ceiling = art["concurrency_ceiling"]
    assert ceiling["int8_ceiling_vs_fp32"] >= 2.0
    assert (
        ceiling["int8"]["bytes_per_resident_seq"]
        < ceiling["float32"]["bytes_per_resident_seq"]
    )
    scrape = art["modes"]["paged"]["midload_scrape"]
    assert scrape["ok"] is True
    assert 0 <= scrape["in_flight"] <= scrape["in_flight_cap"]
    assert scrape["metrics_bytes"] > 0
    rows = art["modes"]["paged"]["rows"]
    assert rows and all(row["completed"] > 0 for row in rows)
    summary = art["modes"]["paged"]["engine_summary"]
    assert summary["padding_waste"] is not None
    assert art["modes"]["paged"]["paged_runtime"]["prefix_cache"]["hits"] > 0
    # The int8 column serves the same sweep on the same programs.
    int8 = art["modes"]["paged-int8"]
    assert int8["recompiles_after_warmup"] == 0
    assert int8["rows"] and all(r["completed"] > 0 for r in int8["rows"])
    assert int8["paged_runtime"]["kv_dtype"] == "int8"
    assert int8["paged_runtime"]["quantize_self"] is True


def _http_get(url, timeout=10.0):
    """(body, status) for one scrape; HTTP errors still return their body
    (a 503 /healthz carries the degraded payload)."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read().decode("utf-8"), r.status
    except urllib.error.HTTPError as e:
        return e.read().decode("utf-8"), e.code


def _http_get_json(url, timeout=10.0):
    import json

    body, code = _http_get(url, timeout)
    return json.loads(body), code


class TestObservabilityPlane:
    """The live plane over a serving engine (docs/OBSERVABILITY.md "Live
    plane"): per-request trace timelines, the /healthz verdict flipping
    with quarantine and supervisor restarts, and concurrent /metrics
    scrapes while decode runs."""

    @pytest.fixture(autouse=True)
    def _fresh_plane(self, monkeypatch):
        from machine_learning_apache_spark_tpu import telemetry

        monkeypatch.delenv("MLSPARK_TELEMETRY", raising=False)
        monkeypatch.delenv("MLSPARK_TELEMETRY_DIR", raising=False)
        monkeypatch.setenv("MLSPARK_TELEMETRY_HTTP", "0")  # ephemeral port
        telemetry.reset()
        yield
        telemetry.reset()

    def test_request_trace_timeline_end_to_end(self, tiny_translator):
        """Every request carries a trace from submit to completion: the
        mark vocabulary is present in order, the derived breakdown is
        sane, each request's annotation names the batches that served it,
        and the engine keeps the slowest traces as exemplars."""
        from machine_learning_apache_spark_tpu import telemetry

        t, texts = tiny_translator
        with t.serve(
            boundaries=(8, 16), max_batch=4, max_new_tokens=8,
        ) as eng:
            futs = [eng.submit(s) for s in texts[:8]]
            [f.result(timeout=120) for f in futs]
            ids = {f.trace.trace_id for f in futs}
            assert len(ids) == 8  # ids are unique
            for f in futs:
                names = [m[0] for m in f.trace.marks]
                assert names[0] == "submit"
                for required in ("batched", "admit", "first_token",
                                 "complete"):
                    assert required in names, names
                bd = f.trace.breakdown()
                assert bd["queue_wait_s"] >= 0.0
                assert bd["ttft_s"] > 0.0
                assert bd["service_s"] > 0.0
                assert bd["total_s"] >= bd["ttft_s"]
                assert f.trace.launches >= 1
            # the batch↔request join, kept from the request's side: every
            # ``serving.request`` annotation names the first and the last
            # batch (``seq``) that served it, and each is a batch the log
            # holds.
            events = telemetry.get_log().snapshot()
            batch_seqs = {
                e.attrs["seq"] for e in events
                if e.kind == "span_end" and e.name == "serving.batch"
            }
            assert batch_seqs
            assert not any(
                "requests" in (e.attrs or {}) for e in events
                if e.name == "serving.batch"
            )
            joined = {
                e.attrs["trace_id"]: e.attrs for e in events
                if e.kind == "annotation" and e.name == "serving.request"
            }
            assert ids <= set(joined)
            for f in futs:
                a = joined[f.trace.trace_id]
                assert a["first_batch"] in batch_seqs
                assert a["last_batch"] in batch_seqs
                assert (
                    a["last_batch"] - a["first_batch"] + 1 >= a["launches"] >= 1
                )
            # slowest-request exemplars, sorted worst-first
            ex = eng.metrics.request_exemplars()
            assert 1 <= len(ex) <= 8
            assert {e["trace_id"] for e in ex} <= ids
            totals = [e["total_s"] for e in ex]
            assert totals == sorted(totals, reverse=True)
            assert all(e["timeline"] for e in ex)
            led = eng.metrics.ledger()
            assert led["completed"] == 8 and led["in_flight"] == 0

    def test_healthz_flips_on_quarantine_then_recovers(
        self, tiny_translator, tmp_path, monkeypatch
    ):
        """A quarantined batch turns /healthz 503/degraded; the next
        successful batch flips it back to 200/ok. The quarantine flight
        dump carries every victim's trace timeline."""
        from machine_learning_apache_spark_tpu import telemetry
        from machine_learning_apache_spark_tpu.serving import InternalError
        from machine_learning_apache_spark_tpu.telemetry import recorder
        from machine_learning_apache_spark_tpu.utils import faults
        from machine_learning_apache_spark_tpu.utils.faults import FaultPlan

        monkeypatch.setenv("MLSPARK_TELEMETRY_DIR", str(tmp_path))
        telemetry.reset()
        t, texts = tiny_translator
        faults.clear()
        faults.install(FaultPlan.from_spec("raise@decode_batch:batch=0"))
        try:
            with t.serve(
                boundaries=(8, 16), max_batch=4, max_new_tokens=8,
            ) as eng:
                srv = telemetry.get_http_server()
                assert srv is not None
                # one request -> one poisoned batch -> quarantine
                victim = eng.submit(texts[0])
                with pytest.raises(InternalError):
                    victim.result(timeout=120)
                deadline = time.monotonic() + 10
                payload = code = None
                while time.monotonic() < deadline:
                    payload, code = _http_get_json(srv.url("/healthz"))
                    if code == 503:
                        break
                    time.sleep(0.01)
                assert code == 503 and payload["status"] == "degraded"
                check = payload["checks"]["serving"]
                assert check["healthy"] is False
                assert check["quarantined"] >= 1
                # flight dump landed with the victim's full timeline.
                # The quarantine dump is written by the worker thread
                # AFTER the victim's future fails (it overwrites the
                # fault-site dump at the same path), so poll for it.
                dump = {}
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    dump = recorder.load_flight(
                        recorder.flight_path(str(tmp_path))
                    )
                    if "request_traces" in dump.get("extra", {}):
                        break
                    time.sleep(0.01)
                traces = dump["extra"]["request_traces"]
                assert traces and traces[0]["trace_id"] == \
                    victim.trace.trace_id
                marks = [m["event"] for m in traces[0]["timeline"]]
                assert "failed" in marks
                # next successful batch flips the verdict back
                ok = eng.submit(texts[1]).result(timeout=120)
                assert isinstance(ok, str)
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    payload, code = _http_get_json(srv.url("/healthz"))
                    if code == 200:
                        break
                    time.sleep(0.01)
                assert code == 200 and payload["status"] == "ok"
                assert payload["checks"]["serving"]["healthy"] is True
        finally:
            faults.clear()

    def test_healthz_survives_supervisor_restart(self, tiny_translator):
        """The outer containment ring is visible on the plane: a decode
        loop death is restarted by the supervisor and /healthz reports
        ok with the restart counted."""
        from machine_learning_apache_spark_tpu import telemetry

        t, texts = tiny_translator
        eng = t.serve(
            boundaries=(8, 16), max_batch=4, max_new_tokens=8, start=False
        )
        real = eng._paged_loop
        died = {"n": 0}

        def dying_then_real():
            if died["n"] == 0:
                died["n"] += 1
                raise RuntimeError("decode loop death (injected)")
            real()

        eng._paged_loop = dying_then_real
        eng.start()
        try:
            srv = telemetry.get_http_server()
            assert srv is not None
            out = eng.submit(texts[0]).result(timeout=120)
            assert isinstance(out, str)
            payload, code = _http_get_json(srv.url("/healthz"))
            assert code == 200 and payload["status"] == "ok"
            assert payload["checks"]["serving"]["loop_restarts"] == 1
            assert payload["checks"]["serving"]["worker_alive"] is True
        finally:
            eng.stop()

    def test_concurrent_scrapes_under_decode_load(self, tiny_translator):
        """4 scraper threads hammer /metrics and /statusz while 24
        requests decode: every scrape answers 200, every mid-flight
        ledger balances, and serving results are unaffected."""
        from machine_learning_apache_spark_tpu import telemetry

        t, texts = tiny_translator
        with t.serve(
            boundaries=(8, 16), max_batch=4, max_new_tokens=8,
        ) as eng:
            srv = telemetry.get_http_server()
            assert srv is not None
            stop = threading.Event()
            failures, ledgers = [], []

            def scraper():
                try:
                    while not stop.is_set():
                        body, code = _http_get(srv.url("/metrics"))
                        assert code == 200 and "mlspark_serving_" in body
                        payload, code = _http_get_json(srv.url("/statusz"))
                        assert code == 200
                        led = payload["sections"]["serving"]["ledger"]
                        assert led["in_flight"] >= 0
                        assert led["submitted"] == (
                            led["completed"] + led["rejected"]
                            + led["expired"] + led["failed"]
                            + led["in_flight"]
                        )
                        ledgers.append(led)
                except Exception as e:  # noqa: BLE001 — reported below
                    failures.append(e)

            threads = [
                threading.Thread(target=scraper, daemon=True)
                for _ in range(4)
            ]
            for th in threads:
                th.start()
            try:
                futs = [eng.submit(s) for s in texts[:24]]
                outs = [f.result(timeout=120) for f in futs]
            finally:
                stop.set()
                for th in threads:
                    th.join(timeout=30)
            assert not failures, failures
            assert len(outs) == 24 and ledgers
            assert max(led["submitted"] for led in ledgers) <= 24
            eng.metrics.check_conservation(in_flight=0)


class TestPagedCancellation:
    """Satellite of the fleet cancellation tentpole: the engine-side reap
    (the mechanic behind ``POST /v1/cancel`` and deadline burn-down) must
    leave NO residue — pages, launch slots, prefix-cache refcounts, and
    the compiled program set all return exactly to their pre-wave state,
    and the conservation ledger still closes."""

    @staticmethod
    def _prefix_refcounts(runtime):
        """Cache key -> per-page refcounts, via the pool's public
        refcount probe (entry enumeration is unavoidably internal)."""
        cache = runtime.prefix_cache
        with cache._lock:
            pages = {k: list(e["pages"]) for k, e in cache._entries.items()}
        return {
            k: [runtime.mem_pool.refcount(p) for p in ps]
            for k, ps in pages.items()
        }

    @pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
    def test_cancel_mid_decode_restores_pool_and_cache(
        self, tiny_translator, kv_dtype
    ):
        t, texts = tiny_translator
        wave = texts[:4]
        with t.serve(
            boundaries=(8, 16), max_batch=4,
            max_new_tokens=8, kv_dtype=kv_dtype,
            steps_per_launch=1,
        ) as eng:
            # Warm wave: completes normally and seeds the prefix cache,
            # so the baseline below includes cached (shared) pages.
            for f in [eng.submit(s, deadline_s=120.0) for s in wave]:
                f.result(timeout=120)
            base_in_use = eng.runtime.mem_pool.in_use
            base_refs = self._prefix_refcounts(eng.runtime)
            assert eng.pool.in_use == 0
            assert eng.recompiles_after_warmup == 0

            # Cancel wave: same prompts, generous deadline. As soon as a
            # row goes active, pull its deadline to the past — exactly
            # what ReplicaServer.cancel does — and let the engine's
            # between-launch sweep (every step: steps_per_launch=1) reap
            # it instead of decoding tokens nobody will read.
            futs = [eng.submit(s, deadline_s=120.0) for s in wave]
            cancelled = set()
            t_end = time.time() + 30.0
            while len(cancelled) < len(wave) and time.time() < t_end:
                for _row, req in eng.runtime.active_rows():
                    if req.id not in cancelled:
                        req.deadline = 0.0
                        cancelled.add(req.id)
                time.sleep(0.001)
            assert len(cancelled) == len(wave)
            n_expired = 0
            for f in futs:
                try:
                    f.result(timeout=60)
                except DeadlineExceeded:
                    n_expired += 1
            # A ~1ms poll against one-step launches: every row is seen
            # and reaped before it can decode to completion.
            assert n_expired == len(wave)
            assert eng.metrics.expired_in_flight >= 1

            # Hygiene: everything the cancelled wave held is back.
            assert eng.runtime.mem_pool.in_use == base_in_use
            assert self._prefix_refcounts(eng.runtime) == base_refs
            assert eng.pool.in_use == 0
            assert eng.recompiles_after_warmup == 0
            eng.metrics.check_conservation(in_flight=0)

            # The engine still serves cleanly after the reap wave — the
            # cancelled rows left no poisoned state behind.
            again = [eng.submit(s, deadline_s=120.0) for s in wave]
            outs = [f.result(timeout=120) for f in again]
            assert all(isinstance(o, str) for o in outs)
            assert eng.recompiles_after_warmup == 0
            eng.metrics.check_conservation(in_flight=0)

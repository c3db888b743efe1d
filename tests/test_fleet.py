"""fleet/: replica router, scrape plane, SLO admission, affinity, and
the per-replica data plane (docs/FLEET.md).

Policy decisions are unit-tested on synthetic ``ReplicaSnapshot`` maps
(no sockets); the dispatch loop is tested against a monkeypatched
``ReplicaClient`` with scripted replica behavior (refusals, pushback,
mid-request loss); the ``ReplicaServer`` data plane runs for real on an
ephemeral port over a fake engine (no JAX); and the end-to-end gang +
router path rides ``tools/fleet_bench.py --smoke`` as a tier-1
subprocess test.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from machine_learning_apache_spark_tpu.fleet import (
    AffinityTable,
    FleetAdmission,
    FleetBackpressure,
    FleetRequestFailed,
    FleetRouter,
    FleetUnavailable,
    ReplicaServer,
    ReplicaSnapshot,
    SLOTier,
    find_fleet_sidecars,
    pick_replica,
    prefix_digest,
    scrape,
    write_fleet_sidecar,
)
from machine_learning_apache_spark_tpu.fleet.router import AFFINITY_LOAD_SLACK
from machine_learning_apache_spark_tpu.serving.queue import (
    Backpressure,
    DeadlineExceeded,
)

pytestmark = pytest.mark.fleet


def snap(rank, *, healthy=True, in_flight=0, port=None, digests=()):
    return ReplicaSnapshot(
        rank=rank,
        port=port if port is not None else 10000 + rank,
        healthy=healthy,
        status="ok" if healthy else "degraded",
        in_flight=in_flight,
        queue_depth=0,
        prefix_digests=frozenset(digests),
    )


# -- pick_replica: the three policies on synthetic snapshots ------------------
class TestPickReplica:
    def test_least_loaded_picks_min_in_flight(self):
        snaps = {0: snap(0, in_flight=5), 1: snap(1, in_flight=1),
                 2: snap(2, in_flight=3)}
        assert pick_replica(snaps, policy="least_loaded") == 1

    def test_least_loaded_tie_breaks_by_rank(self):
        snaps = {2: snap(2, in_flight=1), 0: snap(0, in_flight=1)}
        assert pick_replica(snaps, policy="least_loaded") == 0

    def test_round_robin_cycles_healthy_set(self):
        import itertools

        snaps = {0: snap(0), 1: snap(1), 2: snap(2)}
        rr = itertools.count()
        picks = [
            pick_replica(snaps, policy="round_robin", rr_state=rr)
            for _ in range(6)
        ]
        assert picks == [0, 1, 2, 0, 1, 2]

    def test_affinity_prefers_warm_replica_over_colder_peer(self):
        # Rank 1 holds the prefix and is (slightly) busier — affinity
        # still prefers it while within the load slack.
        snaps = {0: snap(0, in_flight=0), 1: snap(1, in_flight=1)}
        assert pick_replica(
            snaps, policy="affinity", candidates={1}
        ) == 1

    def test_affinity_falls_back_least_loaded_when_cold(self):
        snaps = {0: snap(0, in_flight=4), 1: snap(1, in_flight=1)}
        assert pick_replica(snaps, policy="affinity", candidates=None) == 1

    def test_affinity_load_slack_escape(self):
        # Unbounded affinity would pin traffic onto a backlog while a
        # peer idles (the post-failover starvation mode). Past the
        # slack, residency loses to load.
        over = int(AFFINITY_LOAD_SLACK) + 1
        snaps = {0: snap(0, in_flight=over), 1: snap(1, in_flight=0)}
        assert pick_replica(snaps, policy="affinity", candidates={0}) == 1
        within = {0: snap(0, in_flight=int(AFFINITY_LOAD_SLACK)),
                  1: snap(1, in_flight=0)}
        assert pick_replica(within, policy="affinity", candidates={0}) == 0

    def test_unhealthy_never_picked_any_policy(self):
        # The 503-draining property at the decision layer: a degraded
        # replica gets zero new requests no matter the policy.
        snaps = {0: snap(0, healthy=False, in_flight=0),
                 1: snap(1, in_flight=9)}
        for policy in ("affinity", "least_loaded", "round_robin"):
            assert pick_replica(snaps, policy=policy) == 1
        assert pick_replica(
            snaps, policy="affinity", candidates={0}
        ) == 1

    def test_exclude_and_empty(self):
        snaps = {0: snap(0), 1: snap(1)}
        assert pick_replica(snaps, exclude={0}) == 1
        assert pick_replica(snaps, exclude={0, 1}) is None
        assert pick_replica({}) is None

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="unknown policy"):
            pick_replica({0: snap(0)}, policy="random")


# -- admission: SLO tiers + tenant quotas -------------------------------------
class TestAdmission:
    def test_tier_quota_exhaustion_returns_retry_after(self):
        adm = FleetAdmission(
            tiers={"interactive": SLOTier("interactive", 10.0, 2)},
        )
        leases = [adm.admit(tier="interactive") for _ in range(2)]
        with pytest.raises(FleetBackpressure) as ei:
            adm.admit(tier="interactive")
        assert ei.value.retry_after > 0
        assert isinstance(ei.value, Backpressure)  # the serving contract
        adm.release(leases[0])
        lease = adm.admit(tier="interactive")  # slot freed -> admitted
        assert lease.tier == "interactive"
        assert lease.deadline_s == 10.0  # tier default stamped on

    def test_tenant_quota_independent_of_tier(self):
        adm = FleetAdmission(tenant_max_in_flight=1)
        l0 = adm.admit(tier="batch", tenant="acme")
        with pytest.raises(FleetBackpressure):
            adm.admit(tier="interactive", tenant="acme")
        adm.admit(tier="interactive", tenant="other")  # other tenant fine
        adm.release(l0)
        adm.admit(tier="interactive", tenant="acme")

    def test_release_idempotent_and_unknown_tier(self):
        adm = FleetAdmission()
        lease = adm.admit()
        adm.release(lease)
        adm.release(lease)  # second release must not underflow
        assert adm.stats()["tiers"]["interactive"]["in_flight"] == 0
        with pytest.raises(ValueError, match="unknown SLO tier"):
            adm.admit(tier="platinum")

    def test_retry_after_tracks_observed_service_time(self):
        clock = [0.0]
        adm = FleetAdmission(
            tiers={"interactive": SLOTier("interactive", 10.0, 1)},
            clock=lambda: clock[0],
        )
        lease = adm.admit(tier="interactive")
        clock[0] += 2.0
        adm.release(lease, service_s=2.0)
        adm.admit(tier="interactive")
        with pytest.raises(FleetBackpressure) as ei:
            adm.admit(tier="interactive")
        # One oversubscribed slot, EWMA service ~2s -> retry_after ~2s.
        assert 0.2 <= ei.value.retry_after <= 4.0


# -- affinity table -----------------------------------------------------------
class TestAffinityTable:
    def test_routing_memory_and_ttl(self):
        clock = [0.0]
        table = AffinityTable(memory_ttl_s=5.0, clock=lambda: clock[0])
        table.note_routed("d1", 0)
        assert table.candidates("d1") == {0}
        clock[0] = 6.0
        assert table.candidates("d1") == set()  # expired
        assert table.candidates(None) == set()

    def test_scrape_residency_replaces_and_forgets(self):
        table = AffinityTable()
        table.observe_scrape(0, {"a", "b"})
        table.observe_scrape(1, {"b"})
        assert table.candidates("b") == {0, 1}
        table.observe_scrape(0, {"c"})  # replace, not union
        assert table.candidates("b") == {1}
        table.forget_rank(1)
        assert table.candidates("b") == set()

    def test_prefix_digest_matches_serving_keying(self):
        from machine_learning_apache_spark_tpu.serving import (
            prefix_digest as serving_digest,
        )

        ids = [3, 1, 4, 1, 5]
        assert prefix_digest(ids) == serving_digest(tuple(ids))
        assert prefix_digest(ids) != prefix_digest([3, 1, 4])
        assert len(prefix_digest(ids)) == 16  # blake2b-8 hex


# -- prefix cache stats (the /statusz provider satellite) ---------------------
class TestPrefixCacheStats:
    def _cache(self, capacity=4):
        from machine_learning_apache_spark_tpu.serving.kv_pages import (
            KVPagePool,
            PrefixCache,
        )

        pool = KVPagePool(32)
        return PrefixCache(pool, capacity), pool

    def test_stats_counters_and_digests(self):
        cache, pool = self._cache()
        k1, k2 = (1, 2, 3), (4, 5)
        for key in (k1, k2):
            pages = pool.try_acquire(1, owner=("req", key))
            cache.put(key, pages)
            pool.release_owner(("req", key))
        assert cache.get(k1, owner="r1") is not None
        assert cache.get((9, 9), owner="r2") is None
        st = cache.stats()
        assert st["entries"] == 2
        assert st["hits"] == 1 and st["misses"] == 1
        assert st["hit_rate"] == 0.5
        # MRU-first: k1 was just touched, so its digest leads.
        assert st["resident_digests"][0] == prefix_digest(k1)
        assert set(st["resident_digests"]) == {
            prefix_digest(k1), prefix_digest(k2)
        }
        assert st["digests_truncated"] == 0

    def test_stats_digest_bound(self):
        cache, pool = self._cache(capacity=8)
        for i in range(6):
            key = (i,)
            pages = pool.try_acquire(1, owner=("req", key))
            cache.put(key, pages)
            pool.release_owner(("req", key))
        st = cache.stats(max_digests=2)
        assert len(st["resident_digests"]) == 2
        assert st["digests_truncated"] == 4
        assert st["hit_rate"] is None  # no lookups yet


# -- scrape plane -------------------------------------------------------------
class TestScrape:
    def test_sidecar_roundtrip_and_fleet_precedence(self, tmp_path):
        d = str(tmp_path)
        write_fleet_sidecar(4321, directory=d, rank=1)
        with open(os.path.join(d, "http_rank1.json"), "w") as f:
            json.dump({"port": 9999, "rank": 1}, f)
        with open(os.path.join(d, "http_rank0.json"), "w") as f:
            json.dump({"port": 1111, "rank": 0}, f)
        sides = find_fleet_sidecars(d)
        assert sides[1]["port"] == 4321  # fleet_ wins over http_
        assert sides[1]["kind"] == "fleet"
        assert sides[0]["port"] == 1111  # http_ fallback still discovered
        assert sides[0]["kind"] == "http"

    def test_scrape_retries_through_late_bind(self):
        """The sidecar-discovery race regression: the port is published
        before/while the server binds, so the first GET connection-
        refuses. With retries the scrape must land once the server is
        up — never a cached 'unreachable'."""
        import socket
        from http.server import BaseHTTPRequestHandler, HTTPServer

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()

        class H(BaseHTTPRequestHandler):
            def do_GET(self):
                body = b'{"status": "ok"}'
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        assert scrape(port, "/healthz", timeout=1.0, retries=0) is None

        httpd = None

        def bind_late():
            nonlocal httpd
            time.sleep(0.4)
            httpd = HTTPServer(("127.0.0.1", port), H)
            httpd.serve_forever(poll_interval=0.05)

        t = threading.Thread(target=bind_late, daemon=True)
        t.start()
        try:
            out = scrape(port, "/healthz", timeout=2.0,
                         retries=5, backoff=0.1)
            assert out == {"status": "ok"}
        finally:
            for _ in range(100):
                if httpd is not None:
                    break
                time.sleep(0.05)
            if httpd is not None:
                httpd.shutdown()


# -- replica data plane (fake engine, real sockets) ---------------------------
class _FakeReq:
    def __init__(self, text):
        self.text = text
        self.trace = type("T", (), {"trace_id": "t-1"})()

    def result(self, timeout=None):
        return self.text.upper()


class _FakeEngine:
    """Just enough engine for ReplicaServer: submit -> future-ish."""

    def __init__(self):
        self.mode = "ok"
        self.submitted = []
        self.clock = time.monotonic
        self.expire_sweeps = 0
        eng = self

        class _Q:
            @staticmethod
            def expire_now():
                eng.expire_sweeps += 1
                return 0

        self.queue = _Q()
        pipe = type("P", (), {"ragged": staticmethod(
            lambda texts: [[1, 2, 3] for _ in texts]
        )})()
        self.translator = type("Tr", (), {"trg_pipe": pipe})()

    def submit(self, text, deadline_s=None, tier=None):
        if self.mode == "backpressure":
            raise Backpressure(7, 0.25)
        self.submitted.append(text)
        return _FakeReq(text)

    def _health_snapshot(self):
        return {"healthy": True}


@pytest.fixture()
def replica(tmp_path):
    eng = _FakeEngine()
    healthy = {"v": True}
    server = ReplicaServer(
        eng, rank=0, port=0, health_fn=lambda: healthy["v"]
    )
    server.start(directory=str(tmp_path))
    yield server, eng, healthy, str(tmp_path)
    server.stop()


def _post(port, payload, timeout=5.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode()), dict()
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode()), dict(e.headers)


def _post_cancel(port, payload, timeout=5.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/cancel",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


class TestReplicaServer:
    def test_generate_roundtrip_and_sidecar(self, replica):
        server, eng, _, d = replica
        code, payload, _ = _post(server.port, {"text": "hello world"})
        assert code == 200
        assert payload["text"] == "HELLO WORLD"
        assert payload["rank"] == 0
        assert payload["tokens"] == 3
        sides = find_fleet_sidecars(d)
        assert sides[0]["port"] == server.port

    def test_backpressure_maps_to_429_with_retry_after(self, replica):
        server, eng, _, _ = replica
        eng.mode = "backpressure"
        code, payload, headers = _post(server.port, {"text": "x"})
        assert code == 429
        assert payload["retry_after"] == 0.25
        assert float(headers.get("Retry-After")) == 0.25

    def test_unhealthy_refuses_before_submit(self, replica):
        # The drain contract: a degraded replica 503s new requests
        # WITHOUT queueing them (its backlog drains, new traffic is the
        # router's problem), then serves again once healthy.
        server, eng, healthy, _ = replica
        healthy["v"] = False
        code, payload, _ = _post(server.port, {"text": "x"})
        assert code == 503
        assert eng.submitted == []  # never reached the queue
        healthy["v"] = True
        code, _, _ = _post(server.port, {"text": "x"})
        assert code == 200
        assert server.stats()["refused_503"] == 1

    def test_bad_body_400(self, replica):
        server, _, _, _ = replica
        code, payload, _ = _post(server.port, {"nope": 1})
        assert code == 400

    def test_cancel_unknown_trace_id_404(self, replica):
        # Best-effort by contract: a cancel that races a completed (or
        # never-arrived) request answers 404, touches nothing.
        server, eng, _, _ = replica
        code, payload = _post_cancel(server.port, {"trace_id": "nope"})
        assert code == 404 and payload["cancelled"] is False
        assert server.stats()["cancelled"] == 0
        assert eng.expire_sweeps == 0

    def test_cancel_in_flight_force_expires(self, replica):
        # Seed an in-flight entry the way generate does, then reap it
        # over the wire: the deadline snaps to "now" (the engine's next
        # sweep books ``expired``) and the queued-work sweep fires.
        server, eng, _, _ = replica
        victim = _FakeReq("slow")
        victim.deadline = eng.clock() + 120.0
        with server._lock:
            server._inflight["t-cancel"] = victim
        code, payload = _post_cancel(server.port, {"trace_id": "t-cancel"})
        assert code == 200 and payload["cancelled"] is True
        assert payload["trace_id"] == "t-cancel"
        assert victim.deadline <= eng.clock()  # pulled to the past
        assert eng.expire_sweeps == 1
        assert server.stats()["cancelled"] == 1

    def test_cancel_bad_body_400(self, replica):
        server, _, _, _ = replica
        code, payload = _post_cancel(server.port, {"nope": 1})
        assert code == 400


# -- router dispatch loop (scripted replicas, no sockets) ---------------------
class _ScriptedFleet:
    """Monkeypatched ReplicaClient backend: per-rank scripted behavior;
    snapshots carry port == 10000 + rank so dispatches map back."""

    def __init__(self, behaviors):
        self.behaviors = dict(behaviors)  # rank -> callable | kind str
        self.calls = []  # (rank, text)

    def generate(self, port, text, **kw):
        rank = port - 10000
        self.calls.append((rank, text))
        b = self.behaviors.get(rank, "ok")
        if callable(b):
            b = b()
        if b == "ok":
            return "ok", 200, {"text": text.upper(), "rank": rank,
                               "tokens": 3}
        if b == "refused":
            return "refused", 503, {"error": "replica degraded"}
        if b == "backpressure":
            return "backpressure", 429, {"retry_after": 0.5, "depth": 9}
        if b == "lost":
            return "lost", None, {"error": "socket died"}
        if b == "failed":
            return "failed", 500, {"error": "decode exploded"}
        raise AssertionError(b)


@pytest.fixture()
def scripted(monkeypatch):
    def make(behaviors, *, snapshots, policy="least_loaded", **kw):
        fleet = _ScriptedFleet(behaviors)
        from machine_learning_apache_spark_tpu.fleet import router as rmod

        monkeypatch.setattr(
            rmod.ReplicaClient, "generate",
            staticmethod(fleet.generate),
        )
        router = FleetRouter(
            snapshot_source=lambda: dict(snapshots), policy=policy, **kw
        )
        return fleet, router

    return make


class TestRouterDispatch:
    def test_completes_on_least_loaded(self, scripted):
        snaps = {0: snap(0, in_flight=3), 1: snap(1, in_flight=0)}
        fleet, router = scripted({}, snapshots=snaps)
        out = router.submit("hi")
        assert out["text"] == "HI"
        assert fleet.calls == [(1, "hi")]
        assert router.check_conservation() == {
            "submitted": 1, "completed": 1, "rejected": 0,
            "unavailable": 0, "failed": 0, "expired": 0,
            "hedged": 0, "cancelled": 0, "in_flight": 0,
        }

    def test_drains_around_503_until_recovery(self, scripted):
        # Rank 0 refuses: the request retries on rank 1, rank 0 goes to
        # the penalty box and gets ZERO further requests until a scrape
        # reports it healthy again.
        snaps = {0: snap(0, in_flight=0), 1: snap(1, in_flight=5)}
        fleet, router = scripted({0: "refused"}, snapshots=snaps)
        for _ in range(5):
            assert router.submit("x")["rank"] == 1
        rank0_calls = [c for c in fleet.calls if c[0] == 0]
        assert len(rank0_calls) == 1  # the single refused dispatch
        assert router.stats()["down"] == [0]
        assert router.stats()["per_replica"][0]["refused"] == 1

        # Recovery is scrape-driven: a healthy snapshot releases the box.
        fleet.behaviors[0] = "ok"
        router._on_scrape({0: snap(0, in_flight=0)})
        assert router.stats()["down"] == []
        assert router.submit("y")["rank"] == 0  # least-loaded again
        assert router.retries == 1

    def test_all_backpressure_surfaces_max_retry_after(self, scripted):
        snaps = {0: snap(0), 1: snap(1)}
        fleet, router = scripted(
            {0: "backpressure", 1: "backpressure"}, snapshots=snaps,
        )
        with pytest.raises(FleetBackpressure) as ei:
            router.submit("x")
        assert ei.value.retry_after == 0.5
        assert len(fleet.calls) == 2  # tried both before giving up
        ledger = router.ledger()
        assert ledger["rejected"] == 1 and ledger["in_flight"] == 0

    def test_lost_mid_request_is_terminal_not_retried(self, scripted):
        # The conservation story: a request that may have been decoding
        # is NOT silently replayed on another replica.
        snaps = {0: snap(0, in_flight=0), 1: snap(1, in_flight=5)}
        fleet, router = scripted({0: "lost"}, snapshots=snaps)
        with pytest.raises(FleetRequestFailed) as ei:
            router.submit("x")
        assert ei.value.rank == 0
        assert len(fleet.calls) == 1  # no replay on rank 1
        assert router.ledger()["failed"] == 1
        assert router.stats()["down"] == [0]  # socket death boxes too

    def test_no_healthy_replica_unavailable(self, scripted):
        snaps = {0: snap(0, healthy=False), 1: snap(1, healthy=False)}
        fleet, router = scripted({}, snapshots=snaps)
        with pytest.raises(FleetUnavailable):
            router.submit("x")
        assert fleet.calls == []
        assert router.ledger()["unavailable"] == 1

    def test_admission_rejection_counts_and_conserves(self, scripted):
        snaps = {0: snap(0)}
        adm = FleetAdmission(
            tiers={"interactive": SLOTier("interactive", 10.0, 1)},
        )
        fleet, router = scripted({}, snapshots=snaps, admission=adm)
        held = adm.admit(tier="interactive")  # budget fully leased out
        with pytest.raises(FleetBackpressure):
            router.submit("x")
        assert fleet.calls == []  # rejected before any dispatch
        router.check_conservation()
        assert router.ledger()["rejected"] == 1
        adm.release(held)
        assert router.submit("x")["rank"] == 0

    def test_pre_dispatch_deadline_expires_locally(self, scripted):
        # A request whose budget is gone before any dispatch fails HERE
        # as ``expired`` — no replica ever decodes for it.
        snaps = {0: snap(0)}
        fleet, router = scripted({}, snapshots=snaps)
        with pytest.raises(DeadlineExceeded, match="before"):
            router.submit("x", deadline_s=0.0)
        assert fleet.calls == []  # never reached a replica
        ledger = router.check_conservation()
        assert ledger["expired"] == 1 and ledger["completed"] == 0

    def test_affinity_routing_memory_steers_repeat_prompts(self, scripted):
        snaps = {0: snap(0, in_flight=1), 1: snap(1, in_flight=0)}
        fleet, router = scripted(
            {}, snapshots=snaps, policy="affinity",
            key_fn=lambda text: prefix_digest([ord(c) for c in text]),
        )
        first = router.submit("abc")["rank"]  # least-loaded: rank 1
        assert first == 1
        # Make the warm rank the busier one (within slack): affinity
        # must still prefer it over the now-idle peer.
        snaps[0] = snap(0, in_flight=0)
        snaps[1] = snap(1, in_flight=2)
        assert router.submit("abc")["rank"] == 1
        assert router.submit("zzz")["rank"] == 0  # cold prompt: coldest


# -- distributed tracing across the fleet hops --------------------------------
@pytest.fixture()
def fresh_trace(monkeypatch):
    """Clean telemetry + tracing state (and no env overrides) for tests
    that assert on the global event log."""
    from machine_learning_apache_spark_tpu import telemetry

    for var in ("MLSPARK_TELEMETRY", "MLSPARK_TELEMETRY_DIR",
                "MLSPARK_TELEMETRY_EVENTS", "MLSPARK_TELEMETRY_HTTP",
                "MLSPARK_TRACE", "MLSPARK_TRACE_SAMPLE",
                "MLSPARK_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


class TestRouterTracing:
    """Router-side trace semantics on the scripted (no-socket) fleet."""

    def test_retry_attempts_are_siblings_under_one_trace(
        self, scripted, fresh_trace
    ):
        from machine_learning_apache_spark_tpu.telemetry import events

        snaps = {0: snap(0, in_flight=0), 1: snap(1, in_flight=5)}
        fleet, router = scripted({0: "refused"}, snapshots=snaps)
        assert router.submit("x")["rank"] == 1

        evs = events.get_log().snapshot()
        submit_start = next(
            e for e in evs
            if e.kind == "span_start" and e.name == "fleet.submit"
        )
        tid = submit_start.trace
        assert tid and len(tid) == 32  # minted + sampled at default rate
        starts = [e for e in evs
                  if e.kind == "span_start" and e.name == "fleet.attempt"]
        # Two attempts (503-drained, then retried) land as siblings: same
        # trace, same fleet.submit parent span ...
        assert [e.attrs["replica"] for e in starts] == [0, 1]
        assert {e.trace for e in starts} == {tid}
        assert {e.parent for e in starts} == {submit_start.span}
        # ... but each carries its own wire (traceparent child) span id,
        # so replica-side spans attach to the right attempt.
        ctx_spans = [e.attrs["ctx_span"] for e in starts]
        assert len(set(ctx_spans)) == 2
        ann = next(e for e in evs if e.name == "fleet.request")
        assert ann.trace == tid
        assert ann.attrs["retries"] == 1
        assert ann.attrs["outcome"] == "completed"

    def test_trace_off_serves_untraced(
        self, scripted, fresh_trace, monkeypatch
    ):
        from machine_learning_apache_spark_tpu import telemetry
        from machine_learning_apache_spark_tpu.telemetry import events

        monkeypatch.setenv("MLSPARK_TRACE", "0")
        telemetry.reset()
        fleet, router = scripted({}, snapshots={0: snap(0)})
        assert router.submit("x")["rank"] == 0  # request unharmed
        evs = events.get_log().snapshot()
        assert evs and all(e.trace is None for e in evs)
        attempt = next(e for e in evs if e.kind == "span_start"
                       and e.name == "fleet.attempt")
        assert "ctx_span" not in (attempt.attrs or {})

    def test_router_slo_burn_per_tier(self, scripted, fresh_trace):
        snaps = {0: snap(0, healthy=False)}
        fleet, router = scripted({}, snapshots=snaps)
        with pytest.raises(FleetUnavailable):
            router.submit("x")  # burns interactive budget
        slo = router.stats()["slo"]
        assert slo["interactive"]["total"] == 1
        assert slo["interactive"]["missed"] == 1
        assert slo["interactive"]["window_rate"] == 1.0
        # Recovery: completed-within-deadline requests decay the gauge.
        router._on_scrape({0: snap(0)})
        snaps[0] = snap(0)
        for _ in range(3):
            router.submit("y")
        slo = router.stats()["slo"]
        assert slo["interactive"]["total"] == 4
        assert slo["interactive"]["missed"] == 1
        from machine_learning_apache_spark_tpu.telemetry import registry

        snap_reg = registry.get_registry().snapshot()
        assert "slo_burn_interactive" in snap_reg["fleet"]


class TestRouterHedging:
    """Straggler hedging on the scripted fleet: the duplicate fires only
    past the hedge delay, first response wins, the loser is reaped via
    /v1/cancel, and a hedged request still retires in exactly ONE
    terminal ledger bucket (``hedged``/``cancelled`` ride outside the
    conservation sum)."""

    def _reap_log(self, monkeypatch):
        reaps = []
        from machine_learning_apache_spark_tpu.fleet import router as rmod

        monkeypatch.setattr(
            rmod.ReplicaClient, "cancel",
            staticmethod(
                lambda port, trace_id, **kw:
                reaps.append((port, trace_id)) or True
            ),
        )
        return reaps

    def test_hedge_rescues_straggler_and_cancels_loser(
        self, scripted, monkeypatch, fresh_trace
    ):
        snaps = {0: snap(0, in_flight=0), 1: snap(1, in_flight=3)}

        def slow_ok():
            time.sleep(0.6)
            return "ok"

        fleet, router = scripted(
            {0: slow_ok}, snapshots=snaps,
            hedge=True, hedge_tiers=("interactive",),
            hedge_delay_factor=0.0, hedge_min_delay_s=0.05,
        )
        reaps = self._reap_log(monkeypatch)
        out = router.submit("hi", tier="interactive")
        assert out["rank"] == 1  # the hedge won the race
        ledger = router.check_conservation()
        assert ledger["completed"] == 1
        assert ledger["hedged"] == 1 and ledger["cancelled"] == 1
        stats = router.stats()
        assert stats["per_replica"][1]["hedged"] == 1
        assert stats["per_replica"][0]["cancelled"] == 1
        # the reap is fire-and-forget on a helper thread: wait for it,
        # then check it targeted the straggler's port with the shared
        # router-minted trace id (the /v1/cancel key).
        deadline = time.time() + 5.0
        while not reaps and time.time() < deadline:
            time.sleep(0.01)
        assert reaps == [(10000, reaps[0][1])] and reaps[0][1]

    def test_fast_primary_never_hedges(
        self, scripted, monkeypatch, fresh_trace
    ):
        snaps = {0: snap(0, in_flight=0), 1: snap(1, in_flight=3)}
        fleet, router = scripted(
            {}, snapshots=snaps,
            hedge=True, hedge_tiers=("interactive",),
            hedge_delay_factor=0.0, hedge_min_delay_s=0.25,
        )
        reaps = self._reap_log(monkeypatch)
        assert router.submit("hi")["rank"] == 0
        ledger = router.check_conservation()
        assert ledger["hedged"] == 0 and ledger["cancelled"] == 0
        assert len(fleet.calls) == 1 and reaps == []

    def test_hedge_scoped_to_configured_tiers(
        self, scripted, fresh_trace
    ):
        snaps = {0: snap(0, in_flight=0), 1: snap(1, in_flight=3)}

        def slow_ok():
            time.sleep(0.3)
            return "ok"

        fleet, router = scripted(
            {0: slow_ok}, snapshots=snaps,
            hedge=True, hedge_tiers=("interactive",),
            hedge_delay_factor=0.0, hedge_min_delay_s=0.02,
        )
        # batch is not a hedged tier: the slow primary is simply waited
        # out, no duplicate dispatch.
        assert router.submit("hi", tier="batch")["rank"] == 0
        assert router.ledger()["hedged"] == 0
        assert len(fleet.calls) == 1

    def test_hedge_saves_lost_primary_without_replay(
        self, scripted, fresh_trace
    ):
        # The socket dies under the primary AFTER the hedge is already
        # in flight: the hedge's 200 wins, the lost sibling is absorbed
        # (rank boxed, per-replica taxonomy booked) — but lost-is-lost
        # still holds in that nothing was REPLAYED in response to the
        # loss; the rescue rode a duplicate issued before it.
        snaps = {0: snap(0, in_flight=0), 1: snap(1, in_flight=3)}

        def slow_lost():
            time.sleep(0.2)
            return "lost"

        def slow_ok():
            time.sleep(0.3)
            return "ok"

        fleet, router = scripted(
            {0: slow_lost, 1: slow_ok}, snapshots=snaps,
            hedge=True, hedge_tiers=("interactive",),
            hedge_delay_factor=0.0, hedge_min_delay_s=0.05,
        )
        out = router.submit("hi", tier="interactive")
        assert out["rank"] == 1
        ledger = router.check_conservation()
        assert ledger["completed"] == 1 and ledger["failed"] == 0
        assert ledger["hedged"] == 1
        stats = router.stats()
        assert stats["down"] == [0]  # the dead socket still boxes
        assert stats["per_replica"][0]["lost"] == 1
        assert len(fleet.calls) == 2  # primary + one hedge, no third

    def test_hedge_both_fail_single_terminal(
        self, scripted, fresh_trace
    ):
        # No winner: the sibling outcomes reduce to ONE terminal result
        # (severity: terminal > backpressure > refused) — the ledger
        # books exactly one failure for the request.
        snaps = {0: snap(0, in_flight=0), 1: snap(1, in_flight=3)}

        def slow_failed():
            time.sleep(0.2)
            return "failed"

        fleet, router = scripted(
            {0: slow_failed, 1: "failed"}, snapshots=snaps,
            hedge=True, hedge_tiers=("interactive",),
            hedge_delay_factor=0.0, hedge_min_delay_s=0.05,
        )
        with pytest.raises(FleetRequestFailed):
            router.submit("hi", tier="interactive")
        ledger = router.check_conservation()
        assert ledger["failed"] == 1 and ledger["completed"] == 0
        assert ledger["hedged"] == 1 and ledger["cancelled"] == 0
        assert len(fleet.calls) == 2


@pytest.fixture(scope="module")
def mt_bundle(make_tiny_translator):
    """Untrained tiny MT bundle over 32 sentence pairs."""
    return make_tiny_translator(32)


class TestFleetTraceE2E:
    """One trace id from router mint through the replica HTTP hop into
    the real engine — the distributed-tracing acceptance path, over two
    engine replicas so each request's id crosses its own process hop."""

    def test_one_trace_id_per_request_across_two_replicas(
        self, mt_bundle, fresh_trace, tmp_path
    ):
        from machine_learning_apache_spark_tpu.telemetry import (
            events,
            traceview,
        )

        t, texts = mt_bundle
        engines, servers = [], []
        try:
            for rank in range(2):
                eng = t.serve(
                    boundaries=(8, 16), max_batch=2, max_new_tokens=8,
                )
                engines.append(eng)
                srv = ReplicaServer(eng, rank=rank, port=0)
                srv.start(directory=str(tmp_path))
                servers.append(srv)
            snaps = {s.rank: snap(s.rank, port=s.port) for s in servers}
            router = FleetRouter(
                snapshot_source=lambda: dict(snaps), policy="round_robin",
            )
            payloads = [router.submit(texts[i]) for i in range(2)]
        finally:
            for srv in servers:
                srv.stop()
            for eng in engines:
                eng.stop()

        assert {p["rank"] for p in payloads} == {0, 1}  # both replicas
        evs = events.get_log().snapshot()
        hexdigits = set("0123456789abcdef")
        assert len({p["trace_id"] for p in payloads}) == 2
        for payload in payloads:
            tid = payload["trace_id"]
            # The id the replica returned IS the router-minted trace id.
            assert len(tid) == 32 and set(tid) <= hexdigits
            mine = [e for e in evs if e.trace == tid]
            names = {(e.kind, e.name) for e in mine}
            for span_name in ("fleet.submit", "fleet.attempt",
                              "fleet.replica", "serving.submit"):
                assert ("span_end", span_name) in names, (tid, names)
            assert ("annotation", "fleet.request") in names
            assert ("annotation", "serving.request") in names
            # The cross-process edge: the attempt's wire span id is what
            # the replica recorded as its remote parent.
            attempt = next(e for e in mine if e.kind == "span_start"
                           and e.name == "fleet.attempt")
            rep = next(e for e in mine if e.kind == "span_start"
                       and e.name == "fleet.replica")
            assert attempt.attrs["ctx_span"] == rep.attrs["remote_parent"]

        # And the read side stitches each request into one complete tree.
        trees = traceview.assemble([e.to_dict() for e in evs])
        for payload in payloads:
            tree = trees[payload["trace_id"]]
            summary = traceview.trace_summary(tree)
            assert summary["complete"], summary
            assert summary["root"] == "fleet.submit"
        comp = traceview.completeness(trees)
        assert comp["fraction"] == 1.0


# -- aggregate: fleet report + replica skew -----------------------------------
class TestFleetAggregate:
    def test_fleet_report_rollup(self):
        from machine_learning_apache_spark_tpu.telemetry.aggregate import (
            fleet_report,
        )

        evs = [
            {"kind": "annotation", "name": "fleet.request",
             "attrs": {"outcome": "completed", "replica": 0,
                       "tier": "interactive", "tenant": "a",
                       "retries": 0, "total_s": 0.1}},
            {"kind": "annotation", "name": "fleet.request",
             "attrs": {"outcome": "completed", "replica": 1,
                       "tier": "batch", "retries": 2, "total_s": 0.3}},
            {"kind": "annotation", "name": "fleet.request",
             "attrs": {"outcome": "rejected", "tier": "interactive",
                       "retries": 1}},
            {"kind": "span_end", "name": "not.fleet", "value": 1.0},
        ]
        rep = fleet_report(evs)
        assert rep["requests"] == 3
        assert rep["by_outcome"] == {"completed": 2, "rejected": 1}
        assert rep["by_tier"] == {"batch": 1, "interactive": 2}
        assert rep["retries"] == 3
        assert rep["per_replica"][0]["requests"] == 1
        assert rep["per_replica"][1]["latency"]["mean"] == 0.3
        assert fleet_report([]) == {}

    def test_replica_skew_verdict(self):
        from machine_learning_apache_spark_tpu.telemetry.aggregate import (
            replica_skew,
        )

        rows = [
            {"rank": 0, "tokens_per_sec": 300.0, "in_flight": 4},
            {"rank": 1, "tokens_per_sec": 100.0, "in_flight": 1},
        ]
        sk = replica_skew(rows)
        assert sk["hottest_rank"] == 0 and sk["coldest_rank"] == 1
        assert sk["skew_ratio"] == 3.0
        assert sk["hottest_share"] == 0.75
        assert replica_skew(rows[:1]) == {}


# -- end-to-end: 2-replica gang + router (tier-1 CI entry) --------------------
def test_fleet_bench_smoke_subprocess(tmp_path):
    """tools/fleet_bench.py --smoke: real ReplicaGang (2 serving
    replicas, each engine + HTTP data plane), real FleetRouter over the
    scrape plane, parity vs a local engine, and router+replica
    conservation after a concurrent load burst."""
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "fleet_smoke.json"
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(repo_root, "tools", "fleet_bench.py"),
            "--smoke", "--out", str(out),
        ],
        capture_output=True, text=True, timeout=280,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    artifact = json.loads(out.read_text())
    assert artifact["ok"] is True
    assert artifact["gates"] == {
        "parity": True,
        "conservation": True,
        "both_replicas_served": True,
    }
    assert artifact["parity"]["identical"] is True
    assert artifact["conservation"]["router_ledger"]["in_flight"] == 0


def test_trace_bench_smoke_subprocess(tmp_path):
    """tools/trace_bench.py --smoke: the BENCH_SERVE_r06 gates in tier-1
    form — traced-vs-untraced paged sweeps (same-run overhead floor),
    engine-level trace completeness over the whole traced sweep, and a
    2-replica fleet section where every minted trace must stitch into
    one fleet.submit-rooted tree across the HTTP hop."""
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "trace_bench.json"
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(repo_root, "tools", "trace_bench.py"),
            "--smoke", "--out", str(out),
        ],
        capture_output=True, text=True, timeout=480,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    artifact = json.loads(out.read_text())
    assert artifact["ok"] is True
    assert artifact["gates"] == {
        "overhead": True,
        "vs_r05": True,
        "trace_complete_engine": True,
        "trace_complete_fleet": True,
        "zero_recompiles": True,
        "conservation": True,
        "midload_scrape": True,
    }
    # The smoke never compares a tiny model's knee to r05 — the skip
    # must be recorded, not silent.
    assert artifact["knee"]["gate_skipped_reason"]
    assert artifact["trace_complete"]["engine"]["fraction"] >= 0.99
    fleet = artifact["trace_complete"]["fleet"]
    assert fleet["both_replicas_served"] is True
    assert fleet["fraction"] >= 0.99


@pytest.mark.slow
def test_replica_gang_restarts_killed_rank(tmp_path):
    """ReplicaGang supervision is per-rank: SIGKILL one replica and only
    it restarts; the survivor's process is untouched."""
    from machine_learning_apache_spark_tpu.launcher import ReplicaGang

    gang = ReplicaGang(
        "launcher_workers:sleep_forever",
        num_replicas=2,
        workdir=str(tmp_path),
        platform="cpu",
        backoff_base=0.1,
    ).start()
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if all(gang.alive().values()) and len(gang.alive()) == 2:
                break
            time.sleep(0.2)
        pid0 = gang._procs[0].pid
        assert gang.kill_rank(1)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = gang.status()
            if st["restarts"].get(1, 0) >= 1 and st["alive"].get(1):
                break
            time.sleep(0.2)
        st = gang.status()
        assert st["restarts"][1] >= 1
        assert st["restarts"][0] == 0
        assert st["alive"][1] is True
        assert gang._procs[0].pid == pid0  # survivor untouched
    finally:
        gang.stop(drain_s=1.0)

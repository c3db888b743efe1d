"""telemetry/: event log, trace spans, metrics registry, gang aggregation,
and the crash flight recorder (docs/OBSERVABILITY.md).

Unit tests drive each surface directly; the aggregation tests build a
synthetic 2-rank gang from hand-written JSONL (deterministic durations,
so the skew report's straggler attribution is exact) and the CLI test
runs ``tools/telemetry_report.py`` against that fixture end to end.
Disabled-mode tests pin the zero-cost contract: module-level no-op
singletons, nothing written, nothing stored.
"""

import json
import os
import subprocess
import sys

import pytest

from machine_learning_apache_spark_tpu import telemetry
from machine_learning_apache_spark_tpu.telemetry import (
    aggregate,
    events,
    http,
    recorder,
    registry,
    spans,
    tracectx,
    traceview,
)

pytestmark = pytest.mark.telemetry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_telemetry(monkeypatch):
    """Every test gets a clean process-global log/registry and no env
    overrides; state is re-armed afterwards so other suites see their own
    environment, not this test's."""
    monkeypatch.delenv(events.ENV_TELEMETRY, raising=False)
    monkeypatch.delenv(events.ENV_TELEMETRY_DIR, raising=False)
    monkeypatch.delenv(events.ENV_MAX_EVENTS, raising=False)
    monkeypatch.delenv(http.ENV_TELEMETRY_HTTP, raising=False)
    monkeypatch.delenv(tracectx.ENV_TRACE, raising=False)
    monkeypatch.delenv(tracectx.ENV_TRACE_SAMPLE, raising=False)
    monkeypatch.delenv("MLSPARK_PROCESS_ID", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


# -- spans ---------------------------------------------------------------------


class TestSpans:
    def test_nesting_parent_attribution_and_timestamps(self):
        with telemetry.span("outer") as outer:
            assert spans.current_span_id() == outer.id
            with telemetry.span("inner", step=3) as inner:
                assert spans.current_span_id() == inner.id
            assert spans.current_span_id() == outer.id
        assert spans.current_span_id() is None

        evs = events.get_log().snapshot()
        assert [(e.kind, e.name) for e in evs] == [
            ("span_start", "outer"),
            ("span_start", "inner"),
            ("span_end", "inner"),
            ("span_end", "outer"),
        ]
        start_inner, end_inner, end_outer = evs[1], evs[2], evs[3]
        assert start_inner.span == inner.id
        assert start_inner.parent == outer.id
        assert start_inner.attrs == {"step": 3}
        assert end_inner.value is not None and end_inner.value >= 0
        assert end_outer.value >= end_inner.value  # outer encloses inner
        ts = [e.ts for e in evs]
        assert ts == sorted(ts)  # monotonic within a process
        assert all(e.wall > 0 and e.pid == os.getpid() for e in evs)

    def test_exception_tagged_on_span_end(self):
        with pytest.raises(RuntimeError):
            with telemetry.span("boom"):
                raise RuntimeError("x")
        end = events.get_log().snapshot()[-1]
        assert end.kind == "span_end" and end.name == "boom"
        assert end.attrs["error"] == "RuntimeError"
        assert spans.current_span_id() is None  # stack unwound

    def test_leaked_inner_span_does_not_corrupt_stack(self):
        outer = telemetry.span("outer")
        outer.__enter__()
        spans._Span("leaked", None).__enter__()  # never exited
        outer.__exit__(None, None, None)
        assert spans.current_span_id() is None

    def test_traced_decorator(self):
        @spans.traced("my.fn")
        def f(x):
            return x + 1

        assert f(1) == 2
        names = [e.name for e in events.get_log().snapshot()]
        assert names == ["my.fn", "my.fn"]

    def test_per_thread_stacks(self):
        import threading

        got = {}

        def other():
            got["id"] = spans.current_span_id()

        with telemetry.span("main-only"):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert got["id"] is None  # spans never leak across threads


# -- event log -----------------------------------------------------------------


class TestEventLog:
    def test_ring_eviction_counts_drops(self):
        log = events.EventLog(max_events=4)
        for i in range(6):
            log.emit("annotation", f"a{i}")
        assert len(log) == 4 and log.dropped == 2
        assert [e.name for e in log.snapshot()] == ["a2", "a3", "a4", "a5"]
        assert [e.name for e in log.tail(2)] == ["a4", "a5"]
        log.clear()
        assert len(log) == 0 and log.dropped == 0

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown event kind"):
            events.EventLog().emit("bogus", "x")

    def test_jsonl_round_trip_and_torn_tail(self, tmp_path):
        log = events.EventLog()
        log.emit("annotation", "a", attrs={"k": 1})
        log.emit("counter", "c", value=2.0)
        path = str(tmp_path / "out.jsonl")
        assert log.export_jsonl(path) == 2
        back = aggregate.load_jsonl(path)
        assert [d["name"] for d in back] == ["a", "c"]
        assert back[0]["attrs"] == {"k": 1} and back[1]["value"] == 2.0
        # a killed writer's torn final line is skipped, not fatal
        with open(path, "a") as f:
            f.write('{"kind": "annotation", "na')
        assert len(aggregate.load_jsonl(path)) == 2
        # ... but a malformed interior line is corruption and raises
        with open(path, "a") as f:
            f.write("\n{}\n")
        with pytest.raises(json.JSONDecodeError):
            aggregate.load_jsonl(path)

    def test_max_events_env_knob(self, monkeypatch):
        monkeypatch.setenv(events.ENV_MAX_EVENTS, "7")
        telemetry.reset()
        assert events.get_log().max_events == 7


# -- registry ------------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_histogram_snapshot(self):
        reg = registry.get_registry()
        reg.counter("train", "steps").inc(3)
        reg.gauge("serving", "queue_depth").set(5)
        h = reg.histogram("train", "step_s")
        for v in (0.1, 0.2, 0.3, 0.4):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["train"]["steps"] == 3
        assert snap["serving"]["queue_depth"] == 5
        assert snap["train"]["step_s"]["count"] == 4
        assert snap["train"]["step_s"]["p50"] == 0.2
        # same (scope, name) returns the same metric object
        assert reg.counter("train", "steps") is reg.counter("train", "steps")

    def test_counter_rejects_decrease_and_type_conflicts(self):
        reg = registry.get_registry()
        with pytest.raises(ValueError):
            reg.counter("t", "x").inc(-1)
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("t", "x")

    def test_histogram_ring_keeps_cumulative_count(self):
        h = registry.HistogramMetric("t", "x", max_samples=4)
        for v in range(1, 11):
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 10 and s["sum"] == 55.0  # cumulative past evict
        assert s["max"] == 10.0  # newest sample survives the ring
        assert h.percentile(0) >= 7.0  # oldest samples (1..6) evicted

    def test_prometheus_text_and_rank_label(self, monkeypatch):
        reg = registry.get_registry()
        reg.counter("serving", "submitted").inc(12)
        h = reg.histogram("train", "step_s")
        h.observe(0.5)
        text = reg.to_prometheus_text()
        assert "# TYPE mlspark_serving_submitted counter" in text
        assert "mlspark_serving_submitted 12" in text
        assert 'mlspark_train_step_s{quantile="0.5"} 0.5' in text
        assert "mlspark_train_step_s_count 1" in text
        monkeypatch.setenv("MLSPARK_PROCESS_ID", "1")
        assert 'mlspark_serving_submitted{rank="1"} 12' in (
            reg.to_prometheus_text()
        )

    def test_name_sanitization(self):
        reg = registry.get_registry()
        reg.counter("serving", "p99.latency-ms").inc()
        assert "mlspark_serving_p99_latency_ms 1" in reg.to_prometheus_text()


# -- flight recorder -----------------------------------------------------------


class TestFlightRecorder:
    def test_dump_and_load(self, tmp_path):
        with telemetry.span("step"):
            telemetry.annotate("checkpoint", step=7)
        path = recorder.dump_flight(
            "test:crash", directory=str(tmp_path), extra={"step": 7}
        )
        assert path == str(tmp_path / "flight_driver.json")
        dump = recorder.load_flight(path)
        assert dump["artifact"] == "flight"
        assert dump["reason"] == "test:crash"
        assert dump["rank"] is None and dump["extra"] == {"step": 7}
        assert dump["event_count"] == len(dump["events"]) == 3
        assert [e["name"] for e in dump["events"]] == [
            "step", "checkpoint", "step",
        ]

    def test_capacity_bounds_the_tail(self, tmp_path):
        for i in range(recorder.FLIGHT_CAPACITY + 50):
            telemetry.annotate(f"a{i}")
        path = recorder.dump_flight("test", directory=str(tmp_path))
        dump = recorder.load_flight(path)
        assert dump["event_count"] == recorder.FLIGHT_CAPACITY
        assert dump["events"][-1]["name"] == f"a{recorder.FLIGHT_CAPACITY + 49}"

    def test_rank_in_file_name(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MLSPARK_PROCESS_ID", "3")
        telemetry.annotate("x")
        path = recorder.dump_flight("test", directory=str(tmp_path))
        assert path.endswith("flight_3.json")
        assert recorder.load_flight(path)["rank"] == 3

    def test_no_directory_means_no_dump(self):
        telemetry.annotate("x")
        assert recorder.dump_flight("test") is None  # never raises


# -- gang aggregation ----------------------------------------------------------


def _write_rank_jsonl(directory, rank, phases):
    """Hand-built rank export: ``phases`` is {name: [durations]}. Events
    carry rank=None on purpose — the merge must stamp rank from the file
    name, which is authoritative."""
    path = os.path.join(directory, aggregate.rank_file_name(rank))
    sid = 0
    t = 0.0
    with open(path, "w") as f:
        for name, durations in phases.items():
            for d in durations:
                sid += 1
                f.write(json.dumps({
                    "kind": "span_start", "name": name, "ts": t,
                    "wall": 1e9 + t, "rank": None, "pid": 1, "span": sid,
                }) + "\n")
                t += d
                f.write(json.dumps({
                    "kind": "span_end", "name": name, "ts": t,
                    "wall": 1e9 + t, "rank": None, "pid": 1, "span": sid,
                    "value": d,
                }) + "\n")
    return path


@pytest.fixture
def two_rank_dir(tmp_path):
    """A synthetic 2-rank gang: rank 1 is a 3x straggler on train.step and
    also the only rank running io.load."""
    d = str(tmp_path / "gang")
    os.makedirs(d)
    _write_rank_jsonl(d, 0, {"train.step": [0.010, 0.010, 0.010, 0.010]})
    _write_rank_jsonl(d, 1, {
        "train.step": [0.030, 0.030, 0.030, 0.030],
        "io.load": [0.5],
    })
    return d


class TestAggregation:
    def test_merge_phase_table_and_skew(self, two_rank_dir):
        report = aggregate.merge_gang_dir(two_rank_dir)
        assert report["ranks"] == [0, 1]
        assert report["event_count"] == 18  # (4 + 4 + 1) spans × 2 events

        step = report["phases"]["train.step"]
        assert step["overall"]["count"] == 8
        assert step["ranks"][0]["p50"] == 0.010
        assert step["ranks"][1]["p99"] == 0.030
        assert report["phases"]["io.load"]["ranks"][1]["count"] == 1

        skew = report["skew"]
        assert "io.load" not in skew  # single-rank phase: no skew entry
        s = skew["train.step"]
        assert s["slowest_rank"] == 1 and s["fastest_rank"] == 0
        assert s["skew_ratio"] == 3.0
        assert abs(s["spread"] - 0.020) < 1e-9

    def test_render_markdown(self, two_rank_dir):
        md = aggregate.render_markdown(aggregate.merge_gang_dir(two_rank_dir))
        assert "# Telemetry report" in md
        assert "| train.step | all | 8 |" in md
        assert "## Rank skew" in md
        assert "| train.step | 1 | 0 | 3.0 |" in md

    def test_write_rank_file_exports_live_log(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MLSPARK_PROCESS_ID", "2")
        with telemetry.span("train.step"):
            pass
        path = aggregate.write_rank_file(str(tmp_path))
        assert path.endswith("telemetry_rank2.jsonl")
        assert aggregate.find_rank_files(str(tmp_path)) == {2: path}
        merged = aggregate.merge_rank_files({2: path})
        assert [e["rank"] for e in merged] == [2, 2]


class TestCommsReport:
    """The comms rollup next to the rank-skew report: zero1 wire-byte
    counters (with bytes/step from the emitter's step stamp) plus the
    comms.* collective span phases."""

    @pytest.fixture
    def comms_dir(self, tmp_path):
        d = str(tmp_path / "gang")
        os.makedirs(d)
        for rank in (0, 1):
            path = _write_rank_jsonl(
                d, rank, {"comms.reduce_scatter": [0.002, 0.002]}
            )
            with open(path, "a") as f:
                f.write(json.dumps({
                    "kind": "counter", "name": "comms.bytes_reduce_scattered",
                    "ts": 1.0, "wall": 1e9, "rank": None, "pid": 1,
                    "value": 4096.0, "attrs": {"steps": 4,
                                               "comms_dtype": "float32"},
                }) + "\n")
        return d

    def test_counters_and_collectives(self, comms_dir):
        report = aggregate.merge_gang_dir(comms_dir)
        comms = report["comms"]
        per_rank = comms["counters"]["comms.bytes_reduce_scattered"]
        assert per_rank[0] == {"total": 4096.0, "steps": 4, "per_step": 1024.0}
        assert per_rank[1]["per_step"] == 1024.0
        coll = comms["collectives"]["comms.reduce_scatter"]
        assert coll["overall"]["count"] == 4
        assert coll["ranks"][0]["p50"] == 0.002
        # Non-comms phases stay out of the collectives table.
        assert "train.step" not in comms["collectives"]

    def test_markdown_section(self, comms_dir):
        md = aggregate.render_markdown(aggregate.merge_gang_dir(comms_dir))
        assert "## Comms" in md
        assert "| comms.bytes_reduce_scattered | 0 | 4096 | 4 | 1024.0 |" in md
        assert "| comms.reduce_scatter | all | 4 |" in md

    def test_section_absent_without_comms_events(self, two_rank_dir):
        report = aggregate.merge_gang_dir(two_rank_dir)
        assert report["comms"] == {
            "counters": {},
            "collectives": {},
            "overlap": {},
            "comms_fraction": None,
            "verdict": None,
        }
        assert "## Comms" not in aggregate.render_markdown(report)


class TestReportCLI:
    """tools/telemetry_report.py against the synthetic 2-rank fixture."""

    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "telemetry_report.py"), *argv],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )

    def test_merges_directory_into_json_and_md(self, two_rank_dir, tmp_path):
        json_out = str(tmp_path / "report.json")
        md_out = str(tmp_path / "report.md")
        proc = self._run(two_rank_dir, "--json", json_out, "--md", md_out)
        assert proc.returncode == 0, proc.stderr
        with open(json_out) as f:
            report = json.load(f)
        assert report["artifact"] == "telemetry_report"
        assert report["ranks"] == [0, 1]
        assert report["skew"]["train.step"]["slowest_rank"] == 1
        with open(md_out) as f:
            assert "## Per-phase durations (ms)" in f.read()
        assert "merged 18 events from ranks [0, 1]" in proc.stdout

    def test_markdown_to_stdout_by_default(self, two_rank_dir):
        proc = self._run(two_rank_dir)
        assert proc.returncode == 0, proc.stderr
        assert "# Telemetry report" in proc.stdout

    def test_empty_directory_is_an_error(self, tmp_path):
        proc = self._run(str(tmp_path))
        assert proc.returncode == 1
        assert "no telemetry_rank" in proc.stderr


# -- disabled mode -------------------------------------------------------------


class TestDisabledMode:
    def test_env_kill_switch_spellings(self, monkeypatch):
        for v in ("0", "false", "off", "no", " OFF "):
            monkeypatch.setenv(events.ENV_TELEMETRY, v)
            telemetry.reset()
            assert not events.enabled(), v
        monkeypatch.setenv(events.ENV_TELEMETRY, "1")
        telemetry.reset()
        assert events.enabled()

    def test_noop_singletons_and_nothing_recorded(self, tmp_path):
        events.set_enabled(False)
        # identity, not equality: the no-op path allocates nothing per call
        assert telemetry.span("x") is spans.NOOP_SPAN
        assert telemetry.span("y", a=1) is spans.NOOP_SPAN
        assert events.get_log() is events.NOOP_LOG
        assert registry.get_registry() is registry.NOOP_REGISTRY

        with telemetry.span("x"):
            telemetry.annotate("a")
        registry.get_registry().counter("t", "c").inc()
        assert len(events.get_log()) == 0
        assert registry.get_registry().snapshot() == {}
        assert registry.get_registry().to_prometheus_text() == ""
        assert recorder.dump_flight("test", directory=str(tmp_path)) is None
        assert os.listdir(str(tmp_path)) == []
        assert events.get_log().export_jsonl(str(tmp_path / "x.jsonl")) == 0

    def test_timed_span_still_prints_when_disabled(self):
        events.set_enabled(False)
        lines = []
        with spans.timed_span("Training Time", emit=lines.append):
            pass
        assert len(lines) == 1 and lines[0].startswith("Training Time: ")
        assert len(events.get_log()) == 0


# -- back-compat re-exports ----------------------------------------------------


class TestBackCompat:
    def test_utils_timing_reexports(self):
        from machine_learning_apache_spark_tpu.utils import timing

        assert timing.Timer is spans.Timer
        assert timing.timed_span is spans.timed_span

    def test_timed_span_lands_on_the_timeline(self):
        lines = []
        with spans.timed_span("Epoch Time", emit=lines.append):
            pass
        assert lines and lines[0].startswith("Epoch Time: ")
        names = [e.name for e in events.get_log().snapshot()]
        assert names == ["Epoch Time", "Epoch Time"]  # span_start + span_end

    def test_profiling_annotate_emits_spans(self):
        from machine_learning_apache_spark_tpu.utils.profiling import annotate

        with annotate("square", step=1):
            pass
        evs = events.get_log().snapshot()
        assert [(e.kind, e.name) for e in evs] == [
            ("span_start", "square"), ("span_end", "square"),
        ]
        assert evs[0].attrs == {"step": 1}


# -- the live HTTP plane -------------------------------------------------------


class TestHTTPPlane:
    """telemetry/http.py: endpoint payload functions (no socket), the
    provider registry lifecycle, sidecar discovery, the env port
    contract, and the real server over loopback."""

    def test_metrics_text_includes_registry_and_live_gauges(self):
        registry.get_registry().counter("plane", "hits").inc(3)
        http.register_live_gauge("queue", "depth", lambda: 7.0)
        text = http.metrics_text()
        assert "mlspark_plane_hits 3" in text
        assert "mlspark_queue_depth 7" in text
        # a raising gauge is skipped, never a dead scrape
        http.register_live_gauge("bad", "gauge", lambda: 1 / 0)
        text = http.metrics_text()
        assert "mlspark_queue_depth" in text
        assert "mlspark_bad_gauge" not in text

    def test_healthz_verdict_and_beacon_age(self):
        payload, healthy = http.healthz()
        assert healthy and payload["status"] == "ok"
        assert payload["heartbeat_age_s"] is None  # no beacon yet
        events.beacon_update(phase="train", step=12)
        http.register_health_provider(
            "worker", lambda: {"healthy": True, "note": "fine"}
        )
        payload, healthy = http.healthz()
        assert healthy
        assert payload["phase"] == "train" and payload["step"] == 12
        assert payload["heartbeat_age_s"] is not None
        assert payload["heartbeat_age_s"] < 60.0
        assert payload["checks"]["worker"]["note"] == "fine"
        # one unhealthy check flips the verdict; a raising one does too
        http.register_health_provider("worker", lambda: {"healthy": False})
        payload, healthy = http.healthz()
        assert not healthy and payload["status"] == "degraded"
        http.register_health_provider("worker", lambda: 1 / 0)
        payload, healthy = http.healthz()
        assert not healthy
        assert "error" in payload["checks"]["worker"]

    def test_statusz_sections_and_provider_isolation(self, monkeypatch):
        monkeypatch.setenv("MLSPARK_DP_MODE", "zero1")
        http.register_status_provider("good", lambda: {"answer": 42})
        http.register_status_provider("bad", lambda: 1 / 0)
        payload = http.statusz()
        assert payload["artifact"] == "statusz"
        assert payload["config"]["MLSPARK_DP_MODE"] == "zero1"
        assert payload["sections"]["good"] == {"answer": 42}
        assert "error" in payload["sections"]["bad"]  # isolated, not fatal
        assert "python" in payload["build"]

    def test_flightz_tails_the_ring(self):
        for i in range(20):
            telemetry.annotate("tick", i=i)
        payload = http.flightz(5)
        assert payload["event_count"] == 5
        assert [e["attrs"]["i"] for e in payload["events"]] == list(
            range(15, 20)
        )

    def test_unregister_drops_status_health_and_gauges(self):
        http.register_status_provider("serving", lambda: {})
        http.register_health_provider("serving", lambda: {"healthy": False})
        http.register_live_gauge("serving", "queue_depth", lambda: 1.0)
        http.unregister_provider("serving")
        payload, healthy = http.healthz()
        assert healthy and "serving" not in payload["checks"]
        assert "serving" not in http.statusz()["sections"]
        assert "mlspark_serving_queue_depth" not in http.metrics_text()

    def test_port_sidecar_round_trip(self, tmp_path):
        path = http.write_port_sidecar(1234, directory=str(tmp_path), rank=3)
        assert path and path.endswith("http_rank3.json")
        (tmp_path / "http_rank9.json").write_text("{torn")  # skipped
        found = http.find_port_sidecars(str(tmp_path))
        assert list(found) == [3]
        assert found[3]["port"] == 1234 and found[3]["pid"] == os.getpid()
        # no telemetry dir configured -> no sidecar, no crash
        assert http.write_port_sidecar(1234) is None

    def test_http_port_from_env(self, monkeypatch):
        assert http.http_port_from_env() is None
        for raw, expect in [
            ("0", 0), ("8080", 8080), ("", None), ("  ", None),
            ("nope", None), ("-1", None), ("70000", None),
        ]:
            monkeypatch.setenv(http.ENV_TELEMETRY_HTTP, raw)
            assert http.http_port_from_env() == expect, raw

    def test_server_disabled_means_zero_threads(self, monkeypatch):
        import threading

        # no MLSPARK_TELEMETRY_HTTP: no server, no thread
        before = threading.active_count()
        assert http.start_http_server() is None
        assert threading.active_count() == before
        assert http.get_http_server() is None
        # telemetry killed outright: even an explicit port starts nothing
        monkeypatch.setenv(events.ENV_TELEMETRY, "0")
        telemetry.reset()
        monkeypatch.setenv(http.ENV_TELEMETRY_HTTP, "0")
        assert http.start_http_server() is None
        assert threading.active_count() == before

    def test_server_end_to_end_scrape(self, tmp_path, monkeypatch):
        import urllib.error
        import urllib.request

        monkeypatch.setenv(events.ENV_TELEMETRY_DIR, str(tmp_path))
        telemetry.reset()
        registry.get_registry().counter("scrape", "count").inc(2)
        http.register_health_provider("w", lambda: {"healthy": True})
        srv = http.start_http_server(0, rank=1)
        assert srv is not None and srv.port > 0
        assert http.start_http_server(0) is srv  # idempotent
        # sidecar published + beacon carries the port
        assert http.find_port_sidecars(str(tmp_path))[1]["port"] == srv.port
        assert events.beacon()["http_port"] == srv.port

        def get(path):
            with urllib.request.urlopen(srv.url(path), timeout=10) as r:
                return r.read().decode(), r.status

        body, code = get("/metrics")
        assert code == 200 and "mlspark_scrape_count 2" in body
        body, code = get("/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        body, code = get("/statusz")
        assert code == 200 and json.loads(body)["artifact"] == "statusz"
        body, code = get("/flightz?n=3")
        assert code == 200 and json.loads(body)["event_count"] <= 3
        with pytest.raises(urllib.error.HTTPError) as ei:
            get("/nope")
        assert ei.value.code == 404
        # degraded health answers 503 with the payload attached
        http.register_health_provider("w", lambda: {"healthy": False})
        with pytest.raises(urllib.error.HTTPError) as ei:
            get("/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "degraded"
        sidecar = srv.sidecar_path
        http.stop_http_server()
        assert http.get_http_server() is None
        assert not os.path.exists(sidecar)  # sidecar retracted on stop


class TestBeacon:
    def test_update_and_reset(self):
        assert events.beacon() == {}
        events.beacon_update(phase="train", step=3)
        b = events.beacon()
        assert b["phase"] == "train" and b["step"] == 3
        assert "ts" in b and "wall" in b
        events.beacon_update(step=4)  # merge, not replace
        assert events.beacon()["phase"] == "train"
        assert events.beacon()["step"] == 4
        telemetry.reset()
        assert events.beacon() == {}

    def test_beacon_works_when_telemetry_disabled(self, monkeypatch):
        """The beacon is liveness, not telemetry: the heartbeat payload
        must carry phase/step even with MLSPARK_TELEMETRY=0."""
        monkeypatch.setenv(events.ENV_TELEMETRY, "0")
        telemetry.reset()
        events.beacon_update(phase="train", step=1)
        assert events.beacon()["phase"] == "train"


class TestRequestReport:
    def _ev(self, rank, trace_id, total, queue=0.001, prefill="miss"):
        return {
            "kind": "annotation", "name": "serving.request", "rank": rank,
            "attrs": {
                "trace_id": trace_id, "total_s": total,
                "queue_wait_s": queue, "ttft_s": total / 2,
                "service_s": total - queue, "launches": 3,
                "prefill": prefill,
            },
        }

    def test_breakdown_slowest_and_prefill_split(self):
        evs = [
            self._ev(0, "r0-a", 0.5, prefill="miss"),
            self._ev(1, "r1-b", 2.0, prefill="hit"),
            self._ev(0, "r0-c", 1.0, prefill="hit"),
        ]
        evs.append({"kind": "annotation", "name": "other", "attrs": {}})
        rep = aggregate.request_report(evs)
        assert rep["breakdown"]["total_s"]["count"] == 3
        assert rep["breakdown"]["total_s"]["max"] == 2.0
        assert rep["by_prefill"] == {"hit": 2, "miss": 1}
        assert [r["trace_id"] for r in rep["slowest"]] == [
            "r1-b", "r0-c", "r0-a"
        ]
        assert rep["slowest"][0]["rank"] == 1

    def test_empty_without_request_events(self):
        rep = aggregate.request_report([])
        assert rep["breakdown"] == {} and rep["slowest"] == []

    def test_markdown_section_renders(self):
        report = {
            "ranks": [0], "event_count": 1, "phases": {}, "skew": {},
            "requests": aggregate.request_report(
                [self._ev(0, "r0-a", 0.25)]
            ),
        }
        md = aggregate.render_markdown(report)
        assert "## Request latency breakdown (ms)" in md
        assert "r0-a" in md

    def test_live_report_round_trip(self):
        """on_trace -> event log -> request_report: the real producer
        feeds the real consumer."""
        from machine_learning_apache_spark_tpu.serving.metrics import (
            ServingMetrics,
        )
        from machine_learning_apache_spark_tpu.serving.queue import (
            RequestTrace,
        )

        class _Req:
            def __init__(self, i):
                self.trace = RequestTrace(f"t-{i}")
                self.trace.mark("submit", 0.0)
                self.trace.mark("admit", 0.01 * (i + 1))
                self.trace.mark("first_token", 0.05)
                self.trace.mark("complete", 0.1 * (i + 1))

        m = ServingMetrics()
        for i in range(3):
            m.on_trace(_Req(i))
        evs = [e.to_dict() for e in events.get_log().snapshot()]
        rep = aggregate.request_report(evs)
        assert rep["breakdown"]["total_s"]["count"] == 3
        assert rep["slowest"][0]["trace_id"] == "t-2"
        assert len(m.request_exemplars()) == 3


def test_serving_rollup_of_a_paged_gang_has_no_padded_mode(
    make_tiny_translator
):
    """engine -> event log -> serving_report: the engine stamps every
    ``serving.batch`` span with its one mode, the rollup lists that mode
    alone, and a span that carries none (a foreign log) is not taken for
    a padded engine's."""
    translator, texts = make_tiny_translator(16)
    with translator.serve(
        boundaries=(8, 16), max_active=4, max_new_tokens=4,
    ) as eng:
        for req in [eng.submit(s) for s in texts[:6]]:
            req.result(timeout=120)
    evs = [e.to_dict() for e in events.get_log().snapshot()]
    launches = sum(
        e["kind"] == "span_end" and e["name"] == "serving.batch" for e in evs
    )
    rep = aggregate.serving_report(evs)
    assert launches >= 1
    assert list(rep["batches_by_mode"]) == ["paged"]
    assert rep["batches_by_mode"]["paged"]["count"] == launches
    assert set(rep["counters"]) >= {
        "serving.tokens_real", "serving.tokens_padded"
    }
    assert 0.0 <= rep["padding_waste"] < 1.0
    md = aggregate.render_markdown({
        "ranks": [0], "event_count": len(evs), "phases": {}, "skew": {},
        "serving": rep,
    })
    assert "## Serving" in md and "| paged |" in md
    assert "| padded |" not in md
    evs.append({
        "kind": "span_end", "name": "serving.batch", "value": 0.5,
        "rank": 0, "attrs": {},
    })
    assert list(aggregate.serving_report(evs)["batches_by_mode"]) == [
        "paged", "unknown",
    ]


class TestStatusMarkdown:
    def test_render_rows_and_step_skew(self):
        rows = [
            {"rank": 1, "status": "ok", "phase": "train", "step": 12,
             "heartbeat_age_s": 0.5, "queue_depth": 3, "in_flight": 2,
             "tokens_per_sec": 123.4, "occupancy": 0.25, "port": 9100},
            {"rank": 0, "status": "unreachable", "step": 10},
        ]
        md = aggregate.render_status_markdown(rows)
        assert md.startswith("# Gang status")
        lines = md.splitlines()
        r0 = next(ln for ln in lines if ln.startswith("| 0 "))
        r1 = next(ln for ln in lines if ln.startswith("| 1 "))
        assert lines.index(r0) < lines.index(r1)  # sorted by rank
        assert "unreachable" in r0
        assert "123.4" in r1 and "9100" in r1
        assert "step skew (max - min): 2" in md

    def test_missing_fields_render_dashes(self):
        md = aggregate.render_status_markdown([{"rank": 0}])
        assert "| 0 | - | - | - |" in md


# -- distributed trace context -------------------------------------------------


class TestTraceContext:
    def test_mint_shape_and_uniqueness(self):
        hexdigits = set("0123456789abcdef")
        ctxs = [tracectx.mint() for _ in range(8)]
        assert all(c is not None and c.sampled for c in ctxs)
        for c in ctxs:
            assert len(c.trace_id) == 32 and set(c.trace_id) <= hexdigits
            assert len(c.span_id) == 16 and set(c.span_id) <= hexdigits
        assert len({c.trace_id for c in ctxs}) == 8

    def test_use_stamps_events_and_restores(self):
        ctx = tracectx.mint()
        assert tracectx.current() is None
        with tracectx.use(ctx):
            assert tracectx.current() is ctx
            telemetry.annotate("traced")
            # use(None) is a passthrough — the active context survives
            with tracectx.use(None):
                assert tracectx.current() is ctx
                telemetry.annotate("still-traced")
        assert tracectx.current() is None
        telemetry.annotate("untraced")
        traces = [e.trace for e in events.get_log().snapshot()]
        assert traces == [ctx.trace_id, ctx.trace_id, None]

    def test_nested_use_restores_outer(self):
        a, b = tracectx.mint(), tracectx.mint()
        with tracectx.use(a):
            with tracectx.use(b):
                assert tracectx.current() is b
            assert tracectx.current() is a

    def test_child_shares_trace_with_fresh_span(self):
        ctx = tracectx.mint()
        kid = tracectx.child(ctx)
        assert kid.trace_id == ctx.trace_id
        assert kid.span_id != ctx.span_id
        assert kid.flags == ctx.flags
        assert tracectx.child(None) is None

    def test_mint_none_when_disabled_or_unsampled(self, monkeypatch):
        monkeypatch.setenv(tracectx.ENV_TRACE, "0")
        telemetry.reset()
        assert not tracectx.trace_enabled()
        assert tracectx.mint() is None

        monkeypatch.delenv(tracectx.ENV_TRACE, raising=False)
        monkeypatch.setenv(tracectx.ENV_TRACE_SAMPLE, "0.0")
        telemetry.reset()
        assert tracectx.trace_enabled()
        assert tracectx.mint() is None  # head sampler declines
        assert tracectx.mint(sampled=True) is not None  # explicit override

        # tracing never outlives telemetry itself
        monkeypatch.delenv(tracectx.ENV_TRACE_SAMPLE, raising=False)
        telemetry.reset()
        events.set_enabled(False)
        assert not tracectx.trace_enabled()
        assert tracectx.mint() is None

    def test_sample_rate_clamps_and_tolerates_garbage(self, monkeypatch):
        for raw, expect in [("0.25", 0.25), ("2.5", 1.0), ("-1", 0.0),
                            ("nope", 1.0), ("", 1.0)]:
            monkeypatch.setenv(tracectx.ENV_TRACE_SAMPLE, raw)
            telemetry.reset()
            assert tracectx.sample_rate() == expect, raw

    def test_traceparent_round_trip(self):
        ctx = tracectx.mint()
        header = tracectx.to_traceparent(ctx)
        assert header == f"00-{ctx.trace_id}-{ctx.span_id}-01"
        back = tracectx.parse_traceparent(header)
        assert back == ctx
        # uppercase and surrounding whitespace are tolerated on the wire
        assert tracectx.parse_traceparent(f"  {header.upper()}  ") == ctx

    def test_traceparent_garbage_yields_none(self):
        good_trace, good_span = "ab" * 16, "cd" * 8
        bad = [
            None,
            b"00-" + b"ab" * 16,
            "",
            "not-a-header",
            f"00-{good_trace}-{good_span}",          # missing flags
            f"00-{good_trace}-{good_span}-01-extra",  # too many parts
            f"ff-{good_trace}-{good_span}-01",        # forbidden version
            f"0x-{good_trace}-{good_span}-01",        # non-hex version
            f"00-{'0' * 32}-{good_span}-01",          # all-zero trace id
            f"00-{good_trace}-{'0' * 16}-01",         # all-zero span id
            f"00-{good_trace[:-2]}-{good_span}-01",   # short trace id
            f"00-{good_trace}-{good_span}-zz",        # non-hex flags
        ]
        for header in bad:
            assert tracectx.parse_traceparent(header) is None, header


# -- traceview: stitching, completeness, Perfetto export -----------------------


def _fleet_trace_events(tid="ab" * 16, wire="11" * 8, with_attempt=True):
    """Synthetic router (pid 100, driver) + replica (pid 200, rank 1)
    exports for one traced request, joined by the ctx_span/remote_parent
    cross-process edge."""
    router = [
        {"kind": "span_start", "name": "fleet.submit", "ts": 0.0,
         "wall": 100.0, "rank": None, "pid": 100, "span": 1,
         "parent": None, "trace": tid},
        {"kind": "span_end", "name": "fleet.submit", "ts": 0.5,
         "wall": 100.5, "rank": None, "pid": 100, "span": 1,
         "parent": None, "trace": tid, "value": 0.5},
        {"kind": "annotation", "name": "fleet.request", "ts": 0.5,
         "wall": 100.5, "rank": None, "pid": 100, "trace": tid,
         "attrs": {"outcome": "completed"}},
    ]
    if with_attempt:
        router[1:1] = [
            {"kind": "span_start", "name": "fleet.attempt", "ts": 0.01,
             "wall": 100.01, "rank": None, "pid": 100, "span": 2,
             "parent": 1, "trace": tid,
             "attrs": {"replica": 1, "ctx_span": wire}},
            {"kind": "span_end", "name": "fleet.attempt", "ts": 0.4,
             "wall": 100.4, "rank": None, "pid": 100, "span": 2,
             "parent": 1, "trace": tid, "value": 0.39},
        ]
    replica = [
        {"kind": "span_start", "name": "fleet.replica", "ts": 5.0,
         "wall": 100.02, "rank": 1, "pid": 200, "span": 7, "parent": None,
         "trace": tid, "attrs": {"remote_parent": wire}},
        {"kind": "span_end", "name": "fleet.replica", "ts": 5.3,
         "wall": 100.35, "rank": 1, "pid": 200, "span": 7, "parent": None,
         "trace": tid, "value": 0.33},
        {"kind": "counter", "name": "queue.depth", "ts": 5.1,
         "wall": 100.1, "rank": 1, "pid": 200, "value": 3.0},
    ]
    return router + replica


class TestTraceView:
    def test_assemble_resolves_remote_edge(self):
        trees = traceview.assemble(_fleet_trace_events())
        assert list(trees) == ["ab" * 16]
        tree = trees["ab" * 16]
        assert [n["name"] for n in tree["roots"]] == ["fleet.submit"]
        assert tree["orphans"] == []
        assert tree["span_count"] == 3
        attempt = tree["roots"][0]["children"][0]
        assert attempt["name"] == "fleet.attempt"
        rep = attempt["children"][0]
        assert rep["name"] == "fleet.replica"
        assert rep["via"] == "remote"
        assert rep["rank"] == 1 and rep["dur_s"] == 0.33
        assert [a["name"] for a in tree["annotations"]] == ["fleet.request"]
        summary = traceview.trace_summary(tree)
        assert summary["complete"] is True
        assert summary["root"] == "fleet.submit"
        assert summary["total_s"] == 0.5
        assert summary["processes"] == 2

    def test_unresolved_remote_parent_is_an_orphan(self):
        trees = traceview.assemble(
            _fleet_trace_events(with_attempt=False)
        )
        tree = trees["ab" * 16]
        assert [n["name"] for n in tree["orphans"]] == ["fleet.replica"]
        summary = traceview.trace_summary(tree)
        assert summary["complete"] is False
        comp = traceview.completeness(trees)
        assert comp == {"traces": 1, "complete": 0, "fraction": 0.0}

    def test_completeness_and_slowest_over_many_traces(self):
        evs = _fleet_trace_events(tid="aa" * 16, wire="11" * 8)
        slow = [
            {"kind": "span_start", "name": "fleet.submit", "ts": 0.0,
             "wall": 200.0, "rank": None, "pid": 100, "span": 9,
             "parent": None, "trace": "bb" * 16},
            {"kind": "span_end", "name": "fleet.submit", "ts": 2.0,
             "wall": 202.0, "rank": None, "pid": 100, "span": 9,
             "parent": None, "trace": "bb" * 16, "value": 2.0},
        ]
        trees = traceview.assemble(evs + slow)
        comp = traceview.completeness(trees)
        assert comp == {"traces": 2, "complete": 2, "fraction": 1.0}
        rows = traceview.slowest(trees, n=10)
        assert [r["trace_id"] for r in rows] == ["bb" * 16, "aa" * 16]
        assert traceview.slowest(trees, n=1)[0]["total_s"] == 2.0

    def test_perfetto_export_shape(self):
        doc = traceview.perfetto_export(_fleet_trace_events())
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        json.dumps(doc)  # valid Chrome trace JSON end to end
        by_ph = {}
        for e in evs:
            by_ph.setdefault(e["ph"], []).append(e)
        # 3 slices, one s->f flow over the remote edge, 1 instant,
        # 1 counter, and name+sort metadata for both processes
        assert len(by_ph["X"]) == 3
        assert len(by_ph["s"]) == len(by_ph["f"]) == 1
        assert len(by_ph["i"]) == 1
        assert len(by_ph["C"]) == 1
        assert len(by_ph["M"]) == 4
        # replica rows key on gang rank, driver rows on OS pid
        assert {e["pid"] for e in by_ph["X"]} == {100, 1}
        names = {e["args"]["name"] for e in by_ph["M"]
                 if e["name"] == "process_name"}
        assert names == {"driver pid=100", "rank 1"}
        # flow arrow ties the attempt slice to the replica slice
        s, f = by_ph["s"][0], by_ph["f"][0]
        assert s["id"] == f["id"] == "11" * 8
        assert s["pid"] == 100 and f["pid"] == 1
        # wall-clock micros; traced spans share a per-trace track id
        submit = next(e for e in by_ph["X"] if e["name"] == "fleet.submit")
        assert submit["ts"] == 100.0 * 1e6 and submit["dur"] == 0.5 * 1e6
        assert submit["tid"] == int("ab" * 4, 16) & 0x3FFFFFFF

    def test_perfetto_trace_filter_and_untraced_track(self):
        evs = _fleet_trace_events() + [
            {"kind": "span_start", "name": "train.step", "ts": 9.0,
             "wall": 300.0, "rank": 0, "pid": 300, "span": 42,
             "parent": None},
            {"kind": "span_end", "name": "train.step", "ts": 9.1,
             "wall": 300.1, "rank": 0, "pid": 300, "span": 42,
             "parent": None, "value": 0.1},
        ]
        full = traceview.perfetto_export(evs)
        slices = [e for e in full["traceEvents"] if e["ph"] == "X"]
        train = next(e for e in slices if e["name"] == "train.step")
        assert train["tid"] == 0  # untraced spans share track 0
        only = traceview.perfetto_export(evs, trace_id="ab" * 16)
        names = {e["name"] for e in only["traceEvents"] if e["ph"] == "X"}
        assert "train.step" not in names
        assert "fleet.submit" in names

    def test_tracez_payload_summary_and_tree(self):
        evs = _fleet_trace_events()
        summary = traceview.tracez_payload(evs)
        assert summary["artifact"] == "tracez"
        assert summary["completeness"]["traces"] == 1
        assert len(summary["traces"]) == 1
        tree = traceview.tracez_payload(evs, "ab" * 16)
        assert tree["trace_id"] == "ab" * 16
        assert [n["name"] for n in tree["roots"]] == ["fleet.submit"]
        missing = traceview.tracez_payload(evs, "ff" * 16)
        assert missing["error"] == "unknown trace id"

    def test_live_tracez_endpoint_payload(self):
        """The /tracez payload over the live ring: the real span layer
        feeds the real stitcher."""
        ctx = tracectx.mint()
        with tracectx.use(ctx), telemetry.span("fleet.submit"):
            pass
        payload = http.tracez()
        assert payload["artifact"] == "tracez"
        assert payload["completeness"]["complete"] == 1
        tree = http.tracez(ctx.trace_id)
        assert [n["name"] for n in tree["roots"]] == ["fleet.submit"]

    def test_load_dir_merges_rank_files_and_flight_dumps(self, tmp_path):
        d = str(tmp_path)
        _write_rank_jsonl(d, 0, {"fleet.submit": [0.5]})
        # A crashed replica's only export is its flight dump; its events
        # must merge in (rank-stamped) without duplicating rank files.
        with open(os.path.join(d, "flight_1.json"), "w") as f:
            json.dump({"rank": 1, "events": [
                {"kind": "span_start", "name": "fleet.replica", "ts": 0.1,
                 "wall": 1e9, "rank": None, "pid": 2, "span": 1},
            ]}, f)
        evs = traceview.load_dir(d)
        assert len(evs) == 3
        replica = next(e for e in evs if e["name"] == "fleet.replica")
        assert replica["rank"] == 1
        # dedup: re-listing the same events in a second dump adds nothing
        with open(os.path.join(d, "flight_2.json"), "w") as f:
            json.dump({"rank": 1, "events": [dict(replica)]}, f)
        assert len(traceview.load_dir(d)) == 3


# -- aggregate: the mtime/size-keyed JSONL parse cache -------------------------


class TestParseCache:
    def _write(self, path, names):
        with open(path + ".tmp", "w") as f:
            for i, name in enumerate(names):
                f.write(json.dumps({
                    "kind": "annotation", "name": name, "ts": float(i),
                    "wall": 1e9 + i, "rank": None, "pid": 1,
                }) + "\n")
        os.replace(path + ".tmp", path)

    def test_hit_returns_fresh_outer_list(self, tmp_path):
        path = str(tmp_path / "telemetry_rank0.jsonl")
        self._write(path, ["a", "b"])
        first = aggregate.load_jsonl(path)
        second = aggregate.load_jsonl(path)
        assert first == second
        assert first is not second  # callers own their list
        first.append({"name": "poison"})
        assert [e["name"] for e in aggregate.load_jsonl(path)] == ["a", "b"]

    def test_rewrite_invalidates(self, tmp_path):
        path = str(tmp_path / "telemetry_rank0.jsonl")
        self._write(path, ["a"])
        assert len(aggregate.load_jsonl(path)) == 1
        self._write(path, ["a", "b", "c"])  # atomic replace, new stamp
        assert len(aggregate.load_jsonl(path)) == 3

    def test_merge_rank_stamping_does_not_poison_cache(self, tmp_path):
        path = str(tmp_path / aggregate.rank_file_name(3))
        self._write(path, ["a"])
        merged = aggregate.merge_rank_files({3: path})
        assert merged[0]["rank"] == 3  # stamped on a copy
        assert aggregate.load_jsonl(path)[0]["rank"] is None

    def test_reset_clears_the_cache(self, tmp_path):
        path = str(tmp_path / "telemetry_rank0.jsonl")
        self._write(path, ["a"])
        aggregate.load_jsonl(path)
        assert aggregate._PARSE_CACHE
        telemetry.reset()
        assert not aggregate._PARSE_CACHE

    def test_cache_is_bounded(self, tmp_path):
        for i in range(aggregate._PARSE_CACHE_MAX + 8):
            path = str(tmp_path / f"telemetry_rank{i}.jsonl")
            self._write(path, ["a"])
            aggregate.load_jsonl(path)
        assert len(aggregate._PARSE_CACHE) <= aggregate._PARSE_CACHE_MAX

"""Tracing-overhead + trace-completeness bench — BENCH_SERVE_r06.json.

Re-pins the BENCH_SERVE_r05 paged saturation knee with distributed
tracing enabled at its defaults (``MLSPARK_TRACE`` on,
``MLSPARK_TRACE_SAMPLE`` 1.0 — every request minted, stamped, and
annotated) and answers the two questions the tracing layer promised
(docs/OBSERVABILITY.md, "Distributed tracing"):

- **overhead** — the traced paged knee must stay within 3% of a
  same-run untraced column (``MLSPARK_TRACE=0``, the ``use(None)``
  zero-cost path) over the identical workload, engine knobs, and
  self-calibration method ``serve_bench`` uses. Same-run is the honest,
  machine-contention-immune form of "within 3% of r05" (the PR-13
  caveat: cross-run numbers on a contended host are garbage); the
  artifact additionally records the cross-run ratio against
  BENCH_SERVE_r05's paged knee and enforces *that* gate too whenever
  the comparison is meaningful (full-size model, r05 artifact present,
  host not contended at preflight, and the *untraced* column itself
  reproducing the r05 baseline — a host that is slow with tracing off
  would fail the cross-run pin for reasons that have nothing to do
  with tracing; otherwise ``gate_skipped_reason`` says why the number
  is reference-only).
- **trace_complete** — ≥ 99% of sampled requests must stitch into a
  single rooted tree with zero orphan spans (``telemetry.traceview``):
  over the whole traced sweep (engine-level traces rooted at
  ``serving.submit``), and over a 2-replica fleet section where every
  trace must cross router → HTTP → replica → engine and root at
  ``fleet.submit`` with the ``fleet.replica`` span joined through its
  ``remote_parent`` edge.

``--smoke`` is the tier-1 CI entry: tiny model, short sweeps, the
same-run overhead + completeness gates (the r05 cross-run gate is
skipped — a tiny model's knee is not comparable). The full run writes
``BENCH_SERVE_r06.json`` (``--out`` relocates).

Usage: JAX_PLATFORMS=cpu python tools/trace_bench.py [--smoke] [--out P]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from machine_learning_apache_spark_tpu.utils.sysinfo import host_load  # noqa: E402

#: Must match the serve_bench sweep knobs exactly — the r05 knee this
#: bench re-pins was measured under these; a different engine config
#: would compare two different machines' worth of work.
SERVE_KNOBS = dict(
    boundaries=(8, 16), max_batch=8,
    max_queue_depth=128, max_new_tokens=10, prefix_cache_size=256,
    steps_per_launch=10, max_active=16,
)

#: 3% throughput tolerance — both for the same-run traced/untraced
#: ratio and the cross-run ratio against r05's paged knee.
OVERHEAD_FLOOR = 0.97

#: The smoke's sweep is 1.5 s of a tiny model — the traced/untraced
#: ratio there is noise-dominated (measured runs land on either side of
#: 1.0), so tier-1 enforces a pathology floor (catching a tracing layer
#: that *halves* throughput) and leaves the 3% pin to the full run.
SMOKE_OVERHEAD_FLOOR = 0.75

#: trace_complete gate: fraction of sampled requests stitching into a
#: single rooted orphan-free tree.
COMPLETE_FLOOR = 0.99

#: Ring budget covering every event the traced sweep emits (3 events
#: per request plus 24 per decode cycle: the cycle's phase spans and its
#: token counters); sized so completeness is measured over the whole
#: run, not a ring tail.
EVENT_RING = 524288


def _reset_tracing(value: str) -> None:
    """Flip ``MLSPARK_TRACE`` between columns. The cached env parse (and
    the event ring, so each column's events are its own) drop on
    ``telemetry.reset()``; the next engine start re-bootstraps the HTTP
    plane."""
    from machine_learning_apache_spark_tpu import telemetry

    os.environ["MLSPARK_TRACE"] = value
    telemetry.reset()


def sweep_column(translator, texts, traced: bool, duration: float,
                 fractions) -> dict:
    """One paged sweep with tracing on or off — serve_bench's run_mode
    verbatim (same calibration, conservation, and mid-load scrape), so
    the two columns differ in exactly one variable."""
    from serve_bench import run_mode

    _reset_tracing("1" if traced else "0")
    result = run_mode(
        translator, texts, "paged", SERVE_KNOBS, duration, fractions
    )
    result["traced"] = traced
    return result


def knee_row(column: dict) -> dict:
    return next(
        r for r in column["rows"] if r["load_fraction"] == 1.0
    )


def engine_trace_complete() -> dict:
    """Stitch every trace the traced sweep left in the event ring —
    called before anything resets it."""
    from machine_learning_apache_spark_tpu.telemetry import (
        events,
        traceview,
    )

    evs = [e.to_dict() for e in events.get_log().snapshot()]
    trees = traceview.assemble(evs)
    comp = traceview.completeness(trees)
    comp["slowest"] = traceview.slowest(trees, 5)
    return comp


def fleet_trace_complete(translator, texts, n_requests: int) -> dict:
    """2-replica fleet section: two engine replicas behind real HTTP
    data planes, a round-robin router minting one context per
    request, and the traceview verdict over exactly the minted trace
    ids — every one must root at ``fleet.submit`` and resolve its
    cross-process ``remote_parent`` edge."""
    from machine_learning_apache_spark_tpu.fleet import (
        FleetRouter,
        ReplicaServer,
        ReplicaSnapshot,
    )
    from machine_learning_apache_spark_tpu.telemetry import (
        events,
        traceview,
    )

    import tempfile

    engines, servers, payloads = [], [], []
    with tempfile.TemporaryDirectory(prefix="trace_bench_fleet_") as tmp:
        try:
            for rank in range(2):
                eng = translator.serve(
                    boundaries=(8, 16), max_batch=4, max_new_tokens=8,
                )
                engines.append(eng)
                srv = ReplicaServer(eng, rank=rank, port=0)
                srv.start(directory=tmp)
                servers.append(srv)
            snaps = {
                s.rank: ReplicaSnapshot(
                    rank=s.rank, port=s.port, healthy=True, status="ok",
                    in_flight=0, queue_depth=0,
                    prefix_digests=frozenset(),
                )
                for s in servers
            }
            router = FleetRouter(
                snapshot_source=lambda: dict(snaps),
                policy="round_robin",
            )
            for i in range(n_requests):
                payloads.append(router.submit(texts[i % len(texts)]))
        finally:
            for srv in servers:
                srv.stop()
            for eng in engines:
                eng.stop()

    minted = [p.get("trace_id") for p in payloads]
    evs = [e.to_dict() for e in events.get_log().snapshot()]
    trees = traceview.assemble(evs)
    complete = 0
    incomplete: list[dict] = []
    for tid in minted:
        tree = trees.get(tid)
        summary = None if tree is None else traceview.trace_summary(tree)
        if (
            summary is not None
            and summary["complete"]
            and summary["root"] == "fleet.submit"
        ):
            complete += 1
        elif len(incomplete) < 8:
            incomplete.append(
                {"trace_id": tid, "summary": summary}
            )
    ranks_served = sorted({p["rank"] for p in payloads})
    return {
        "requests": n_requests,
        "ranks_served": ranks_served,
        "both_replicas_served": ranks_served == [0, 1],
        "traces": len(minted),
        "complete": complete,
        "fraction": round(complete / n_requests, 6) if n_requests else None,
        "incomplete": incomplete,
    }


def r05_reference(traced_knee_tps: float, untraced_knee_tps: float,
                  smoke: bool, contended: bool) -> dict:
    """The cross-run half of the overhead story: the traced knee against
    the r05 paged knee, enforced only when the comparison means
    something. The confound detector is the *untraced* column: if the
    host cannot reproduce the r05 baseline even with tracing off, the
    cross-run ratio measures the machine, not the tracing layer — the
    ratios are still recorded, the gate records why it didn't bind, and
    the same-run ``overhead`` gate stays authoritative (the PR-13
    contention caveat, applied to cross-run comparisons)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_SERVE_r05.json")
    out: dict = {"r05_path": None, "r05_paged_tokens_per_sec": None,
                 "vs_r05_ratio": None, "untraced_vs_r05_ratio": None,
                 "gate_skipped_reason": None}
    if smoke:
        out["gate_skipped_reason"] = (
            "smoke: tiny model, knee not comparable to r05"
        )
        return out
    if not os.path.exists(path):
        out["gate_skipped_reason"] = "BENCH_SERVE_r05.json not found"
        return out
    with open(path) as fh:
        r05 = json.load(fh)
    ref = ((r05.get("knee") or {}).get("paged_tokens_per_sec"))
    out["r05_path"] = path
    out["r05_paged_tokens_per_sec"] = ref
    if not ref:
        out["gate_skipped_reason"] = "r05 artifact has no paged knee"
        return out
    out["vs_r05_ratio"] = round(traced_knee_tps / ref, 4)
    out["untraced_vs_r05_ratio"] = round(untraced_knee_tps / ref, 4)
    if contended:
        out["gate_skipped_reason"] = (
            "host contended at preflight; cross-run ratio is "
            "reference-only (PR-13 caveat)"
        )
    elif out["untraced_vs_r05_ratio"] < OVERHEAD_FLOOR:
        out["gate_skipped_reason"] = (
            f"host does not reproduce the r05 baseline even untraced "
            f"(untraced knee at {out['untraced_vs_r05_ratio']}x r05); "
            "cross-run ratio is reference-only, same-run overhead gate "
            "is authoritative"
        )
    return out


def main() -> None:
    smoke = "--smoke" in sys.argv
    out_path = "BENCH_SERVE_r06.json"
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    if smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # Same production configuration as serve_bench: live plane on an
    # ephemeral port (the mid-load scrape gate rides every column), and
    # an event ring sized to hold the whole traced sweep.
    os.environ.setdefault("MLSPARK_TELEMETRY_HTTP", "0")
    os.environ.setdefault("MLSPARK_TELEMETRY_EVENTS", str(EVENT_RING))

    host = host_load()
    if host["contended"]:
        print(json.dumps({"warning": "host contended at preflight",
                          "host_load": host}), flush=True)

    from serve_bench import _platform, build_translator

    translator, texts = build_translator(tiny=smoke)
    duration = 1.5 if smoke else 8.0
    fractions = (0.25, 1.0) if smoke else (0.5, 1.0)

    untraced = sweep_column(translator, texts, False, duration, fractions)
    traced = sweep_column(translator, texts, True, duration, fractions)
    engine_complete = engine_trace_complete()
    print(json.dumps({"engine_trace_complete": {
        k: v for k, v in engine_complete.items() if k != "slowest"
    }}), flush=True)

    fleet = fleet_trace_complete(translator, texts, 16 if smoke else 64)
    print(json.dumps({"fleet_trace_complete": {
        k: v for k, v in fleet.items() if k != "incomplete"
    }}), flush=True)

    un_knee, tr_knee = knee_row(untraced), knee_row(traced)
    overhead_ratio = round(
        tr_knee["tokens_per_sec"] / un_knee["tokens_per_sec"], 4
    )
    r05 = r05_reference(
        tr_knee["tokens_per_sec"], un_knee["tokens_per_sec"],
        smoke, bool(host["contended"]),
    )

    overhead_floor = SMOKE_OVERHEAD_FLOOR if smoke else OVERHEAD_FLOOR
    gates = {
        "overhead": overhead_ratio >= overhead_floor,
        "vs_r05": (
            True if r05["gate_skipped_reason"]
            else r05["vs_r05_ratio"] >= OVERHEAD_FLOOR
        ),
        "trace_complete_engine": (
            engine_complete["traces"] > 0
            and engine_complete["fraction"] >= COMPLETE_FLOOR
        ),
        "trace_complete_fleet": (
            fleet["both_replicas_served"]
            and fleet["fraction"] >= COMPLETE_FLOOR
        ),
        "zero_recompiles": (
            untraced["recompiles_after_warmup"] == 0
            and traced["recompiles_after_warmup"] == 0
        ),
        "conservation": True,  # run_mode raised already if violated
        "midload_scrape": (
            untraced["midload_scrape"].get("ok") is True
            and traced["midload_scrape"].get("ok") is True
        ),
    }
    ok = all(gates.values())
    artifact = {
        "bench": "serve-trace",
        "smoke": smoke,
        "platform": _platform(),
        "host_load": host,
        "contended": host["contended"],
        "duration_per_level_s": duration,
        "sampling": {"trace": "on", "sample_rate": 1.0},
        "columns": {"untraced": untraced, "traced": traced},
        "knee": {
            "overhead_floor": overhead_floor,
            "untraced_tokens_per_sec": un_knee["tokens_per_sec"],
            "traced_tokens_per_sec": tr_knee["tokens_per_sec"],
            "untraced_p99_s": un_knee["p99_latency_s"],
            "traced_p99_s": tr_knee["p99_latency_s"],
            "overhead_ratio": overhead_ratio,
            **r05,
        },
        "trace_complete": {
            "engine": engine_complete,
            "fleet": fleet,
        },
        "gates": gates,
        "ok": ok,
    }
    with open(out_path, "w") as fh:
        json.dump(artifact, fh, indent=1)
    print(json.dumps({"wrote": out_path, "gates": gates, "ok": ok}),
          flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()

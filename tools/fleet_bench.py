"""Fleet bench — N serving replicas behind the router vs one replica.

Stands up a real multi-replica data plane (``launcher.ReplicaGang`` →
``fleet.serve_replica`` workers, one engine + HTTP front door each) with
a ``fleet.FleetRouter`` dispatching over the live scrape plane, and
measures what the fleet layer itself adds:

- **parity** — prompts routed through the fleet must produce
  token-identical greedy outputs to a local in-process engine (the
  replicas build the same deterministic seed-0 translator, so HTTP +
  routing must be a pure transport);
- **conservation** — after the drain, the router ledger balances
  (submitted == completed + rejected + unavailable + failed) and every
  replica's scraped ledger shows zero in-flight: nothing silently lost
  across process boundaries;
- **affinity** — the prefix-cache-affinity policy must land repeated
  prompts on the replica already holding their prefix: fleet-wide
  prefix-cache hit rate under ``affinity`` ≥ ``AFFINITY_GATE_RATIO`` ×
  the ``round_robin`` hit rate on the same shared-prefix workload
  (fresh caches for each policy);
- **scaling** — closed-loop tokens/sec through the router at the
  saturation knee, fleet vs single replica. The ≥ ``SCALING_GATE``
  ratio is *enforced when the host has the cores to run the replicas in
  parallel* (``cores >= 2``); on a single-core host a CPU-bound decode
  fleet cannot physically exceed 1.0× aggregate (the replicas time-share
  one core), so the bench records the measured ratio, checks the router
  adds no capacity loss (``SINGLE_CORE_FLOOR``), and marks the gate
  skipped — loudly, in the artifact — rather than faking a pass.

Per-replica skew comes from the scrape plane itself
(``telemetry.aggregate.replica_skew`` over ``ScrapeLoop.rows()``), and
the router's per-replica dispatch counts ride along — the evidence that
traffic actually spread.

``--smoke`` is the tier-1 CI entry: 2-replica gang + router, parity and
conservation gates only (the timing-sensitive gates need the full run),
exiting nonzero if either fails. The full run writes
``BENCH_SERVE_r04.json`` (``--out`` relocates).

Usage: JAX_PLATFORMS=cpu python tools/fleet_bench.py [--smoke] [--out P]
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from serve_bench import build_translator  # noqa: E402

from machine_learning_apache_spark_tpu.utils.sysinfo import host_load  # noqa: E402

#: Affinity hit rate must beat round-robin by at least this factor.
AFFINITY_GATE_RATIO = 1.5
#: Fleet tokens/sec must reach this multiple of single-replica (when the
#: host has >= 2 cores — see module docstring).
SCALING_GATE = 1.8
#: On a single core the fleet shares the CPU with the baseline; the
#: router must still not *lose* more than this fraction of capacity.
SINGLE_CORE_FLOOR = 0.6


def replica_main(tiny: bool, knobs: dict, max_s: float = 900.0) -> dict:
    """Gang-worker body (run by reference in each replica process):
    build the deterministic bench translator and serve it behind the
    fleet data plane until the stop marker lands."""
    from machine_learning_apache_spark_tpu.fleet.replica import serve_replica

    translator, _ = build_translator(tiny=tiny)
    return serve_replica(translator, dict(knobs), max_s=max_s)


def bench_knobs(tiny: bool) -> dict:
    """Per-replica engine knobs — the serve_bench paged profile, so the
    fleet columns are comparable to the single-engine bench's."""
    return dict(
        boundaries=(8, 16), max_batch=8,
        max_queue_depth=128, max_new_tokens=10, prefix_cache_size=256,
        steps_per_launch=10, max_active=16,
    )


def make_key_fn(translator):
    """The router's affinity key: the SAME tokens the engine keys its
    ``PrefixCache`` on (``src_pipe.ragged``), through the same digest —
    agreement by construction, not by convention."""
    from machine_learning_apache_spark_tpu.serving import prefix_digest

    src_pipe = translator.src_pipe
    return lambda text: prefix_digest(src_pipe.ragged([text])[0])


def build_fleet(
    n: int,
    workdir: str,
    *,
    tiny: bool,
    policy: str = "affinity",
    key_fn=None,
    knobs: dict | None = None,
    extra_env: dict | None = None,
    router_kw: dict | None = None,
):
    """Launch an n-replica gang + router over it; blocks until every
    replica scrapes healthy. Returns ``(gang, router)`` — both started;
    the caller owns teardown (router.stop() then gang.stop()).
    ``extra_env`` reaches every replica process (how the fault drill
    ships a ``MLSPARK_FAULTS`` wire plan to the ranks); ``router_kw``
    reaches the router constructor (how the hedge drill flips
    ``hedge=True`` without touching this driver's environment)."""
    from machine_learning_apache_spark_tpu.fleet import FleetRouter
    from machine_learning_apache_spark_tpu.launcher import ReplicaGang

    gang = ReplicaGang(
        "fleet_bench:replica_main",
        tiny,
        knobs or bench_knobs(tiny),
        num_replicas=n,
        workdir=workdir,
        platform="cpu",
        # Replicas serve observability through the data-plane port; the
        # runner's separate telemetry HTTP server would only burn CPU.
        telemetry_http=None,
        env={"MLSPARK_TELEMETRY_HTTP": "", **(extra_env or {})},
    ).start()
    router = FleetRouter(
        workdir, policy=policy, key_fn=key_fn, scrape_interval=0.25,
        **(router_kw or {}),
    ).start()
    if not router.wait_for_replicas(n, timeout=240.0):
        router.stop()
        gang.stop()
        raise RuntimeError(
            f"fleet of {n} never came healthy in {workdir} "
            f"(gang status: {gang.status()})"
        )
    return gang, router


def drive_load(
    router, texts, *, clients: int, duration: float, tier: str = "batch",
) -> dict:
    """Closed-loop load: ``clients`` threads each submit → wait → repeat
    for ``duration`` seconds. Client-observed tokens/sec (the sum of the
    replicas' own token counts over the wall window) plus per-outcome
    tallies."""
    from machine_learning_apache_spark_tpu.fleet import (
        FleetBackpressure,
        FleetRequestFailed,
        FleetUnavailable,
    )
    from machine_learning_apache_spark_tpu.serving.queue import (
        DeadlineExceeded,
    )

    lock = threading.Lock()
    counts = {"completed": 0, "rejected": 0, "unavailable": 0,
              "failed": 0, "expired": 0, "tokens": 0}
    latencies: list[float] = []
    stop_at = time.monotonic() + duration

    def client(i: int) -> None:
        n = i  # stagger starting prompts so clients don't lockstep
        while time.monotonic() < stop_at:
            t0 = time.monotonic()
            try:
                out = router.submit(
                    texts[n % len(texts)], tier=tier, deadline_s=60.0,
                )
                with lock:
                    counts["completed"] += 1
                    counts["tokens"] += int(out.get("tokens") or 0)
                    latencies.append(time.monotonic() - t0)
            except FleetBackpressure as e:
                with lock:
                    counts["rejected"] += 1
                time.sleep(min(e.retry_after, 0.25))
            except FleetUnavailable:
                with lock:
                    counts["unavailable"] += 1
                time.sleep(0.1)
            except FleetRequestFailed:
                with lock:
                    counts["failed"] += 1
            except DeadlineExceeded:
                with lock:
                    counts["expired"] += 1
            n += clients
        return None

    t0 = time.monotonic()
    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(duration + 120.0)
    elapsed = time.monotonic() - t0
    from machine_learning_apache_spark_tpu.serving.metrics import percentile

    return {
        "clients": clients,
        "duration_s": round(elapsed, 2),
        **counts,
        "tokens_per_sec": round(counts["tokens"] / elapsed, 1),
        "requests_per_sec": round(counts["completed"] / elapsed, 2),
        "p50_latency_s": _r4(percentile(latencies, 50)),
        "p99_latency_s": _r4(percentile(latencies, 99)),
    }


def _r4(v):
    return None if v is None else round(v, 4)


def fleet_prefix_stats(router) -> dict:
    """Fleet-wide prefix-cache hit rate from the scraped replicas (tick
    the loop once more so the numbers include the workload's tail)."""
    if router._scrape is not None:
        snaps = router._scrape.tick()
    else:
        snaps = router._snapshot_source()
    hits = misses = 0
    per_replica = {}
    for rank, snap in sorted(snaps.items()):
        st = snap.prefix_stats or {}
        h, m = int(st.get("hits") or 0), int(st.get("misses") or 0)
        hits += h
        misses += m
        per_replica[rank] = dict(st)
    lookups = hits + misses
    return {
        "hits": hits,
        "misses": misses,
        "hit_rate": round(hits / lookups, 4) if lookups else None,
        "per_replica": per_replica,
    }


def parity_gate(router, translator, texts, knobs: dict, n: int) -> dict:
    """Token-identical outputs: the same prompts through the fleet and
    through a local in-process engine built from the same seed."""
    routed = []
    for t in texts[:n]:
        out = router.submit(t, tier="interactive", deadline_s=60.0)
        routed.append(out["text"])
    local_knobs = {k: v for k, v in knobs.items()}
    with translator.serve(**local_knobs) as eng:
        futs = [eng.submit(t) for t in texts[:n]]
        local = [f.result(timeout=120) for f in futs]
    mismatches = [i for i, (a, b) in enumerate(zip(routed, local)) if a != b]
    return {
        "checked": n,
        "identical": not mismatches,
        "mismatches": mismatches[:8],
    }


def conservation_gate(router) -> dict:
    """Router ledger balanced + zero in-flight scraped on every replica."""
    ledger = router.check_conservation(in_flight=0)
    snaps = (
        router._scrape.tick() if router._scrape is not None
        else router._snapshot_source()
    )
    replica_in_flight = {
        rank: snap.in_flight for rank, snap in sorted(snaps.items())
    }
    drained = all((v or 0) == 0 for v in replica_in_flight.values())
    return {
        "ok": drained,
        "router_ledger": ledger,
        "replica_in_flight": replica_in_flight,
    }


def affinity_phase(
    workdir_base: str, translator, texts, *, tiny: bool, knobs: dict,
) -> dict:
    """Hit-rate comparison on a shared-prefix workload: K distinct
    prompts cycled ``repeats`` times, sequentially (hit rate is a
    routing property, not a throughput one), against a FRESH fleet per
    policy so each policy owns its cache history. K is odd so strict
    round-robin on 2 replicas alternates every prompt between them —
    the workload that punishes affinity-blind dispatch hardest."""
    key_fn = make_key_fn(translator)
    k, repeats = 11, 3
    prompts = texts[:k]
    results = {}
    for policy in ("round_robin", "affinity"):
        workdir = os.path.join(workdir_base, f"affinity_{policy}")
        gang, router = build_fleet(
            2, workdir, tiny=tiny, policy=policy, key_fn=key_fn,
            knobs=knobs,
        )
        try:
            for r in range(repeats):
                for p in prompts:
                    router.submit(p, tier="interactive", deadline_s=60.0)
            stats = fleet_prefix_stats(router)
            results[policy] = {
                "requests": k * repeats,
                "distinct_prompts": k,
                **stats,
                "router_per_replica": router.stats()["per_replica"],
            }
        finally:
            router.stop()
            gang.stop()
    rr = results["round_robin"]["hit_rate"] or 0.0
    af = results["affinity"]["hit_rate"] or 0.0
    ratio = round(af / rr, 3) if rr > 0 else None
    return {
        **results,
        "hit_rate_ratio": ratio,
        "gate_ratio": AFFINITY_GATE_RATIO,
        "ok": ratio is not None and ratio >= AFFINITY_GATE_RATIO,
    }


def scaling_phase(
    workdir_base: str, translator, texts, *, tiny: bool, knobs: dict,
    replicas: int, clients: int, duration: float,
) -> dict:
    """Closed-loop knee throughput, fleet of N vs fleet of 1 — same
    router, same client pool, same knobs, so the only variable is the
    replica count. Includes the per-replica skew verdict from the
    scrape plane."""
    from machine_learning_apache_spark_tpu.telemetry.aggregate import (
        replica_skew,
    )

    key_fn = make_key_fn(translator)
    columns = {}
    for n in (replicas, 1):
        workdir = os.path.join(workdir_base, f"scale_{n}")
        gang, router = build_fleet(
            n, workdir, tiny=tiny, policy="affinity", key_fn=key_fn,
            knobs=knobs,
        )
        try:
            # Warm every replica's cache + programs before the window.
            for p in texts[: 2 * len(gang.alive())]:
                router.submit(p, tier="interactive", deadline_s=60.0)
            load = drive_load(
                router, texts, clients=clients, duration=duration,
            )
            rows = (
                router._scrape.rows() if router._scrape is not None else []
            )
            columns[f"replicas_{n}"] = {
                "replicas": n,
                "load": load,
                "conservation": conservation_gate(router),
                "router": router.stats(),
                "scrape_rows": rows,
                "replica_skew": replica_skew(rows),
            }
        finally:
            router.stop()
            gang.stop()
    fleet_tps = columns[f"replicas_{replicas}"]["load"]["tokens_per_sec"]
    single_tps = columns["replicas_1"]["load"]["tokens_per_sec"]
    ratio = round(fleet_tps / single_tps, 3) if single_tps else None
    cores = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    enforced = cores >= 2
    if ratio is None:
        ok = False
    elif enforced:
        ok = ratio >= SCALING_GATE
    else:
        # One core: the replicas time-share the CPU, so aggregate decode
        # throughput is capacity-capped at ~1.0x no matter how many
        # processes serve it. Enforce "the fleet layer loses (almost)
        # nothing" instead, and say so in the artifact.
        ok = ratio >= SINGLE_CORE_FLOOR
    return {
        **columns,
        "fleet_tokens_per_sec": fleet_tps,
        "single_tokens_per_sec": single_tps,
        "scaling_ratio": ratio,
        "gate_ratio": SCALING_GATE,
        "cores": cores,
        "gate_enforced": enforced,
        "gate_skipped_reason": None if enforced else (
            f"host has {cores} core(s); a CPU-bound decode fleet cannot "
            f"scale past 1.0x aggregate on one core — enforced floor "
            f"{SINGLE_CORE_FLOOR}x instead"
        ),
        "ok": ok,
    }


def run_smoke(out_path: str | None) -> int:
    """Tier-1 entry: 2-replica gang + router; parity + conservation."""
    import tempfile

    host = host_load()  # preflight — before any replica spawns
    translator, texts = build_translator(tiny=True)
    knobs = bench_knobs(tiny=True)
    workdir = tempfile.mkdtemp(prefix="mlspark_fleet_smoke_")
    gang, router = build_fleet(
        2, workdir, tiny=True, policy="affinity",
        key_fn=make_key_fn(translator), knobs=knobs,
    )
    try:
        parity = parity_gate(router, translator, texts, knobs, n=8)
        print(json.dumps({"parity": parity}), flush=True)
        # A short burst so conservation is checked over real concurrency,
        # not just the sequential parity prompts.
        load = drive_load(router, texts, clients=4, duration=2.0)
        print(json.dumps({"load": load}), flush=True)
        conservation = conservation_gate(router)
        print(json.dumps({"conservation": conservation}), flush=True)
        router_stats = router.stats()
    finally:
        router.stop()
        gang.stop()
    spread = [
        r for r, v in router_stats["per_replica"].items()
        if v.get("completed")
    ]
    gates = {
        "parity": parity["identical"],
        "conservation": conservation["ok"],
        # Both replicas must have actually served traffic — a router
        # that silently pinned everything to rank 0 still "conserves".
        "both_replicas_served": len(spread) >= 2,
    }
    ok = all(gates.values())
    artifact = {
        "bench": "fleet",
        "smoke": True,
        "host_load": host,
        "contended": host["contended"],
        "parity": parity,
        "load": load,
        "conservation": conservation,
        "router": router_stats,
        "gang": gang.status(),
        "gates": gates,
        "ok": ok,
    }
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(artifact, fh, indent=1)
    print(json.dumps({"gates": gates, "ok": ok}), flush=True)
    return 0 if ok else 1


def run_full(out_path: str, *, replicas: int, clients: int,
             duration: float) -> int:
    import tempfile

    host = host_load()  # preflight — before any replica spawns
    translator, texts = build_translator(tiny=True)
    knobs = bench_knobs(tiny=True)
    base = tempfile.mkdtemp(prefix="mlspark_fleet_bench_")

    # Parity rides the scaling fleet below; affinity gets fresh fleets.
    affinity = affinity_phase(
        base, translator, texts, tiny=True, knobs=knobs,
    )
    print(json.dumps({"affinity": {
        k: affinity[k] for k in ("hit_rate_ratio", "ok")
    }}), flush=True)

    scaling = scaling_phase(
        base, translator, texts, tiny=True, knobs=knobs,
        replicas=replicas, clients=clients, duration=duration,
    )
    print(json.dumps({"scaling": {
        k: scaling[k]
        for k in ("fleet_tokens_per_sec", "single_tokens_per_sec",
                  "scaling_ratio", "cores", "gate_enforced", "ok")
    }}), flush=True)

    # Parity on its own small fleet (cheap; reuses one replica).
    workdir = os.path.join(base, "parity")
    gang, router = build_fleet(
        2, workdir, tiny=True, policy="affinity",
        key_fn=make_key_fn(translator), knobs=knobs,
    )
    try:
        parity = parity_gate(router, translator, texts, knobs, n=24)
        conservation = conservation_gate(router)
    finally:
        router.stop()
        gang.stop()
    print(json.dumps({"parity": parity}), flush=True)

    gates = {
        "parity": parity["identical"],
        "conservation": conservation["ok"] and all(
            c["conservation"]["ok"]
            for c in (scaling[f"replicas_{replicas}"],
                      scaling["replicas_1"])
        ),
        "affinity": affinity["ok"],
        "scaling": scaling["ok"],
    }
    ok = all(gates.values())
    artifact = {
        "bench": "fleet",
        "round": 4,
        "smoke": False,
        "host_load": host,
        "contended": host["contended"],
        "replicas": replicas,
        "clients": clients,
        "duration_s": duration,
        "knobs": {k: list(v) if isinstance(v, tuple) else v
                  for k, v in knobs.items()},
        "parity": parity,
        "parity_conservation": conservation,
        "affinity": affinity,
        "scaling": scaling,
        "gates": gates,
        "ok": ok,
    }
    with open(out_path, "w") as fh:
        json.dump(artifact, fh, indent=1)
    print(json.dumps({"wrote": out_path, "gates": gates, "ok": ok}),
          flush=True)
    return 0 if ok else 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 self-test: parity + conservation gates")
    ap.add_argument("--out", default=None,
                    help="artifact path (full run defaults to "
                         "BENCH_SERVE_r04.json; smoke writes one only "
                         "when --out is given)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--duration", type=float, default=6.0,
                    help="seconds per closed-loop load window")
    ns = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # The driver process never decodes; keep its telemetry plane dark
    # unless the caller asked for it.
    os.environ.setdefault("MLSPARK_TELEMETRY_HTTP", "")
    if ns.smoke:
        return run_smoke(ns.out)
    return run_full(
        ns.out or "BENCH_SERVE_r04.json",
        replicas=ns.replicas, clients=ns.clients,
        duration=ns.duration,
    )


if __name__ == "__main__":
    sys.exit(main())

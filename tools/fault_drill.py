"""Fault drill — run the injection scenarios end to end, emit FAULTS_r06.json.

The executable form of docs/FAULT_TOLERANCE.md: each scenario arms a
deterministic fault plan (``utils.faults``), runs the real subsystem
against it, and records what the robustness layer did about it:

- ``gang_crash_resume`` — a 2-process training gang loses rank 1 to an
  injected hard crash (``os._exit``) mid-run; the Distributor must
  detect it (exit path), tear the gang down, retry it whole, and the
  retried run must resume from checkpoints and land on the SAME final
  loss as an unfaulted run.
- ``gang_stall`` — rank 1 goes silent (heartbeats suspended + hang); the
  heartbeat monitor must detect the stall (no exit code ever comes),
  and the structured failure must name the rank and cause.
- ``serving_poison`` — decode batch 0 raises; only its requests may
  fail (``InternalError``), the loop keeps serving, zero recompiles.
- ``fleet_kill_replica`` (round 3) — a 2-replica serving fleet loses
  rank 1 to SIGKILL mid-load; only that replica's in-flight requests may
  be lost (the router's conservation ledger proves no silent loss), the
  surviving replica keeps serving through the outage, the router drains
  around the dead rank, the ``ReplicaGang`` supervisor restarts it, and
  post-recovery traffic reaches it again.
- ``preemption_as_scale_down`` (round 5) — a 3-replica fleet with a
  zero restart budget loses rank 1 permanently under mixed-tier load;
  the ``FleetAutoscaler`` must absorb the death as an observed
  scale-down (corpse reaped, router state purged, decision logged with
  its inputs), exactly the victim's in-flight is lost, the ledger
  conserves, and the interactive tier is never starved.
- ``elastic_shrink`` (round 4) — an 8-rank training gang loses rank 7
  PERMANENTLY (restart budget 0), shrinks to 7 and elastically resumes
  from the group-durable checkpoint via cross-topology resharding
  (``train/reshard.py``), then loses rank 6 of the shrunken gang too and
  shrinks again to 6. The 6-rank survivor must finish the same global
  batch schedule (global batch 168 = lcm(8,7,6) keeps per-step batches
  identical at every world size) within float tolerance of an unfaulted
  run's final loss.

Round 6 adds the **wire** fault family (``utils.faults`` site ``wire``,
applied inside each replica's HTTP handler by deterministic
(rank, request-ordinal) coordinates):

- ``straggler_hedge`` — rank 1 of a 2-replica fleet carries a sticky
  1.5s wire delay on every exchange; with hedging on for the
  interactive tier, every request whose primary lands on the slow rank
  must be saved by a hedged duplicate on the fast rank (first response
  wins, the loser is reaped via ``POST /v1/cancel``). All requests
  complete, the ledger conserves with ``hedged``/``cancelled`` as
  attempt-level side counters, and every returned trace id is distinct
  (exactly-once completion per request id).
- ``torn_response_retry`` — rank 1 tears exactly one response (full
  Content-Length, half a body, hang up). The router must classify the
  short read terminal-``lost`` and NOT silently replay it (the decode
  already happened once — replaying would double-spend it); the
  *client* retries under a fresh request id and completes elsewhere.
  Exactly one ``failed`` in the ledger, zero router-level retries,
  conservation closes, all completed trace ids distinct.

Round 2 additionally asserts the flight recorder: every drilled failure
must leave a non-empty ``flight_<rank>.json`` (dumped by ``maybe_fault``
BEFORE the fault action executes — the failing step's span events ride
along) in the scenario's ``MLSPARK_TELEMETRY_DIR``; the event counts are
recorded in the artifact.

Usage::

    python tools/fault_drill.py [--out FAULTS_r06.json] [scenario ...]
    python tools/fault_drill.py --smoke   # tier-1: the two wire scenarios

Exits nonzero if any scenario's invariant does not hold, so CI can gate
on the drill the way it gates on the test suite.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"),
)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from machine_learning_apache_spark_tpu.utils import faults  # noqa: E402


def _with_plan(plan: str, marker_dir: str, telemetry_dir: str | None = None):
    os.environ[faults.ENV_PLAN] = plan
    os.environ[faults.ENV_MARKER_DIR] = marker_dir
    if telemetry_dir:
        # Persistent flight-dump/rank-export destination: the gang workdir
        # is rmtree'd by the Distributor, so the drill needs its own dir to
        # assert flight files after the run. Workers inherit it (the
        # Distributor's workdir default is a setdefault).
        os.makedirs(telemetry_dir, exist_ok=True)
        os.environ["MLSPARK_TELEMETRY_DIR"] = telemetry_dir
    faults.clear()  # re-arm the lazy env read in THIS process too


def _clear_plan():
    os.environ.pop(faults.ENV_PLAN, None)
    os.environ.pop(faults.ENV_MARKER_DIR, None)
    os.environ.pop("MLSPARK_TELEMETRY_DIR", None)
    faults.clear()


def _flight_info(telemetry_dir: str, rank) -> dict:
    """Summarize one ``flight_<rank>.json`` for the drill artifact: does it
    exist, how many events, does it carry the failing site's spans?"""
    path = os.path.join(telemetry_dir, f"flight_{rank}.json")
    if not os.path.exists(path):
        return {"path": path, "exists": False, "events": 0}
    with open(path) as f:
        dump = json.load(f)
    events = dump.get("events", [])
    return {
        "path": path,
        "exists": True,
        "reason": dump.get("reason"),
        "events": len(events),
        "span_events": sum(
            1 for e in events if e.get("kind") in ("span_start", "span_end")
        ),
    }


def scenario_gang_crash_resume(workdir: str) -> dict:
    import launcher_workers

    from machine_learning_apache_spark_tpu.launcher import Distributor

    t0 = time.monotonic()
    ref = launcher_workers.fault_drill_train(os.path.join(workdir, "ref"))

    plan = "crash@train_step:rank=1,step=9"
    markers = os.path.join(workdir, "markers")
    tdir = os.path.join(workdir, "telemetry")
    _with_plan(plan, markers, telemetry_dir=tdir)
    try:
        out = Distributor(
            num_processes=2, platform="cpu", timeout=300, max_restarts=1,
            backoff_base=0.05, term_grace=2.0,
        ).run(
            "launcher_workers:fault_drill_train", os.path.join(workdir, "gang")
        )
        # Flight recorder: rank 1 dumped its event-log tail in maybe_fault
        # BEFORE os._exit — read it back while the env still points here.
        flight = _flight_info(tdir, 1)
    finally:
        _clear_plan()
    fired = sorted(os.listdir(markers)) if os.path.isdir(markers) else []
    loss_delta = abs(out["final_loss"] - ref["final_loss"])
    return {
        "scenario": "gang_crash_resume",
        "plan": plan,
        "fault_fired": fired,
        "unfaulted_final_loss": ref["final_loss"],
        "drilled_final_loss": out["final_loss"],
        "loss_delta": loss_delta,
        "rank0_resumed_step": out["resumed_step"],
        "flight": flight,
        "wall_seconds": round(time.monotonic() - t0, 2),
        "ok": (
            bool(fired)
            and loss_delta < 1e-6
            and flight["exists"]
            and flight["events"] > 0
        ),
    }


def scenario_gang_stall(workdir: str) -> dict:
    from machine_learning_apache_spark_tpu.launcher import (
        Distributor,
        GangFailure,
    )

    plan = "stall@train_step:rank=1,step=2"
    t0 = time.monotonic()
    tdir = os.path.join(workdir, "telemetry")
    _with_plan(plan, os.path.join(workdir, "markers"), telemetry_dir=tdir)
    failure = None
    try:
        # heartbeat_timeout must comfortably exceed worst-case python
        # spawn-to-first-beat latency: a rank that has not beaten yet is
        # judged against the same timeout from spawn time, and on a busy
        # host (this drill runs right after the crash scenario's gangs) a
        # 4s window can blame a slow-starting innocent rank 0.
        Distributor(
            num_processes=2, platform="cpu", timeout=300,
            heartbeat_interval=0.2, heartbeat_timeout=8.0, term_grace=1.0,
        ).run(
            "launcher_workers:fault_drill_train", os.path.join(workdir, "gang")
        )
    except GangFailure as e:
        failure = e
    finally:
        # Rank 1 dumped flight_1.json before entering the stall loop; the
        # driver's monitor dumped flight_driver.json when it detected the
        # missed heartbeats.
        flight = _flight_info(tdir, 1)
        driver_flight = _flight_info(tdir, "driver")
        _clear_plan()
    return {
        "scenario": "gang_stall",
        "plan": plan,
        "detected": failure is not None,
        "cause": failure.cause if failure else None,
        "rank": failure.rank if failure else None,
        "flight": flight,
        "driver_flight": driver_flight,
        "wall_seconds": round(time.monotonic() - t0, 2),
        "ok": (
            failure is not None
            and failure.cause == "heartbeat"
            and failure.rank == 1
            and flight["exists"]
            and flight["events"] > 0
            and driver_flight["exists"]
            and driver_flight["events"] > 0
        ),
    }


def scenario_serving_poison(workdir: str) -> dict:
    import jax
    import numpy as np

    from machine_learning_apache_spark_tpu.data.datasets import (
        synthetic_translation_pairs,
    )
    from machine_learning_apache_spark_tpu.data.text import TextPipeline
    from machine_learning_apache_spark_tpu.inference import Translator
    from machine_learning_apache_spark_tpu.models import (
        Transformer,
        TransformerConfig,
    )
    from machine_learning_apache_spark_tpu.serving import InternalError

    t0 = time.monotonic()
    pairs = synthetic_translation_pairs(32, min_len=3, max_len=8, seed=0)
    src_pipe = TextPipeline.fit([s for s, _ in pairs], max_seq_len=14)
    trg_pipe = TextPipeline.fit([t for _, t in pairs], max_seq_len=14)
    cfg = TransformerConfig(
        src_vocab_size=len(src_pipe.vocab.itos),
        trg_vocab_size=len(trg_pipe.vocab.itos),
        d_model=32, ffn_hidden=64, num_heads=2, num_layers=1,
        max_len=16, dropout=0.0,
    )
    model = Transformer(cfg)
    dummy = np.ones((2, 8), np.int32)
    params = model.init(jax.random.key(0), dummy, dummy)["params"]
    translator = Translator(model, params, src_pipe, trg_pipe)

    plan = "raise@decode_batch:batch=0"
    # In-process (no gang rank), so the quarantine's flight dump lands in
    # flight_driver.json — point the telemetry dir at this drill's workdir.
    tdir = os.path.join(workdir, "telemetry")
    os.makedirs(tdir, exist_ok=True)
    os.environ["MLSPARK_TELEMETRY_DIR"] = tdir
    faults.install(faults.FaultPlan.from_spec(plan))
    texts = [s for s, _ in pairs][:12]
    try:
        with translator.serve(
            boundaries=(8, 16), max_batch=4, max_new_tokens=8,
        ) as eng:
            futs = [eng.submit(s) for s in texts]
            served = failed = 0
            for f in futs:
                try:
                    f.result(timeout=120)
                    served += 1
                except InternalError:
                    failed += 1
            summary = eng.metrics.summary()
            recompiles = eng.recompiles_after_warmup
            slots_leaked = eng.pool.in_use
    finally:
        faults.clear()
        flight = _flight_info(tdir, "driver")
        os.environ.pop("MLSPARK_TELEMETRY_DIR", None)
    return {
        "scenario": "serving_poison",
        "plan": plan,
        "submitted": len(texts),
        "served": served,
        "poisoned": failed,
        "quarantined": summary["quarantined"],
        "loop_restarts": summary["loop_restarts"],
        "recompiles_after_warmup": recompiles,
        "kv_slots_leaked": slots_leaked,
        "flight": flight,
        "wall_seconds": round(time.monotonic() - t0, 2),
        "ok": (
            0 < failed <= 4
            and served == len(texts) - failed
            and summary["quarantined"] == failed
            and summary["loop_restarts"] == 0
            and recompiles == 0
            and slots_leaked == 0
            and flight["exists"]
            and flight["events"] > 0
        ),
    }


def scenario_fleet_kill_replica(workdir: str) -> dict:
    """Kill one replica of a 2-replica fleet under closed-loop load.

    The invariant chain: (a) only the killed replica's in-flight
    requests are lost — bounded by the client concurrency, zero losses
    on the survivor, and the router ledger conserves every submitted
    request into exactly one terminal counter; (b) the router drains
    around the dead rank (the survivor completes requests during the
    outage, nothing goes fleet-unavailable); (c) the ``ReplicaGang``
    supervisor restarts the rank on a fresh port, the scrape plane
    follows it there, and a post-recovery burst lands traffic on it."""
    import threading

    import fleet_bench

    t0 = time.monotonic()
    clients = 4
    translator, texts = fleet_bench.build_translator(tiny=True)
    knobs = fleet_bench.bench_knobs(tiny=True)
    fleet_dir = os.path.join(workdir, "fleet")
    gang, router = fleet_bench.build_fleet(
        2, fleet_dir, tiny=True, policy="affinity",
        key_fn=fleet_bench.make_key_fn(translator), knobs=knobs,
    )
    try:
        load_result: dict = {}

        def drive() -> None:
            load_result.update(fleet_bench.drive_load(
                router, texts, clients=clients, duration=8.0,
            ))

        loader = threading.Thread(target=drive, daemon=True)
        loader.start()
        time.sleep(2.0)
        before = router.stats()["per_replica"]
        killed = gang.kill_rank(1)
        time.sleep(2.0)
        during = router.stats()["per_replica"]
        loader.join(120.0)

        # The drain story: the survivor completed requests while rank 1
        # was down, and every loss is attributable to rank 1.
        outage_completed = (
            during.get(0, {}).get("completed", 0)
            - before.get(0, {}).get("completed", 0)
        )
        per_replica = router.stats()["per_replica"]
        lost_on_survivor = (
            per_replica.get(0, {}).get("lost", 0)
            + per_replica.get(0, {}).get("failed", 0)
        )
        lost_total = load_result.get("failed", 0)

        # Supervision: rank 1 must come back (fresh port, fresh sidecar)
        # and scrape healthy again.
        recovered = router.wait_for_replicas(2, timeout=180.0)
        pre_burst = router.stats()["per_replica"]
        burst = fleet_bench.drive_load(
            router, texts, clients=clients, duration=3.0,
        )
        post_burst = router.stats()["per_replica"]
        rank1_after_restart = (
            post_burst.get(1, {}).get("completed", 0)
            - pre_burst.get(1, {}).get("completed", 0)
        )
        conservation = fleet_bench.conservation_gate(router)
        ledger = conservation["router_ledger"]
        gang_status = gang.status()
        router_stats = router.stats()
    finally:
        router.stop()
        gang.stop()
    return {
        "scenario": "fleet_kill_replica",
        "clients": clients,
        "kill_acknowledged": killed,
        "load": load_result,
        "outage_completed_on_survivor": outage_completed,
        "lost_total": lost_total,
        "lost_on_survivor": lost_on_survivor,
        "router_retries": router_stats["retries"],
        "recovered_healthy": recovered,
        "recovery_burst": burst,
        "rank1_completed_after_restart": rank1_after_restart,
        "conservation": conservation,
        "gang": gang_status,
        "per_replica": router_stats["per_replica"],
        "wall_seconds": round(time.monotonic() - t0, 2),
        "ok": (
            killed
            and gang_status["restarts"].get(1, 0) >= 1
            and all(gang_status["alive"].values())
            and outage_completed > 0
            and lost_on_survivor == 0
            and lost_total <= clients
            and load_result.get("unavailable", 0) == 0
            and recovered
            and rank1_after_restart > 0
            and conservation["ok"]
            and ledger["in_flight"] == 0
        ),
    }


def scenario_preemption_as_scale_down(workdir: str) -> dict:
    """Permanent replica death absorbed as an *observed scale-down*.

    A 3-replica fleet with a zero restart budget loses rank 1 to SIGKILL
    under mixed interactive+batch load. Nothing restarts it — instead
    the ``FleetAutoscaler`` riding the router's scrape loop must reap
    the corpse (sidecars scrubbed, discovery drops the rank, the router
    purges its penalty-box/affinity state), log an
    ``observed_scale_down`` decision carrying its inputs, and converge
    on the new 2-replica target. Invariant chain: exactly the victim's
    in-flight is lost (zero losses on survivors, total bounded by client
    concurrency), the router ledger conserves every submitted request,
    and the interactive tier is never starved while the fleet absorbs
    the loss (zero fleet-unavailable outcomes, completions keep
    flowing)."""
    import threading

    import fleet_bench

    from machine_learning_apache_spark_tpu.fleet import (
        AutoscaleConfig,
        FleetAutoscaler,
        FleetRouter,
    )
    from machine_learning_apache_spark_tpu.launcher import ReplicaGang

    t0 = time.monotonic()
    clients_per_tier = 3
    translator, texts = fleet_bench.build_translator(tiny=True)
    knobs = fleet_bench.bench_knobs(tiny=True)
    fleet_dir = os.path.join(workdir, "fleet")
    gang = ReplicaGang(
        "fleet_bench:replica_main",
        True,  # tiny
        knobs,
        num_replicas=3,
        workdir=fleet_dir,
        platform="cpu",
        telemetry_http=None,
        max_restarts_per_rank=0,  # first death is permanent — preemption
        env={"MLSPARK_TELEMETRY_HTTP": ""},
    ).start()
    router = FleetRouter(
        fleet_dir, policy="least_loaded", scrape_interval=0.25,
    ).start()
    # Thresholds parked out of reach: the only decision this drill wants
    # is the observed scale-down, not a load-driven resize.
    scaler = FleetAutoscaler(
        gang,
        config=AutoscaleConfig(
            min_replicas=2, max_replicas=3,
            burn_up=10.0, burn_down=0.0,
            queue_up=1000.0, queue_down=0.0,
            hysteresis_ticks=1000, cooldown_s=1.0,
            drain_deadline_s=15.0, drain_batch_shed=0.5,
        ),
        admission=router.admission,
    ).attach(router._scrape)
    try:
        if not router.wait_for_replicas(3, timeout=240.0):
            raise RuntimeError(f"fleet never came healthy: {gang.status()}")
        loads = {"interactive": {}, "batch": {}}

        def drive(tier: str) -> None:
            loads[tier].update(fleet_bench.drive_load(
                router, texts, clients=clients_per_tier, duration=10.0,
                tier=tier,
            ))

        loaders = [
            threading.Thread(target=drive, args=(tier,), daemon=True)
            for tier in loads
        ]
        for t in loaders:
            t.start()
        time.sleep(2.0)
        killed = gang.kill_rank(1)

        # Convergence: supervisor marks the rank exhausted, the scaler
        # reaps it, discovery drops it, and the fleet settles at 2 live.
        deadline = time.monotonic() + 60.0
        converged = False
        while time.monotonic() < deadline:
            snaps = router._snapshot_source()
            if (
                scaler.observed_scale_downs >= 1
                and len(gang.live_ranks()) == 2
                and 1 not in snaps
            ):
                converged = True
                break
            time.sleep(0.25)
        for t in loaders:
            t.join(120.0)
        wait_deadline = time.monotonic() + 60.0
        while (router.ledger()["in_flight"] != 0
               and time.monotonic() < wait_deadline):
            time.sleep(0.2)
        conservation = fleet_bench.conservation_gate(router)
        per_replica = router.stats()["per_replica"]
        decision = next(
            (d for d in scaler.decisions
             if d["action"] == "observed_scale_down"), None
        )
        scaler_stats = scaler.stats()
        gang_status = gang.status()
        router_stats = router.stats()
    finally:
        router.stop()
        gang.stop()
    lost_on_survivors = sum(
        per_replica.get(r, {}).get("lost", 0)
        + per_replica.get(r, {}).get("failed", 0)
        for r in (0, 2)
    )
    lost_total = sum(load.get("failed", 0) for load in loads.values())
    interactive = loads["interactive"]
    decision_has_inputs = decision is not None and all(
        k in decision
        for k in ("action", "burn", "queue_depth", "live", "target")
    )
    return {
        "scenario": "preemption_as_scale_down",
        "clients_per_tier": clients_per_tier,
        "kill_acknowledged": killed,
        "converged_to_new_target": converged,
        "loads": loads,
        "lost_total": lost_total,
        "lost_on_survivors": lost_on_survivors,
        "decision": decision,
        "scaler": scaler_stats,
        "conservation": conservation,
        "per_replica": per_replica,
        "gang": gang_status,
        "router_retries": router_stats["retries"],
        "wall_seconds": round(time.monotonic() - t0, 2),
        "ok": (
            killed
            and converged
            and gang_status["exhausted"] == [1]
            and gang_status["retired"] == [1]
            and scaler_stats["observed_scale_downs"] == 1
            and decision_has_inputs
            and decision["target"] == 2
            # Exactly the victim's in-flight is lost: survivors lose
            # nothing, the total is bounded by client concurrency.
            and lost_on_survivors == 0
            and lost_total <= 2 * clients_per_tier
            # Interactive tier never starved while the loss was absorbed.
            and interactive.get("unavailable", 0) == 0
            and interactive.get("completed", 0) > 0
            and conservation["ok"]
            and conservation["router_ledger"]["in_flight"] == 0
        ),
    }


def scenario_elastic_shrink(workdir: str) -> dict:
    """Shrink-to-fit resume: 8 ranks -> kill 2 permanently -> finish on 6.

    Restart budget 0 makes both crashes permanent rank losses, so the
    Distributor's elastic policy is the only path back: each loss tears
    the gang down and relaunches it one rank smaller, and each smaller
    gang must reshard the previous topology's per-rank checkpoints onto
    its own layout before continuing. The second crash is constrained to
    ``world=7`` so it only arms after the first shrink took effect —
    drilling two sequential reshards (8-rank layout then 7-rank layout)
    rather than two concurrent losses.

    Invariants: both faults fire exactly once (marker files), the final
    gang reports world 6, the resume went through a checkpoint (not a
    fresh start), the final loss is within float tolerance of an
    unfaulted run of the same global batch schedule, and each crashed
    rank left its flight-recorder dump."""
    from machine_learning_apache_spark_tpu.launcher import Distributor

    t0 = time.monotonic()
    # Unfaulted reference at the POST-shrink world size: global batch 168
    # divides every world on the shrink path, so the 6-rank reference runs
    # the exact global batch schedule the drilled gang must reproduce
    # (ZeRO-1 needs a >1 data axis, so the reference is a gang too).
    ref = Distributor(num_processes=6, platform="cpu", timeout=600).run(
        "launcher_workers:elastic_drill_train",
        os.path.join(workdir, "ref"),
        epochs=4, global_batch=168, steps_per_epoch=2,
    )

    plan = (
        "crash@train_step:world=8,rank=7,step=5;"
        "crash@train_step:world=7,rank=6,step=7"
    )
    markers = os.path.join(workdir, "markers")
    tdir = os.path.join(workdir, "telemetry")
    _with_plan(plan, markers, telemetry_dir=tdir)
    try:
        out = Distributor(
            num_processes=8, platform="cpu", timeout=600,
            elastic=True, rank_restart_budget=0, elastic_min_world=6,
            backoff_base=0.05, term_grace=2.0,
        ).run(
            "launcher_workers:elastic_drill_train",
            os.path.join(workdir, "gang"),
            epochs=4, global_batch=168, steps_per_epoch=2,
        )
        flights = {r: _flight_info(tdir, r) for r in (7, 6)}
    finally:
        _clear_plan()
    fired = sorted(os.listdir(markers)) if os.path.isdir(markers) else []
    loss_delta = abs(out["final_loss"] - ref["final_loss"])
    return {
        "scenario": "elastic_shrink",
        "plan": plan,
        "fault_fired": fired,
        "unfaulted_final_loss": ref["final_loss"],
        "drilled_final_loss": out["final_loss"],
        "loss_delta": loss_delta,
        "final_world": out["world"],
        "resumed_step": out["resumed_step"],
        "flights": {str(r): f for r, f in flights.items()},
        "wall_seconds": round(time.monotonic() - t0, 2),
        "ok": (
            len(fired) == 2
            and out["world"] == 6
            and out["resumed_step"] in (2, 4, 6)
            and loss_delta < 1e-3
            and all(
                f["exists"] and f["events"] > 0 for f in flights.values()
            )
        ),
    }


def _wait_replicas_drained(router, timeout: float = 60.0) -> bool:
    """Poll the scrape plane until every replica reports zero in-flight
    — hedge losers may still be decoding on the slow rank after the
    winner's response already returned to the client."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snaps = (
            router._scrape.tick() if router._scrape is not None
            else router._snapshot_source()
        )
        if snaps and all((s.in_flight or 0) == 0 for s in snaps.values()):
            return True
        time.sleep(0.2)
    return False


def scenario_straggler_hedge(workdir: str) -> dict:
    """Hedging rescues a wire-level straggler without double-counting.

    Rank 1 of a 2-replica fleet gets a *sticky* 1.5s wire delay on every
    ``/v1/generate`` exchange (the fault plan rides to the replica
    processes via the gang env; the driver's own plan slot stays empty).
    The router runs round-robin with hedging enabled for the interactive
    tier, so roughly every other request lands its primary on the slow
    rank, outlives the hedge delay (a multiple of the admission EWMA,
    far below 1.5s), gets ONE duplicate on the fast rank, and returns
    the duplicate's response while the loser is reaped via
    ``POST /v1/cancel``. Invariants: every request completes, at least
    one hedge and one cancel were issued, nothing lands in
    failed/expired/unavailable, the ledger conserves with zero
    in-flight, and the returned trace ids are pairwise distinct —
    exactly-once completion per request id even though some requests
    were dispatched twice."""
    import fleet_bench

    t0 = time.monotonic()
    n_requests = 8
    plan = "delay@wire:rank=1,ms=1500,sticky=1"
    translator, texts = fleet_bench.build_translator(tiny=True)
    knobs = fleet_bench.bench_knobs(tiny=True)
    markers = os.path.join(workdir, "markers")
    os.makedirs(markers, exist_ok=True)
    gang, router = fleet_bench.build_fleet(
        2, os.path.join(workdir, "fleet"), tiny=True,
        policy="round_robin", knobs=knobs,
        extra_env={faults.ENV_PLAN: plan, faults.ENV_MARKER_DIR: markers},
        router_kw=dict(
            hedge=True, hedge_tiers=("interactive",),
            hedge_delay_factor=3.0, hedge_min_delay_s=0.05,
        ),
    )
    try:
        payloads = []
        for i in range(n_requests):
            payloads.append(router.submit(
                texts[i % len(texts)], tier="interactive", deadline_s=30.0,
            ))
        drained = _wait_replicas_drained(router)
        conservation = fleet_bench.conservation_gate(router)
        router_stats = router.stats()
    finally:
        router.stop()
        gang.stop()
    fired = sorted(os.listdir(markers)) if os.path.isdir(markers) else []
    ledger = conservation["router_ledger"]
    trace_ids = [p.get("trace_id") for p in payloads]
    winner_ranks = sorted({p.get("rank") for p in payloads})
    return {
        "scenario": "straggler_hedge",
        "plan": plan,
        "fault_fired": fired,
        "requests": n_requests,
        "ledger": ledger,
        "hedged": ledger["hedged"],
        "cancelled": ledger["cancelled"],
        "winner_ranks": winner_ranks,
        "distinct_trace_ids": len(set(trace_ids)),
        "replicas_drained": drained,
        "conservation": conservation,
        "per_replica": router_stats["per_replica"],
        "wall_seconds": round(time.monotonic() - t0, 2),
        "ok": (
            # Sticky fault: marker written once as proof, fault re-fires.
            any(f.startswith("delay_wire") for f in fired)
            and ledger["submitted"] == n_requests
            and ledger["completed"] == n_requests
            and ledger["hedged"] >= 1
            and ledger["cancelled"] >= 1
            and ledger["failed"] == 0
            and ledger["expired"] == 0
            and ledger["unavailable"] == 0
            and drained
            and conservation["ok"]
            and ledger["in_flight"] == 0
            # Exactly-once per request id: one distinct trace per submit.
            and len(set(trace_ids)) == n_requests
            and all(t for t in trace_ids)
        ),
    }


def scenario_torn_response_retry(workdir: str) -> dict:
    """A torn response is terminal-lost; recovery is a NEW request id.

    Rank 1 tears exactly one response (one-shot ``torn`` wire fault on
    its first exchange): full Content-Length, half a body, hang up. The
    replica *did* decode the request — so the router must classify the
    short read ``lost`` and refuse to silently replay it (PR 11's
    lost-is-lost: a replay would double-spend the decode and break
    exactly-once). The client then retries under a fresh request id and
    completes on the surviving rank (the torn rank sits in the penalty
    box until a scrape clears it). Invariants: exactly one ``failed`` in
    the ledger attributed to rank 1, zero router-level retries (the
    failure surfaced, nothing was replayed), every submission lands in
    exactly one terminal bucket, and the completed trace ids are
    pairwise distinct."""
    import fleet_bench

    from machine_learning_apache_spark_tpu.fleet import FleetRequestFailed

    t0 = time.monotonic()
    n_requests = 6
    plan = "torn@wire:rank=1,req=0"
    translator, texts = fleet_bench.build_translator(tiny=True)
    knobs = fleet_bench.bench_knobs(tiny=True)
    markers = os.path.join(workdir, "markers")
    os.makedirs(markers, exist_ok=True)
    gang, router = fleet_bench.build_fleet(
        2, os.path.join(workdir, "fleet"), tiny=True,
        policy="round_robin", knobs=knobs,
        extra_env={faults.ENV_PLAN: plan, faults.ENV_MARKER_DIR: markers},
    )
    try:
        payloads = []
        failures = []
        for i in range(n_requests):
            text = texts[i % len(texts)]
            try:
                payloads.append(router.submit(
                    text, tier="interactive", deadline_s=30.0,
                ))
            except FleetRequestFailed as e:
                # The client-side discipline the taxonomy demands: a lost
                # request is dead; recovery is a fresh submission (new
                # request id), never a replay of the old one.
                failures.append({"rank": e.rank, "status": e.status,
                                 "error": str(e)})
                payloads.append(router.submit(
                    text, tier="interactive", deadline_s=30.0,
                ))
        drained = _wait_replicas_drained(router)
        conservation = fleet_bench.conservation_gate(router)
        router_stats = router.stats()
    finally:
        router.stop()
        gang.stop()
    fired = sorted(os.listdir(markers)) if os.path.isdir(markers) else []
    ledger = conservation["router_ledger"]
    trace_ids = [p.get("trace_id") for p in payloads]
    return {
        "scenario": "torn_response_retry",
        "plan": plan,
        "fault_fired": fired,
        "requests": n_requests,
        "client_retries": len(failures),
        "failures": failures,
        "ledger": ledger,
        "router_retries": router_stats["retries"],
        "distinct_trace_ids": len(set(trace_ids)),
        "replicas_drained": drained,
        "conservation": conservation,
        "per_replica": router_stats["per_replica"],
        "wall_seconds": round(time.monotonic() - t0, 2),
        "ok": (
            # One-shot fault: fired exactly once, consumed thereafter.
            sum(1 for f in fired if f.startswith("torn_wire")) == 1
            and len(failures) == 1
            and failures[0]["rank"] == 1
            # One failed (the torn exchange), everything else completed,
            # and the extra submission is the client's retry — so the
            # ledger carries n+1 submitted, n completed, 1 failed.
            and ledger["submitted"] == n_requests + 1
            and ledger["completed"] == n_requests
            and ledger["failed"] == 1
            and ledger["expired"] == 0
            and ledger["unavailable"] == 0
            # No silent replay: the router never retried the torn
            # request (retries counts drain-around continuations).
            and router_stats["retries"] == 0
            and ledger["hedged"] == 0
            and drained
            and conservation["ok"]
            and ledger["in_flight"] == 0
            and len(set(trace_ids)) == n_requests
            and all(t for t in trace_ids)
        ),
    }


#: The wire-family scenarios double as the tier-1 ``--smoke`` entry:
#: fast enough for CI, and they exercise the hedge + cancel + wire-fault
#: stack end to end over real sockets.
SMOKE_SCENARIOS = ("straggler_hedge", "torn_response_retry")

SCENARIOS = {
    "elastic_shrink": scenario_elastic_shrink,
    "gang_crash_resume": scenario_gang_crash_resume,
    "gang_stall": scenario_gang_stall,
    "serving_poison": scenario_serving_poison,
    "fleet_kill_replica": scenario_fleet_kill_replica,
    "preemption_as_scale_down": scenario_preemption_as_scale_down,
    "straggler_hedge": scenario_straggler_hedge,
    "torn_response_retry": scenario_torn_response_retry,
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument(
        "--out", default=None,
        help="artifact path (full run defaults to FAULTS_r06.json; "
             "--smoke writes one only when --out is given)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help=f"tier-1 self-test: just the wire scenarios {SMOKE_SCENARIOS}",
    )
    ap.add_argument(
        "scenarios", nargs="*", default=None,
        help=f"subset to run (default: all of {sorted(SCENARIOS)})",
    )
    ns = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ns.smoke and ns.scenarios:
        ap.error("--smoke picks its own scenarios; drop the positional args")
    names = (
        list(SMOKE_SCENARIOS) if ns.smoke
        else (ns.scenarios or sorted(SCENARIOS))
    )
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        ap.error(f"unknown scenario(s) {unknown}; pick from {sorted(SCENARIOS)}")

    results = []
    for name in names:
        print(f"== drill: {name}", flush=True)
        with tempfile.TemporaryDirectory(prefix=f"fault_drill_{name}_") as wd:
            results.append(SCENARIOS[name](wd))
        print(json.dumps(results[-1], indent=2), flush=True)

    report = {
        "artifact": "FAULTS",
        "round": 6,
        "smoke": ns.smoke,
        "all_ok": all(r["ok"] for r in results),
        "scenarios": results,
    }
    out = ns.out if ns.smoke else (ns.out or "FAULTS_r06.json")
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"wrote {out} (all_ok={report['all_ok']})")
    else:
        print(json.dumps(
            {"smoke": True, "all_ok": report["all_ok"]}
        ), flush=True)
    return 0 if report["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

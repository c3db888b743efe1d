"""Fault-injection harness + the drills it powers (docs/FAULT_TOLERANCE.md).

Three layers under test, each against an *actual* injected fault rather
than a mocked condition:

- ``utils.faults`` itself — plan grammar, coordinate matching, one-shot
  semantics in-process and across process restarts (marker files);
- the gang drill — a 2-process training gang loses rank 1 to an injected
  crash mid-run, the Distributor retries the gang whole, every rank
  resumes from its last complete checkpoint, and the final loss matches
  an unfaulted run (the tentpole's acceptance bar); plus the stall
  variant the heartbeat monitor must catch;
- the serving drill — a poisoned decode batch fails only its own
  requests (``InternalError``), the loop keeps serving with zero
  recompiles, and the quarantine/restart counters account for it.
"""

import numpy as np
import pytest

from machine_learning_apache_spark_tpu.utils import faults
from machine_learning_apache_spark_tpu.utils.faults import (
    FaultInjected,
    FaultPlan,
    FaultSpec,
)

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _isolated_plan():
    """No plan leaks between tests (clear() also re-arms the lazy env
    read, so env-driven tests see their monkeypatched MLSPARK_FAULTS)."""
    faults.clear()
    yield
    faults.clear()


class TestFaultPlanParsing:
    def test_grammar(self):
        plan = FaultPlan.from_spec(
            "crash@train_step:rank=1,step=5;raise@decode_batch:batch=2;"
            "stall@train_step:rank=0,exit_code=7"
        )
        assert [s.action for s in plan.specs] == ["crash", "raise", "stall"]
        assert plan.specs[0] == FaultSpec("crash", "train_step", rank=1, step=5)
        assert plan.specs[1].batch == 2 and plan.specs[1].rank is None
        assert plan.specs[2].exit_code == 7

    def test_unknown_action_raises(self):
        with pytest.raises(ValueError, match="unknown fault action"):
            FaultPlan.from_spec("explode@train_step:rank=0")

    def test_unknown_field_raises(self):
        with pytest.raises(ValueError, match="unknown fault field"):
            FaultPlan.from_spec("crash@train_step:epoch=3")

    def test_missing_site_raises(self):
        with pytest.raises(ValueError, match="no site"):
            FaultPlan.from_spec("crash@:rank=0")

    def test_from_env(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_PLAN, "raise@decode_batch:batch=1")
        monkeypatch.delenv(faults.ENV_MARKER_DIR, raising=False)
        plan = FaultPlan.from_env()
        assert plan is not None and plan.specs[0].action == "raise"
        monkeypatch.delenv(faults.ENV_PLAN)
        assert FaultPlan.from_env() is None


class TestOneShotSemantics:
    def test_fires_once_in_process(self):
        faults.install(FaultPlan.from_spec("raise@s:step=1"))
        faults.maybe_fault("s", step=0)  # wrong coordinate: no fire
        with pytest.raises(FaultInjected):
            faults.maybe_fault("s", step=1)
        faults.maybe_fault("s", step=1)  # already fired: no second fire

    def test_marker_survives_plan_reload(self, tmp_path):
        """The gang-restart story: a retried worker builds a FRESH plan
        from the same env, and the marker file must stop the re-fire."""
        spec = "raise@s:step=1"
        faults.install(FaultPlan.from_spec(spec, marker_dir=str(tmp_path)))
        with pytest.raises(FaultInjected):
            faults.maybe_fault("s", step=1)
        assert list(tmp_path.iterdir()), "marker was not written"
        faults.install(FaultPlan.from_spec(spec, marker_dir=str(tmp_path)))
        faults.maybe_fault("s", step=1)  # marker on disk: no re-fire

    def test_wildcard_coordinates(self):
        faults.install(FaultPlan.from_spec("raise@s"))
        with pytest.raises(FaultInjected):
            faults.maybe_fault("s", step=42, batch=7)

    def test_rank_scoping(self, monkeypatch):
        monkeypatch.setenv("MLSPARK_PROCESS_ID", "0")
        faults.install(FaultPlan.from_spec("raise@s:rank=1"))
        faults.maybe_fault("s")  # this "rank 0" process is not targeted
        faults.install(FaultPlan.from_spec("raise@s:rank=0"))
        with pytest.raises(FaultInjected):
            faults.maybe_fault("s")

    def test_world_grammar_and_key(self):
        """The elastic-shrink plan grammar: ``world=`` scopes a fault to
        one gang size, so a plan like ``...world=8...;...world=7...``
        kills exactly one rank per topology along the shrink path."""
        plan = FaultPlan.from_spec(
            "crash@train_step:world=8,rank=7,step=5;"
            "crash@train_step:world=7,rank=6,step=7"
        )
        s8, s7 = plan.specs
        assert (s8.world, s8.rank, s8.step) == (8, 7, 5)
        assert s8.key.endswith("_w8") and s7.key.endswith("_w7")
        assert s8.key != s7.key  # distinct one-shot markers per topology
        unscoped = FaultPlan.from_spec("crash@train_step:rank=1").specs[0]
        assert unscoped.world is None and "_w" not in unscoped.key

    def test_world_scoping(self, monkeypatch):
        """A world-scoped fault fires only in a gang of that size: the
        8-rank fault stays dormant after the shrink to 7 even though the
        rank/step coordinates line up again."""
        monkeypatch.setenv("MLSPARK_PROCESS_ID", "7")
        monkeypatch.setenv("MLSPARK_NUM_PROCESSES", "8")
        faults.install(FaultPlan.from_spec("raise@s:world=7,rank=7"))
        faults.maybe_fault("s")  # world 8 != 7: no fire
        monkeypatch.setenv("MLSPARK_NUM_PROCESSES", "7")
        faults.install(FaultPlan.from_spec("raise@s:world=7,rank=7"))
        with pytest.raises(FaultInjected):
            faults.maybe_fault("s")

    def test_shrink_path_plan_matches_one_fault_per_world(self):
        plan = FaultPlan.from_spec(
            "crash@t:world=8,rank=7,step=5;crash@t:world=7,rank=6,step=7"
        )
        s8, s7 = plan.specs
        assert s8.matches("t", 7, 5, None, 8) and not s8.matches("t", 7, 5, None, 7)
        assert s7.matches("t", 6, 7, None, 7) and not s7.matches("t", 6, 7, None, 8)
        # Unscoped specs keep matching any world (legacy plans unchanged).
        legacy = FaultPlan.from_spec("crash@t:rank=1").specs[0]
        assert legacy.matches("t", 1, None, None, 6)

    def test_env_plan_loads_lazily(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_PLAN, "raise@lazy_site")
        with pytest.raises(FaultInjected):
            faults.maybe_fault("lazy_site")

    def test_no_plan_is_noop(self):
        faults.maybe_fault("anything", step=1, batch=2)  # must not raise


class TestGangFaultDrill:
    def test_crash_retry_resumes_and_matches_unfaulted(
        self, tmp_path, monkeypatch
    ):
        """THE fault drill (ISSUE acceptance): kill rank 1 with an injected
        hard crash (os._exit) mid-training, assert the gang retries, every
        rank auto-resumes from its last complete checkpoint, and the final
        loss matches an unfaulted run."""
        import launcher_workers

        from machine_learning_apache_spark_tpu.launcher import Distributor

        # Unfaulted reference: the identical workload, run inline (no env
        # plan is set yet, and the crash spec targets rank 1 anyway).
        ref = launcher_workers.fault_drill_train(str(tmp_path / "ref"))
        assert ref["resumed_step"] is None

        # Step 9 is inside epoch 2 (4 steps/epoch), so checkpoints for
        # epochs 0-1 exist when the crash lands.
        markers = tmp_path / "markers"
        monkeypatch.setenv(faults.ENV_PLAN, "crash@train_step:rank=1,step=9")
        monkeypatch.setenv(faults.ENV_MARKER_DIR, str(markers))
        out = Distributor(
            num_processes=2, platform="cpu", timeout=300, max_restarts=1,
            backoff_base=0.05, term_grace=2.0,
        ).run("launcher_workers:fault_drill_train", str(tmp_path / "gang"))
        assert out["rank"] == 0
        # The crash genuinely fired (its one-shot marker landed)...
        assert list(markers.iterdir()), "crash fault never fired"
        # ...and the retried gang converged to the unfaulted trajectory.
        np.testing.assert_allclose(
            out["final_loss"], ref["final_loss"], rtol=1e-6
        )

    def test_stall_detected_by_heartbeat_monitor(self, tmp_path, monkeypatch):
        """A stalled (hung-not-dead) rank produces no exit code — only the
        missed-heartbeat detector can catch it, and must, with the rank
        and cause in the structured failure."""
        from machine_learning_apache_spark_tpu.launcher import (
            Distributor,
            GangFailure,
        )

        monkeypatch.setenv(faults.ENV_PLAN, "stall@train_step:rank=1,step=2")
        monkeypatch.setenv(faults.ENV_MARKER_DIR, str(tmp_path / "markers"))
        with pytest.raises(GangFailure) as ei:
            Distributor(
                num_processes=2, platform="cpu", timeout=300,
                heartbeat_interval=0.2, heartbeat_timeout=4.0,
                term_grace=1.0,
            ).run(
                "launcher_workers:fault_drill_train", str(tmp_path / "gang")
            )
        assert ei.value.cause == "heartbeat"
        assert ei.value.rank == 1


@pytest.fixture(scope="module")
def tiny_translator(make_tiny_translator):
    """Untrained tiny MT bundle over 32 sentence pairs."""
    return make_tiny_translator(32)


class TestServingPoisonedBatch:
    def test_poisoned_batch_contained(self, tiny_translator):
        """A raised decode batch fails ONLY its own requests (as
        ``InternalError`` with the injected fault as cause), the loop
        keeps serving everything else, recovery triggers zero recompiles,
        and the quarantine ledger accounts for exactly the poisoned
        requests."""
        from machine_learning_apache_spark_tpu.serving import InternalError

        t, texts = tiny_translator
        texts = texts[:12]
        faults.install(FaultPlan.from_spec("raise@decode_batch:batch=0"))
        with t.serve(
            boundaries=(8, 16), max_batch=4, max_new_tokens=8,
        ) as eng:
            futs = [eng.submit(s) for s in texts]
            served, failures = [], []
            for f in futs:
                try:
                    served.append(f.result(timeout=120))
                except InternalError as e:
                    failures.append(e)
            assert failures, "poisoned batch produced no failures"
            assert len(failures) <= 4  # at most one batch's worth
            assert len(served) == len(texts) - len(failures)
            assert eng.metrics.quarantined == len(failures)
            assert eng.metrics.failed == len(failures)
            assert eng.metrics.loop_restarts == 0  # inner ring contained it
            assert eng.recompiles_after_warmup == 0
            assert eng.pool.in_use == 0  # quarantine freed the KV slots
        assert all(
            isinstance(e.__cause__, FaultInjected) for e in failures
        ), "InternalError must carry the injected fault as its cause"

    def test_decode_loop_death_restarts_supervisor(self, tiny_translator):
        """The outer containment ring: if the decode loop itself dies
        (not just one batch), the supervisor restarts it and the engine
        keeps serving — counted in ``loop_restarts``."""
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
            out = eng.submit(texts[0]).result(timeout=120)
            assert isinstance(out, str)  # still serving after the death
            assert eng.metrics.loop_restarts == 1
            assert eng.recompiles_after_warmup == 0
        finally:
            eng.stop()


class TestWireFaults:
    """The ``wire`` site family (fleet data-plane injection): grammar,
    exchange-coordinate matching, and sticky-vs-one-shot semantics —
    the unit layer under ``fault_drill.py``'s socket-level scenarios."""

    def test_wire_grammar_and_key(self):
        plan = FaultPlan.from_spec(
            "delay@wire:rank=1,ms=800,sticky=1;torn@wire:rank=0,req=2"
        )
        d, t = plan.specs
        assert d.action == "delay" and d.site == "wire"
        assert d.ms == 800 and d.sticky == 1
        assert d.key == "delay_wire_r1_sany_bany_m800"
        assert t.req == 2 and not t.sticky
        assert t.key == "torn_wire_r0_sany_bany_q2"

    def test_wire_actions_pair_only_with_wire_site(self):
        with pytest.raises(ValueError, match="wire"):
            FaultPlan.from_spec("torn@train_step:rank=0")
        with pytest.raises(ValueError, match="wire"):
            FaultPlan.from_spec("crash@wire:rank=0")

    def test_wire_fault_matches_exchange_coordinates(self):
        faults.install(FaultPlan.from_spec("torn@wire:rank=1,req=2"))
        assert faults.wire_fault(rank=0, req=2) is None
        assert faults.wire_fault(rank=1, req=1) is None
        spec = faults.wire_fault(rank=1, req=2)
        assert spec is not None and spec.action == "torn"
        # one-shot: the exchange that matched consumed it
        assert faults.wire_fault(rank=1, req=2) is None

    def test_sticky_wire_fault_fires_every_exchange_marker_once(
        self, tmp_path
    ):
        # A sticky delay (the straggler impersonation) engages on EVERY
        # exchange, but the drill's proof-of-engagement marker is still
        # written exactly once.
        faults.install(FaultPlan.from_spec(
            "delay@wire:rank=1,ms=5,sticky=1", marker_dir=str(tmp_path),
        ))
        for q in range(3):
            spec = faults.wire_fault(rank=1, req=q)
            assert spec is not None and spec.ms == 5
        assert [p.name for p in tmp_path.iterdir()] == [
            "delay_wire_r1_sany_bany_m5"
        ]

    def test_wire_fault_no_plan_is_noop(self):
        assert faults.wire_fault(rank=0, req=0) is None


def test_fault_drill_wire_smoke_subprocess(tmp_path):
    """tools/fault_drill.py --smoke: the two wire-level scenarios end to
    end over real sockets — a sticky-delayed replica rescued by hedging
    (losers reaped via /v1/cancel) and a torn 200 surfacing as a
    terminal failure with no silent replay — each gated on ledger
    conservation and exactly-once completion per request id."""
    import json
    import os
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "fault_smoke.json"
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(repo_root, "tools", "fault_drill.py"),
            "--smoke", "--out", str(out),
        ],
        capture_output=True, text=True, timeout=560,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    artifact = json.loads(out.read_text())
    assert artifact["all_ok"] is True and artifact["smoke"] is True
    by_name = {s["scenario"]: s for s in artifact["scenarios"]}
    assert set(by_name) == {"straggler_hedge", "torn_response_retry"}
    hedge = by_name["straggler_hedge"]
    assert hedge["ok"] is True
    assert hedge["ledger"]["hedged"] >= 1
    assert hedge["ledger"]["cancelled"] >= 1
    torn = by_name["torn_response_retry"]
    assert torn["ok"] is True
    assert torn["ledger"]["failed"] == 1 and torn["router_retries"] == 0

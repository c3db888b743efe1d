"""Launcher tests (SURVEY.md §4): spawn N local processes, verify rendezvous
env plumb-through, rank/world assignment, rank-0 result return, and gang
failure propagation."""

import pytest

from machine_learning_apache_spark_tpu.launcher import Distributor, fn_reference
from machine_learning_apache_spark_tpu.launcher.coordinator import RendezvousSpec


class TestFnReference:
    def test_module_function(self):
        from launcher_workers import echo_rank

        assert fn_reference(echo_rank) == "launcher_workers:echo_rank"

    def test_lambda_rejected(self):
        with pytest.raises(ValueError):
            fn_reference(lambda: None)

    def test_string_passthrough(self):
        assert fn_reference("a.b:c") == "a.b:c"
        with pytest.raises(ValueError):
            fn_reference("no_colon")


class TestRendezvousSpec:
    def test_torch_style_env(self, monkeypatch):
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
        monkeypatch.setenv("MASTER_PORT", "1234")
        monkeypatch.setenv("WORLD_SIZE", "4")
        monkeypatch.setenv("RANK", "2")
        spec = RendezvousSpec.from_env()
        assert spec.coordinator_address == "10.0.0.1:1234"
        assert spec.num_processes == 4 and spec.process_id == 2

    def test_single_process_is_none(self, monkeypatch):
        for var in ("MASTER_ADDR", "MLSPARK_COORDINATOR", "WORLD_SIZE"):
            monkeypatch.delenv(var, raising=False)
        assert RendezvousSpec.from_env() is None

    def test_apply_env_roundtrip(self):
        spec = RendezvousSpec("h:29500", 8, 3)
        env = spec.apply_env({})
        assert env["MASTER_ADDR"] == "h" and env["RANK"] == "3"
        assert env["MLSPARK_NUM_PROCESSES"] == "8"


class TestDistributorLocal:
    def test_single_process_inline(self):
        from launcher_workers import echo_rank

        out = Distributor(num_processes=1).run(echo_rank, tag="inline")
        assert out["tag"] == "inline"

    def test_gang_rank0_result(self):
        # 2-process gang: rank 0's dict comes back with correct rank/world env.
        out = Distributor(num_processes=2, platform="cpu", timeout=120).run(
            "launcher_workers:echo_rank", tag="gang"
        )
        assert out == {"rank": 0, "world": 2, "master": "127.0.0.1", "tag": "gang"}

    def test_gang_dp_mode_env_plumbing(self):
        # Distributor(dp_mode="zero1") sets MLSPARK_DP_MODE for every rank
        # — the env contract fit() resolves via parallel.zero.
        out = Distributor(
            num_processes=2, platform="cpu", timeout=120, dp_mode="zero1"
        ).run("launcher_workers:echo_dp_mode")
        assert out == {"dp_mode": "zero1", "rank": 0}

    def test_dp_mode_typo_rejected_at_construction(self):
        with pytest.raises(ValueError, match="dp_mode"):
            Distributor(num_processes=2, dp_mode="zero2")

    def test_distributor_has_no_serve_kv_mode(self, monkeypatch):
        # Serving has one KV discipline: the keyword that chose between
        # two is gone, and the serving contract a worker sees holds the
        # store's dtype only.
        with pytest.raises(TypeError, match="serve_kv_mode"):
            Distributor(num_processes=1, serve_kv_mode="paged")
        monkeypatch.delenv("MLSPARK_SERVE_KV_DTYPE", raising=False)
        out = Distributor(
            num_processes=1, platform="cpu", timeout=120,
            serve_kv_dtype="int8",
        ).run("launcher_workers:echo_serve_env")
        assert out == {"MLSPARK_SERVE_KV_DTYPE": "int8"}

    def test_gang_failure_raises(self):
        with pytest.raises(RuntimeError, match="worker exploded"):
            Distributor(num_processes=2, platform="cpu", timeout=120).run(
                "launcher_workers:boom"
            )

    def test_gang_restart_recovers(self, tmp_path):
        """max_restarts re-runs the whole gang (Spark-barrier all-or-nothing
        recovery, SURVEY.md §5): first attempt fails, second succeeds."""
        out = Distributor(
            num_processes=2, platform="cpu", timeout=240, max_restarts=1
        ).run("launcher_workers:flaky_until", str(tmp_path / "marker"))
        assert out == {"attempt": "recovered"}

    def test_gang_restart_exhausted_raises(self):
        with pytest.raises(RuntimeError, match="worker exploded"):
            Distributor(
                num_processes=2, platform="cpu", timeout=240, max_restarts=1
            ).run("launcher_workers:boom")

    def test_unpicklable_result_reports_rank_failure(self):
        # A worker whose return value can't be pickled must surface as a gang
        # failure naming the rank — not escape as a raw EOFError/unpickling
        # artifact from a truncated result file.
        with pytest.raises(RuntimeError, match="gang failed"):
            Distributor(num_processes=2, platform="cpu", timeout=120).run(
                "launcher_workers:unpicklable_result"
            )

    def test_single_process_with_platform_spawns(self):
        # n=1 + platform override must not run inline (this interpreter's
        # backend is already initialized) — it spawns and applies the env.
        out = Distributor(num_processes=1, platform="cpu", timeout=120).run(
            "launcher_workers:echo_rank", tag="spawned"
        )
        assert out["tag"] == "spawned" and out["rank"] == 0

    @pytest.mark.slow
    def test_gang_jax_distributed_collective(self):
        # Full rendezvous: 2 CPU processes jax.distributed.initialize and
        # allgather — the gloo-collective parity check (SURVEY.md §2.4).
        out = Distributor(num_processes=2, platform="cpu", timeout=240).run(
            "launcher_workers:cross_process_sum"
        )
        assert out == {"rank": 0, "world": 2, "sum": 3.0}

    @pytest.mark.slow
    def test_gang_dp_train_step_parity(self):
        """A REAL cross-process psum train step (VERDICT round-2 item 6): a
        2-process gang builds a 2-device mesh, each rank feeds its shard,
        grads sync through the compiled collective, replicas stay bit-level
        in sync, and the loss trajectory + final params equal the
        single-process full-batch run
        (``distributed_multilayer_perceptron.py:177-181`` parity)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        out = Distributor(num_processes=2, platform="cpu", timeout=240).run(
            "launcher_workers:dp_train_step_parity"
        )
        assert out["world"] == 2
        assert out["divergence"] == 0.0

        # Single-process reference: same init, same data, full batch.
        from machine_learning_apache_spark_tpu.models import MLP
        from machine_learning_apache_spark_tpu.parallel.data_parallel import (
            params_fingerprint,
        )
        from machine_learning_apache_spark_tpu.train.losses import cross_entropy
        from machine_learning_apache_spark_tpu.train.state import (
            TrainState,
            make_optimizer,
        )

        rng = np.random.default_rng(0)
        feats = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
        labels = jnp.asarray(rng.integers(0, 3, 16).astype(np.int64))
        model = MLP(layers=(4, 5, 3))
        params = model.init(jax.random.key(0), jnp.ones((1, 4)))["params"]
        state = TrainState.create(
            apply_fn=model.apply, params=params, tx=make_optimizer("sgd", 0.1)
        )

        @jax.jit
        def step(state):
            def loss_fn(p):
                return cross_entropy(model.apply({"params": p}, feats), labels)

            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            return state.apply_gradients(grads), loss

        expected_losses = []
        for _ in range(3):
            state, loss = step(state)
            expected_losses.append(float(loss))
        np.testing.assert_allclose(out["losses"], expected_losses, rtol=1e-5)
        np.testing.assert_allclose(
            out["fingerprint"], params_fingerprint(state.params), rtol=1e-5
        )


class TestFailureDetection:
    """The monitor/teardown layer's contract: every way a gang dies maps
    to a structured GangFailure (rank, cause, attempt), never a hang."""

    def test_nonzero_exit_structured_failure(self):
        from machine_learning_apache_spark_tpu.launcher import GangFailure

        with pytest.raises(GangFailure) as ei:
            Distributor(num_processes=2, platform="cpu", timeout=120).run(
                "launcher_workers:boom"
            )
        assert ei.value.cause == "exit"
        assert ei.value.attempt == 0
        assert ei.value.rank in (0, 1)
        assert "worker exploded" in str(ei.value)  # real traceback attached

    def test_gang_deadline_expiry(self):
        """Workers that never finish (but never die, and keep
        heartbeating) must be ended by the gang deadline — cause
        'deadline', no rank to blame."""
        from machine_learning_apache_spark_tpu.launcher import GangFailure

        with pytest.raises(GangFailure) as ei:
            Distributor(
                num_processes=2, platform="cpu", timeout=10, term_grace=1.0
            ).run("launcher_workers:sleep_forever")
        assert ei.value.cause == "deadline"
        assert ei.value.rank is None

    def test_restart_exhaustion_keeps_structured_fields(self):
        from machine_learning_apache_spark_tpu.launcher import GangFailure

        with pytest.raises(GangFailure) as ei:
            Distributor(
                num_processes=2, platform="cpu", timeout=120,
                max_restarts=1, backoff_base=0.05,
            ).run("launcher_workers:boom")
        assert ei.value.attempt == 1  # the exhausting (last) attempt

    def test_read_result_missing_file(self, tmp_path):
        r = Distributor._read_result(str(tmp_path / "absent.pkl"), rank=3)
        assert r.rank == 3
        assert "produced no result" in r.error

    def test_read_result_corrupt_file(self, tmp_path):
        p = tmp_path / "result_0.pkl"
        p.write_bytes(b"\x80\x04garbage")
        r = Distributor._read_result(str(p), rank=0)
        assert r.rank == 0
        assert "produced no result" in r.error  # unreadable == no result


class TestCommandsForHosts:
    def test_command_lines(self):
        cmds = Distributor(local_mode=False).commands_for_hosts(
            "launcher_workers:echo_rank", ["tpu-host-0", "tpu-host-1"]
        )
        assert len(cmds) == 2
        assert "--coordinator tpu-host-0:29500" in cmds[0]
        assert "--process-id 1" in cmds[1]

    def test_cluster_run_raises(self):
        with pytest.raises(RuntimeError, match="commands_for_hosts"):
            Distributor(local_mode=False).run("launcher_workers:echo_rank")

    @pytest.mark.slow
    def test_commands_execute_end_to_end(self):
        """The multi-host control plane, end to end: execute the LITERAL
        command strings from ``commands_for_hosts`` (2 "hosts" on loopback —
        the role spark-submit plays for ``distributed_cnn.py:227-231``),
        and assert both ranks rendezvous over the coordinator and agree on
        a cross-process collective sum. The scheduler's own contribution is
        environment only (PYTHONPATH + platform), never edited commands."""
        import os
        import shlex
        import subprocess
        import sys

        from machine_learning_apache_spark_tpu.launcher.distributor import (
            _free_port,
        )

        port = _free_port()
        cmds = Distributor(local_mode=False).commands_for_hosts(
            "launcher_workers:multihost_probe",
            ["127.0.0.1", "127.0.0.1"],
            coordinator_port=port,
        )
        env = {
            **os.environ,
            # Both forms, like Distributor._run_gang: the env var for vanilla
            # images, MLSPARK_PLATFORM for the runner's config-API override.
            "JAX_PLATFORMS": "cpu",
            "MLSPARK_PLATFORM": "cpu",
            "PYTHONPATH": os.pathsep.join(p for p in sys.path if p),
        }
        procs = [
            subprocess.Popen(
                shlex.split(c),
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
            for c in cmds
        ]
        outs = []
        try:
            for p in procs:
                outs.append(p.communicate(timeout=300))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        for rank, (p, (out, err)) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {rank} failed:\n{err[-2000:]}"
            assert f"MULTIHOST_RESULT rank={rank} world=2 sum=3.0" in out, out



class TestObservabilityContracts:
    """The launcher half of the live plane: JSON heartbeat payloads, the
    ``telemetry_http`` knob, and the gang_status scraper end to end."""

    def test_heartbeat_payload_json_round_trip(self, tmp_path):
        import json
        import time

        from machine_learning_apache_spark_tpu.launcher.monitor import (
            read_heartbeat,
        )
        from machine_learning_apache_spark_tpu.launcher.runner import (
            _start_heartbeat,
        )
        from machine_learning_apache_spark_tpu.telemetry import events

        events.beacon_update(phase="train", step=7, http_port=9100)
        try:
            hb = tmp_path / "heartbeat_3"
            _start_heartbeat(str(hb), interval=0.05, rank=3)
            deadline = time.monotonic() + 10
            payload = {}
            while time.monotonic() < deadline:
                payload = read_heartbeat(str(hb))
                if payload.get("phase") == "train":
                    break
                time.sleep(0.02)
            assert payload["rank"] == 3
            assert payload["pid"] > 0 and "wall" in payload
            assert payload["phase"] == "train" and payload["step"] == 7
            assert payload["http_port"] == 9100
            # the beat is a valid single JSON document (atomic replace,
            # never a torn append)
            assert json.loads(hb.read_text()) == payload
        finally:
            events.reset()

    def test_read_heartbeat_tolerates_legacy_and_torn_files(self, tmp_path):
        from machine_learning_apache_spark_tpu.launcher.monitor import (
            read_heartbeat,
        )

        legacy = tmp_path / "heartbeat_0"
        legacy.touch()  # pre-JSON empty-touch beat
        assert read_heartbeat(str(legacy)) == {}
        torn = tmp_path / "heartbeat_1"
        torn.write_text('{"rank": 1, "phase"')
        assert read_heartbeat(str(torn)) == {}
        assert read_heartbeat(str(tmp_path / "absent")) == {}
        notdict = tmp_path / "heartbeat_2"
        notdict.write_text("[1, 2]")
        assert read_heartbeat(str(notdict)) == {}

    def test_telemetry_http_knob_validation(self):
        with pytest.raises(ValueError, match="telemetry_http"):
            Distributor(num_processes=2, telemetry_http=-1)
        with pytest.raises(ValueError, match="telemetry_http"):
            Distributor(num_processes=2, telemetry_http=70000)

    def test_telemetry_http_env_plumbing(self):
        out = Distributor(
            num_processes=2, platform="cpu", timeout=120, telemetry_http=0
        ).run("launcher_workers:echo_telemetry_http")
        assert out == {"telemetry_http": "0", "rank": 0}

    def test_explicit_env_wins_over_knob(self):
        # one spawned rank: a fixed port must not collide across ranks
        from machine_learning_apache_spark_tpu.launcher.distributor import (
            _free_port,
        )

        port = _free_port()
        out = Distributor(
            num_processes=1, platform="cpu", timeout=120, telemetry_http=0,
            env={"MLSPARK_TELEMETRY_HTTP": str(port)},
        ).run("launcher_workers:echo_telemetry_http")
        assert out["telemetry_http"] == str(port)

    def test_gang_status_smoke_subprocess(self):
        """tools/gang_status.py --smoke is the tier-1 CI entry for the
        scrape plane: a 2-rank gang with ephemeral HTTP ports, both ranks
        discovered via sidecars and scraped live."""
        import os
        import subprocess
        import sys

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        r = subprocess.run(
            [
                sys.executable,
                os.path.join(repo_root, "tools", "gang_status.py"),
                "--smoke",
            ],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
        assert "smoke ok: scraped 2/2 ranks" in r.stdout
        assert "# Gang status" in r.stdout


class TestElasticShrinkPolicy:
    """The Distributor's permanent-loss judgment and shrink-to-fit path
    (docs/FAULT_TOLERANCE.md "Elastic resume"). Workers are plain
    functions — no jax gang — so these pin the POLICY; the end-to-end
    reshard-resume is drilled in TestElasticShrinkTraining and
    tools/fault_drill.py."""

    def test_budget_exhausted_names_rank_cause_attempts(self):
        from machine_learning_apache_spark_tpu.launcher import GangFailure

        with pytest.raises(GangFailure) as ei:
            Distributor(
                num_processes=2, platform="cpu", timeout=120,
                rank_restart_budget=0, backoff_base=0.05, term_grace=1.0,
            ).run("launcher_workers:fail_rank", 1)
        f = ei.value
        assert f.permanent is True
        assert f.rank == 1
        assert f.cause == "exit"
        msg = str(f)
        assert "permanently lost" in msg
        assert "budget 0" in msg
        assert "elastic" in msg  # tells the operator which knob to flip

    def test_no_budget_no_elastic_keeps_legacy_semantics(self):
        from machine_learning_apache_spark_tpu.launcher import GangFailure

        with pytest.raises(GangFailure) as ei:
            Distributor(
                num_processes=2, platform="cpu", timeout=120,
                max_restarts=1, backoff_base=0.05, term_grace=1.0,
            ).run("launcher_workers:fail_rank", 1)
        assert ei.value.permanent is False  # exhausted restarts, not a
        # permanent-loss judgment: nobody opted into the elastic policy

    def test_elastic_shrinks_past_lost_rank(self):
        """rank 2 always fails; with elastic on and budget 0 the gang
        must retry at world 2 — where the poisoned rank no longer exists
        — and succeed, with MLSPARK_ELASTIC plumbed to the workers."""
        out = Distributor(
            num_processes=3, platform="cpu", timeout=240, elastic=True,
            rank_restart_budget=0, elastic_min_world=1,
            backoff_base=0.05, term_grace=1.0,
        ).run("launcher_workers:fail_rank", 2)
        assert out["world"] == 2
        assert out["elastic_env"] == "1"

    def test_min_world_floor_raises_permanent(self):
        from machine_learning_apache_spark_tpu.launcher import GangFailure

        with pytest.raises(GangFailure) as ei:
            Distributor(
                num_processes=2, platform="cpu", timeout=120, elastic=True,
                rank_restart_budget=0, elastic_min_world=2,
                backoff_base=0.05, term_grace=1.0,
            ).run("launcher_workers:fail_rank", 1)
        f = ei.value
        assert f.permanent is True and f.rank == 1
        assert "elastic_min_world" in str(f)

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="elastic_min_world"):
            Distributor(num_processes=2, elastic_min_world=3)
        with pytest.raises(ValueError, match="elastic_min_world"):
            Distributor(num_processes=2, elastic_min_world=0)
        with pytest.raises(ValueError, match="rank_restart_budget"):
            Distributor(num_processes=2, rank_restart_budget=-1)


class TestElasticShrinkTraining:
    def test_shrink_resumes_training_from_group_checkpoint(
        self, tmp_path, monkeypatch
    ):
        """Small-config elastic_shrink drill (CI tier of the full
        tools/fault_drill.py scenario): a 3-rank ZeRO-1 gang loses rank
        2 permanently mid-training, shrinks to 2, reshards the 3-rank
        checkpoint group onto the 2-rank world, and finishes the
        remaining epochs — resumed from a checkpoint, not from scratch.
        (Loss parity vs an unfaulted run is asserted by the full drill,
        which this config mirrors at world 3.)"""
        import numpy as np

        from machine_learning_apache_spark_tpu.utils import faults

        monkeypatch.setenv(
            faults.ENV_PLAN, "crash@train_step:world=3,rank=2,step=5"
        )
        monkeypatch.setenv(faults.ENV_MARKER_DIR, str(tmp_path / "markers"))
        out = Distributor(
            num_processes=3, platform="cpu", timeout=480, elastic=True,
            rank_restart_budget=0, elastic_min_world=2,
            backoff_base=0.05, term_grace=2.0,
        ).run(
            "launcher_workers:elastic_drill_train", str(tmp_path / "gang"),
            epochs=4, global_batch=24, steps_per_epoch=2,
        )
        assert list((tmp_path / "markers").iterdir()), "fault never fired"
        assert out["world"] == 2
        # 8 total steps, checkpoints every epoch (2 steps), crash before
        # the 6th step: the shrunken gang resumes from the newest
        # group-durable checkpoint, never from scratch.
        assert out["resumed_step"] in (2, 4)
        assert np.isfinite(out["final_loss"])


class TestTpuChipPinning:
    """``tpu_pinning_env``: local children of a TPU host get chip ``rank``
    through their environment (checked on a four-chip v5e, PERF.md
    "Bring-up"); here the host's chip count is faked."""

    @pytest.fixture
    def four_chips(self, monkeypatch):
        from machine_learning_apache_spark_tpu.launcher import distributor

        monkeypatch.setattr(distributor, "local_tpu_chips", lambda: 4)
        monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
        return distributor

    def test_cpu_children_and_chipless_hosts_get_nothing(self, four_chips):
        d = four_chips
        assert d.tpu_pinning_env(0, "cpu") == {}
        assert d.tpu_pinning_env(0, "cpu", gang_ports=[1, 2]) == {}

    def test_no_chips_no_pinning(self, monkeypatch):
        from machine_learning_apache_spark_tpu.launcher import distributor as d

        monkeypatch.setattr(d, "local_tpu_chips", lambda: 0)
        assert d.tpu_pinning_env(3, "") == {}

    def test_replica_sees_exactly_its_chip(self, four_chips):
        env = four_chips.tpu_pinning_env(2, "tpu,cpu")
        assert env == {
            "TPU_VISIBLE_CHIPS": "2",
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }
        with pytest.raises(ValueError, match="chip 4.*4 chip"):
            four_chips.tpu_pinning_env(4, "")

    def test_gang_forms_one_runtime_over_the_host_grid(self, four_chips):
        ports = [9001, 9002, 9003, 9004]
        envs = [
            four_chips.tpu_pinning_env(k, "", gang_ports=ports)
            for k in range(4)
        ]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == list("0123")
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
        assert {e["TPU_PROCESS_ADDRESSES"] for e in envs} == {
            "localhost:9001,localhost:9002,localhost:9003,localhost:9004"
        }
        assert [e["TPU_PROCESS_PORT"] for e in envs] == [
            "9001", "9002", "9003", "9004"
        ]
        assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == list("0123")

    def test_gang_of_another_size_fails_fast_with_the_reason(self, four_chips):
        with pytest.raises(ValueError, match="one process per chip"):
            four_chips.tpu_pinning_env(0, "", gang_ports=[9001, 9002])


def test_heartbeat_survives_a_half_imported_faults_module(
    tmp_path, monkeypatch
):
    """The beat thread peeks at ``utils.faults`` through sys.modules while
    the main thread may be half-way through importing it; a module object
    that has no ``heartbeats_suspended`` yet must not kill the thread (a
    dead heartbeat reads as a stall)."""
    import sys
    import time
    import types

    from machine_learning_apache_spark_tpu.launcher import runner

    name = "machine_learning_apache_spark_tpu.utils.faults"
    monkeypatch.setitem(sys.modules, name, types.ModuleType(name))
    path = tmp_path / "heartbeat_0"
    thread = runner._start_heartbeat(str(path), 0.05)
    deadline = time.monotonic() + 5
    while not path.exists() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert path.exists() and thread.is_alive()


"""Core runtime tests: session, config, mesh, metrics, timing, prng."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import machine_learning_apache_spark_tpu as mlspark
from machine_learning_apache_spark_tpu.config import SessionConfig, TrainConfig
from machine_learning_apache_spark_tpu.parallel import (
    batch_sharding,
    data_parallel_mesh,
    make_mesh,
)
from machine_learning_apache_spark_tpu.parallel.mesh import shard_batch
from machine_learning_apache_spark_tpu.train.metrics import (
    Mean,
    MetricBundle,
    Sum,
    accuracy,
    logits_accuracy,
)
from machine_learning_apache_spark_tpu.utils import KeySeq, Timer, timed_span


def test_fake_cluster_has_8_devices():
    assert jax.device_count() == 8
    assert jax.default_backend() == "cpu"


class TestSession:
    def test_builder_get_or_create_is_singleton(self):
        s1 = mlspark.Session.builder.app_name("t").get_or_create()
        s2 = mlspark.Session.builder.get_or_create()
        assert s1 is s2
        s1.stop()

    def test_get_or_create_warns_only_on_differing_conf(self):
        """Idempotent re-creation with identical conf stays quiet; only
        keys that would actually change the active session warn (Spark
        semantics: builder conf is never applied to an existing session).
        The package logger doesn't propagate to root (it owns its stream
        handler), so capture with a handler attached to it directly."""
        import logging

        records: list[logging.LogRecord] = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(record)

        session_log = logging.getLogger(
            "machine_learning_apache_spark_tpu.session"
        )
        cap = Capture(level=logging.WARNING)
        session_log.addHandler(cap)
        s = (
            mlspark.Session.builder.appName("warn-test")
            .config("spark.executor.instances", 4)
            .getOrCreate()
        )
        try:
            # Same conf (string value coerces to the active int) → quiet.
            mlspark.Session.builder.appName("warn-test").config(
                "spark.executor.instances", "4"
            ).getOrCreate()
            assert not [r for r in records if "ignored" in r.getMessage()]
            # Differing value → warns, naming only the differing key.
            mlspark.Session.builder.appName("warn-test").config(
                "spark.executor.instances", 8
            ).getOrCreate()
            warns = [r for r in records if "ignored" in r.getMessage()]
            assert warns and "executor_instances" in warns[0].getMessage()
            assert "app_name" not in warns[0].getMessage()
        finally:
            session_log.removeHandler(cap)
            s.stop()

    def test_spark_style_conf_keys(self):
        s = (
            mlspark.Session.builder.appName("conf-test")
            .config("spark.executor.instances", 4)
            .config("spark.executor.cores", 2)
            .getOrCreate()
        )
        assert s.conf.app_name == "conf-test"
        assert s.conf.executor_instances == 4
        assert s.conf.executor_cores == 2
        # world size derives from runtime, not conf (unlike distributed_cnn.py:43)
        assert s.executor_count == jax.process_count()
        assert s.device_count == 8
        s.stop()

    def test_stop_clears_singleton(self):
        s = mlspark.Session.builder.get_or_create()
        s.stop()
        s2 = mlspark.Session.builder.get_or_create()
        assert s2 is not s
        s2.stop()


class TestConfig:
    def test_from_env_override(self, monkeypatch):
        monkeypatch.setenv("MLSPARK_BATCH_SIZE", "64")
        monkeypatch.setenv("MLSPARK_LEARNING_RATE", "0.5")
        cfg = TrainConfig.from_env()
        assert cfg.batch_size == 64
        assert cfg.learning_rate == 0.5

    def test_from_args(self):
        cfg = TrainConfig.from_args(["--epochs", "7", "--optimizer", "sgd"])
        assert cfg.epochs == 7
        assert cfg.optimizer == "sgd"

    def test_replace(self):
        cfg = SessionConfig().replace(app_name="x")
        assert cfg.app_name == "x"


class TestMesh:
    def test_default_data_parallel(self):
        mesh = data_parallel_mesh()
        assert mesh.shape == {"data": 8}

    def test_wildcard(self):
        mesh = make_mesh({"data": 0, "model": 2})
        assert mesh.shape["model"] == 2
        assert mesh.shape["data"] == 4

    def test_2d_mesh_axis_order(self):
        mesh = make_mesh({"model": 4, "data": 2})
        # data is the outer axis, model innermost (ICI locality)
        assert tuple(mesh.axis_names) == ("data", "model")

    def test_invalid_mesh_raises(self):
        with pytest.raises(ValueError):
            make_mesh({"data": 3})
        with pytest.raises(ValueError):
            make_mesh({"data": 0, "model": 0})

    def test_shard_batch_places_on_mesh(self):
        mesh = data_parallel_mesh()
        x = np.arange(32, dtype=np.float32).reshape(16, 2)
        sharded = shard_batch(mesh, {"x": x})["x"]
        assert sharded.sharding == batch_sharding(mesh)
        np.testing.assert_array_equal(np.asarray(sharded), x)


class TestMetrics:
    def test_accuracy_matches_reference_semantics(self):
        y = jnp.array([0, 1, 2, 2])
        p = jnp.array([0, 1, 1, 2])
        assert float(accuracy(y, p)) == 75.0

    def test_logits_accuracy(self):
        logits = jnp.array([[0.1, 0.9], [0.8, 0.2]])
        labels = jnp.array([1, 0])
        assert float(logits_accuracy(logits, labels)) == 100.0

    def test_accumulators(self):
        b = MetricBundle()
        for v in [1.0, 2.0, 3.0]:
            b.sum("total_loss").update(v)
            b.mean("avg_loss").update(v)
        out = b.compute()
        assert out["total_loss"] == 6.0
        assert out["avg_loss"] == 2.0
        assert "total_loss" in b.log_line()


class TestUtils:
    def test_keyseq_deterministic(self):
        a = KeySeq(0)
        b = KeySeq(0)
        assert jnp.array_equal(
            jax.random.key_data(a()), jax.random.key_data(b())
        )
        assert not jnp.array_equal(
            jax.random.key_data(a()), jax.random.key_data(b.fold(1)())
        )

    def test_timer_and_span(self, capsys):
        t = Timer("x").start()
        assert t.lap() >= 0.0
        with timed_span("Training Time"):
            pass
        out = capsys.readouterr().out
        assert "Training Time" in out and "sec" in out


class TestCompileCacheRule:
    """``utils.compilation_cache``: JAX_COMPILATION_CACHE_DIR places the
    cache when set (code sets no directory); unset, it is
    ``<checkout>/.xla_cache``. Checked in subprocesses — conftest turns the
    cache off for the test process itself."""

    SCRIPT = """
import jax
import machine_learning_apache_spark_tpu as mlspark
seen = [jax.config.jax_compilation_cache_dir]
session = mlspark.Session.builder.appName("cache-rule").getOrCreate()
seen.append(jax.config.jax_compilation_cache_dir)
import jax.numpy as jnp
from machine_learning_apache_spark_tpu.models import MLP
from machine_learning_apache_spark_tpu.train import (
    TrainState, classification_loss, fit, make_optimizer,
)
model = MLP(layers=(4, 5, 3))
x, y = jnp.ones((8, 4)), jnp.zeros((8,), jnp.int32)
state = TrainState.create(
    apply_fn=model.apply,
    params=model.init(jax.random.key(0), x[:1])["params"],
    tx=make_optimizer("sgd", 0.1),
)
fit(state, classification_loss(model.apply), [(x, y)], epochs=1, log_every=0)
seen.append(jax.config.jax_compilation_cache_dir)
assert len(set(seen)) == 1, seen
print("CACHE_DIR=" + str(seen[0]))
"""

    def _cache_dirs(self, env_dir, n=1):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {
            k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR",
                         "JAX_ENABLE_COMPILATION_CACHE", "XLA_FLAGS")
        }
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", self.SCRIPT], cwd=repo, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            for _ in range(n)
        ]
        dirs = []
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err[-2000:]
            dirs.append(
                [l for l in out.splitlines() if l.startswith("CACHE_DIR=")]
                [-1].split("=", 1)[1]
            )
        return dirs

    def test_env_var_places_the_cache(self, tmp_path):
        d = str(tmp_path / "placed-from-outside")
        assert self._cache_dirs(d) == [d]

    def test_unset_means_checkout_and_processes_agree(self):
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".xla_cache")
        assert self._cache_dirs(None, n=2) == [want, want]


"""bench.py is chip-only: no TPU -> non-zero exit naming what it found; an
unknown device_kind is an error, not a default peak; sweep points run in
the process that holds the chip (no child mode)."""

import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_exits_nonzero_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")], cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # no artifact from a CPU


def test_unknown_device_kind_is_an_error():
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert bench._peak_flops(v5e) == 197e12
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9z")
    with pytest.raises(ValueError, match="TPU v9z"):
        bench._peak_flops(unknown)


def test_sweep_runs_in_process(monkeypatch):
    calls = []

    def fake(jax, **kw):
        calls.append((kw["batch_per_chip"], kw["layers"]))
        return {"median": 1.0, "mfu": 0.1, "spread": 1.0}

    monkeypatch.setattr(bench, "bench_transformer", fake)
    monkeypatch.setenv("BENCH_SWEEP_POINTS", "128x1,32x4")
    points = bench.bench_transformer_sweep(None)
    assert calls == [(128, 1), (32, 4)]
    assert [(p["batch_per_chip"], p["layers"]) for p in points] == calls
    with open(os.path.join(REPO, "bench.py")) as f:
        assert "--sweep-point" not in f.read()

    def boom(jax, **kw):
        raise RuntimeError("stage failed")

    monkeypatch.setattr(bench, "bench_transformer", boom)
    with pytest.raises(RuntimeError, match="stage failed"):
        bench.bench_transformer_sweep(None)

"""chip_smoke.py's contract, as far as a CPU sandbox can check it: without a
TPU it fails fast and prints no result; the explicit rehearsal runs every
phase at toy width (kernels interpreted) and stamps the platform it ran on.
The chip run itself goes through the chip tool (README "Testing")."""

import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, env=None, timeout=900):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env or dict(os.environ),
        capture_output=True, text=True, timeout=timeout,
    )


def test_no_tpu_fails_fast_naming_the_platform():
    t0 = time.monotonic()
    proc = _run([SMOKE], timeout=120)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "platform 'cpu'" in proc.stderr
    # prints no result: no line of stdout parses as a JSON object
    for line in proc.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line


def test_alone_in_a_directory_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run(["chip_smoke.py"], cwd=str(tmp_path), env=env, timeout=120)
    assert proc.returncode != 0
    assert "machine_learning_apache_spark_tpu" in proc.stderr
    assert "{" not in proc.stdout


def test_rehearsal_runs_every_phase_and_is_stamped_cpu(tmp_path):
    """Toy width, 8 virtual CPU devices (inherited from conftest), kernels
    in interpret mode: data -> train -> paged serve -> kernels -> ZeRO-1."""
    proc = _run([SMOKE, "--rehearse", "--out", str(tmp_path)])
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last, report = json.loads(lines[-1]), json.loads(lines[-2])
    assert last == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    assert report["size"] == "rehearsal"
    assert report["device"]["platform"] == "cpu"
    assert set(report["phases"]) == {
        "data", "train", "serve", "kernels", "zero1",
    }
    assert all(p["ok"] for p in report["phases"].values())
    serve = report["phases"]["serve"]
    assert serve["recompiles_after_warmup"] == 0
    assert serve["token_agreement"] == 1.0  # float32: token-identical
    assert serve["first_disagreements"] == []
    assert serve["engine_devices"] == [0]  # one engine, one device
    assert report["phases"]["kernels"]["mode"] == "interpret"
    # both dtypes of the scan check, and on a CPU the lax.scan path
    paths = report["phases"]["kernels"]["gated_delta_path"]
    assert set(paths) == {"float32", "bfloat16"}
    assert all(p.startswith("chunked_scan (") for p in paths.values())
    assert report["phases"]["train"]["mesh_devices"] == 8
    sites = {a["site"] for a in serve["attention"]}
    assert sites == {"dot_product", "ragged_paged_decode"}
    with open(tmp_path / "report.json") as f:
        assert json.load(f)["ok"] is True

"""CPU rehearsals of ``benchmark.run`` at toy widths, one per kind of cell:
everything a run does but the look for a chip. A rehearsal proves paths,
counts and the decision of ``correct``; its line names the CPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest, manifest as manifest_mod, run as bench_run

ROOT = manifest.ROOT

# big_serve_batch was put off by PR 23 (PERF.md): its files are written and
# its manifest entries wait in benchmark/put_off_big_serve_batch.json.
MANIFEST = manifest_mod.with_put_off(
    manifest_mod.load_manifest(), "big_serve_batch"
)


def _names(group, cell):
    return {m["name"] for m in manifest.metrics_for(MANIFEST, cell, group)}


@pytest.mark.parametrize("cell", [
    "ref_train_1chip", "big_train_dp4", "big_serve_batch", "big_serve_steady",
])
def test_untraced_rehearsal_reports_the_cells_end_to_end_metrics(
    cell, capfd, tmp_path
):
    result = bench_run.run_cell(
        cell, seed=2**31 + 41, seconds=0.6, trace=False,
        require_chip=False, rehearse=True, manifest=MANIFEST,
        out_dir=str(tmp_path),
    )
    err = capfd.readouterr().err
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == _names("end_to_end", cell)
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "compared" and result["compared"]
    # the earlier lines the issue asks for
    assert "device: cpu" in err and "set-up:" in err
    assert "attention site dot_product" in err
    last = [l for l in err.strip().splitlines() if l.startswith("compared ")]
    assert len(last) == len(result["compared"])
    if "serve" in cell:
        assert "conservation ledger" in err and "'failed': 0" in err
    if cell == "big_serve_steady":
        assert "generator lateness" in err


@pytest.mark.parametrize("cell", ["ref_train_1chip", "big_serve_batch"])
def test_traced_rehearsal_reports_per_layer_metrics(cell, tmp_path):
    # An out_dir of its own: other test files rehearse the same cells in
    # other processes, and a run clears its cell's trace directory first.
    result = bench_run.run_cell(
        cell, seed=43, seconds=1.0, trace=True, require_chip=False,
        rehearse=True, manifest=MANIFEST, out_dir=str(tmp_path),
    )
    assert result["correct"] is True
    reported = set(result["metrics"])
    assert reported and reported <= _names("per_layer", cell)
    # No chip: nothing to take a share of, so no mfu and no roofline -
    # a reader that finds nothing returns nothing, never 0.
    assert not any("mfu" in n or "roofline" in n for n in reported)
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not os.path.exists(tmp_path / "trace"), "the trace is read, then removed"


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_command_exits_nonzero_without_a_tpu_and_prints_no_result():
    done = _cli(ROOT, "--workload", "big_serve_steady", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and done.stdout == ""
    assert "not a TPU" in done.stderr


def test_command_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in manifest.load_manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path)
    done = _cli(tmp_path, "--workload", "big_serve_steady", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and done.stdout == ""


def test_result_line_is_one_json_object():
    """``main`` prints the object as the last line of standard output and
    nothing else there (``--rehearse`` stands in for the chip)."""
    done = _cli(ROOT, "--workload", "ref_train_1chip", "--seed", "3000000019",
                "--seconds", "0.5", "--trace", "0", "--rehearse", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert len(lines) == 1
    result = json.loads(lines[0])
    assert set(result) == {
        "correct", "attempted", "failed", "metrics", "device", "compared"}
    assert result["correct"] is True

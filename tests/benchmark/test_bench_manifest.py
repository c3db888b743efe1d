"""``BENCHMARK.json`` against the contract, as far as a loader can tell."""

import json
import os
import re

import pytest

from benchmark import manifest


@pytest.fixture(scope="module")
def m():
    return manifest.load_manifest()


def test_manifest_has_no_problems(m):
    assert manifest.problems(m) == []


def test_exact_top_level_keys(m):
    assert set(m) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_name_and_unit_within_the_allowed_characters(m):
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in m[group]:
            assert name.match(entry["name"]), entry["name"]
    for cell in m["workloads"]:
        assert name.match(cell["config"]) and name.match(cell["traffic"])
    for metric in m["end_to_end"] + m["per_layer"]:
        assert unit.match(metric["unit"]), metric
    for entry in m["configs"] + m["workloads"]:
        for key in ("why", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]


def test_entries_have_just_the_contract_keys(m):
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for c in m["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}


def test_per_layer_cells_report_the_metric_they_move(m):
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for p in m["per_layer"]:
        moved = e2e[p["moves"]]
        for cell in p["workloads"]:
            assert "workloads" not in moved or cell in moved["workloads"], p


def test_every_cell_reports_setup_another_metric_and_a_layer_metric(m):
    for cell in m["workloads"]:
        names = [x["name"] for x in manifest.metrics_for(m, cell["name"], "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
        layer = manifest.metrics_for(m, cell["name"], "per_layer")
        assert any("mfu" in x["name"] for x in layer), cell["name"]


def test_files_are_found_by_name(m):
    for cell in m["workloads"]:
        cell_file = manifest.load_cell_file(cell["name"])
        assert callable(manifest.load_kind(cell_file["kind"]).run)
        assert manifest.load_traffic(cell["traffic"])["kind"] == cell_file["kind"]
        cfg = manifest.load_config(m, cell["config"])
        assert cfg["name"] == cell["config"]
    for p in m["per_layer"]:
        assert callable(manifest.load_reader(p["name"]))


def test_reduced_names_no_width(m):
    width = re.compile(r"(_dim|_rank)$|hidden|d_model|head")
    for c in m["configs"]:
        assert not any(width.search(k) for k in c["reduced"])


def test_problems_catches_a_bad_manifest(m):
    bad = json.loads(json.dumps(m))
    bad["per_layer"][0]["unit"] = "tokens per second"
    bad["workloads"][0]["name"] = "has space"
    bad["end_to_end"][0]["bound"] = 0.5
    found = "\n".join(manifest.problems(bad))
    assert "bad unit" in found and "bad name" in found and "bound" in found

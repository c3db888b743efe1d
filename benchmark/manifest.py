"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one cell, one configuration, one traffic mix or
one per-layer metric sits in a file of its own, found by the name in the
manifest: ``benchmark/workloads/<cell>.json``, the configuration's ``file``,
``benchmark/traffic/<mix>.json``, ``benchmark/layer_metrics/<metric>.py``
and ``benchmark/kinds/<kind>.py``. A later PR adds files and manifest
entries and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def with_put_off(manifest: dict, name: str) -> dict:
    """The manifest with the entries of a put-off cell
    (``benchmark/put_off_<name>.json``) added: what a later benchmark PR
    would commit, and what the tests rehearse the cell with."""
    extra = _read_json(os.path.join(HERE, f"put_off_{name}.json"))
    out = json.loads(json.dumps(manifest))
    for group in ("workloads", "end_to_end", "per_layer"):
        out[group] = out[group] + extra[group]
    return out


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    known = [c["name"] for c in manifest["workloads"]]
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def load_cell_file(name: str) -> dict:
    return _read_json(os.path.join(HERE, "workloads", name + ".json"))


def load_config(manifest: dict, name: str, root: str = ROOT) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return _read_json(os.path.join(root, entry["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> dict:
    return _read_json(os.path.join(HERE, "traffic", name + ".json"))


def _load_module(directory: str, name: str):
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{directory}/{name}.py is not under benchmark/")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{directory}.{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_kind(kind: str):
    """The driver of a kind of cell: ``benchmark/kinds/<kind>.py``."""
    return _load_module("kinds", kind)


def load_reader(metric: str):
    """The reader of one per-layer metric: ``read(run) -> float | None``."""
    return _load_module("layer_metrics", metric).read


def metrics_for(manifest: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that this
    cell reports. Without a ``workloads`` key an end-to-end metric belongs
    to every cell; a per-layer metric to every cell that reports the
    end-to-end metric it moves."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}

    def reports(metric: dict) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        if group == "end_to_end":
            return True
        return reports_e2e(e2e[metric["moves"]])

    def reports_e2e(metric: dict) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    return [m for m in manifest[group] if reports(m)]


def problems(manifest: dict, root: str = ROOT) -> list[str]:
    """What the contract would refuse, as far as a loader can tell: names
    and units within the allowed characters, files where the names say,
    every per-layer metric's cells reporting the metric it moves."""
    out: list[str] = []
    names = lambda group: [m["name"] for m in manifest[group]]  # noqa: E731
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for n in names(group):
            if not NAME.match(n):
                out.append(f"{group}: bad name {n!r}")
            if n in seen:
                out.append(f"{group}: duplicate name {n!r}")
            seen.add(n)
    if "setup_s" not in names("end_to_end"):
        out.append("end_to_end lacks setup_s")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(m["unit"]):
            out.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"{m['name']}: better is {m['better']!r}")
        if m["source"] not in SOURCES:
            out.append(f"{m['name']}: source is {m['source']!r}")
    for m in manifest["end_to_end"]:
        if not 0 < m["bound"] <= 0.1:
            out.append(f"{m['name']}: bound {m['bound']} outside (0, 0.1]")
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']}: an end-to-end source is the benchmark's own")
    configs = {c["name"] for c in manifest["configs"]}
    cells = {c["name"] for c in manifest["workloads"]}
    used = set()
    pairs = set()
    for c in manifest["workloads"]:
        if c["config"] not in configs:
            out.append(f"{c['name']}: unknown config {c['config']!r}")
        used.add(c["config"])
        if (c["config"], c["traffic"]) in pairs:
            out.append(f"{c['name']}: config and traffic pair appears twice")
        pairs.add((c["config"], c["traffic"]))
        if c["chips"] not in (1, 4):
            out.append(f"{c['name']}: chips is {c['chips']}")
        if not 1 <= len(c["why"]) <= 200 or "\n" in c["why"]:
            out.append(f"{c['name']}: why must be one line of 1-200 characters")
        for sub, name in (("workloads", c["name"]), ("traffic", c["traffic"])):
            if not os.path.exists(os.path.join(root, "benchmark", sub, name + ".json")):
                out.append(f"{c['name']}: benchmark/{sub}/{name}.json is missing")
        if len(metrics_for(manifest, c["name"], "end_to_end")) < 2:
            out.append(f"{c['name']}: reports no end-to-end metric besides setup_s")
        if not metrics_for(manifest, c["name"], "per_layer"):
            out.append(f"{c['name']}: reports no per-layer metric")
    four = sum(1 for c in manifest["workloads"] if c["chips"] == 4)
    if four > max(len(manifest["workloads"]) // 4, 1):
        out.append(f"{four} cells ask for 4 chips")
    for c in manifest["configs"]:
        if c["name"] not in used:
            out.append(f"config {c['name']!r} is used by no cell")
        if not os.path.exists(os.path.join(root, c["file"])):
            out.append(f"config file {c['file']} is missing")
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            out.append(f"config file {c['file']} is outside paths")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
            continue
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", []):
            if cell not in cells:
                out.append(f"{m['name']}: unknown cell {cell!r}")
            elif "workloads" in moved and cell not in moved["workloads"]:
                out.append(
                    f"{m['name']}: cell {cell!r} does not report {m['moves']}"
                )
        if not os.path.exists(
            os.path.join(root, "benchmark", "layer_metrics", m["name"] + ".py")
        ):
            out.append(f"{m['name']}: benchmark/layer_metrics/{m['name']}.py is missing")
    return out

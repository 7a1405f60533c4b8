"""BENCHMARK.json is well-formed by the driver's rules, every name in it
resolves to files under its paths, and the harness loads every cell."""

import json
import os
import re

import pytest

import bench_paths
from harness import loader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return loader.load_manifest(bench_paths.ROOT)


def _one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(bench_paths.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32
    assert all(_one_line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(bench_paths.ROOT, p))
    # the command names no file outside the paths
    script = manifest["command"][1]
    assert any(script.startswith(p + "/") for p in manifest["paths"])
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def test_every_file_under_the_paths_is_named_from_name_characters(manifest):
    for p in manifest["paths"]:
        for base, dirs, files in os.walk(os.path.join(bench_paths.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), bench_paths.ROOT)
                assert PATH.match(rel), rel


def test_configs(manifest):
    names, files = set(), set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert c["file"] not in files
        files.add(c["file"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        held = loader.read_json(os.path.join(bench_paths.ROOT, c["file"]))
        assert held["name"] == c["name"]
        assert held["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert "assumed" in held and held["control_precision"]
    used = {w["config"] for w in manifest["workloads"]}
    assert used == names, "every configuration keeps at least one cell"


def test_workloads(manifest):
    names, pairs = set(), set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key]), w[key]
        assert w["name"] not in names
        names.add(w["name"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and _one_line(w["why"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_metrics(manifest):
    names = set()
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _one_line(m["layer"])
        assert m["moves"] in e2e
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in {"lower", "higher"}
        assert set(m.get("workloads", cells)) <= cells


def test_every_cell_resolves_and_reports_what_the_contract_asks(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        cell = loader.resolve_cell(bench_paths.ROOT, w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, "at least one per-layer metric a cell"
        for m in cell.per_layer:
            # each layer metric moves an end-to-end metric this cell reports
            assert m["moves"] in reported, (w["name"], m["name"])
            reader = cell.layer_reader(m["name"])
            assert callable(reader)
        for fn in ("setup", "run_window", "check", "control"):
            assert callable(getattr(cell.driver, fn))
        for fn in ("init_params", "train_steps", "layers"):
            assert callable(getattr(cell.reference, fn))
        assert set(cell.cell["limits"]) == {
            "loss", "grad_norm_worst", "grad_norm_median", "delta_norm_worst"}
        assert len(cell.cell["limits"]["loss"]) == cell.traffic["check_steps"]
    # a layer metric without a "workloads" key is due in EVERY cell that
    # reports the end-to-end metric it moves
    for m in manifest["per_layer"]:
        if "workloads" in m:
            continue
        for w in manifest["workloads"]:
            cell = loader.resolve_cell(bench_paths.ROOT, w["name"])
            if m["moves"] in {x["name"] for x in cell.end_to_end}:
                assert m["name"] in {x["name"] for x in cell.per_layer}


def test_layer_metric_files_state_what_the_manifest_states(manifest):
    for m in manifest["per_layer"]:
        mod = loader.import_file(os.path.join(
            bench_paths.BENCH, "layer_metrics", m["name"] + ".py"),
            "layer_metric")
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                    m["moves"])

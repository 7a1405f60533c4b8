"""A later PR adds a cell, a configuration, a traffic mix, a driver and a
per-layer metric as NEW files plus NEW manifest entries and edits no file
that is there: shown on a throw-away copy of the benchmark, with the
fixture cell (``bench_paths.overlay``) and a throw-away metric."""

import hashlib
import json
import os
import subprocess
import sys

import bench_paths
from harness import loader


def _digest(root):
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def test_add_a_cell_as_files(tmp_path):
    before = _digest(bench_paths.BENCH)
    original = loader.load_manifest(bench_paths.ROOT)
    root = bench_paths.overlay(str(tmp_path))
    bench = os.path.join(root, "benchmark")

    # a new per-layer metric: a small reader of its own, named on the cell
    with open(os.path.join(bench, "layer_metrics",
                           "throwaway.dispatches.py"), "w") as f:
        f.write('LAYER = "fit loops"\nUNIT = "count"\n'
                'MOVES = "train_items_per_s"\n\n\n'
                'def read(ctx):\n    return float(ctx["raw"]["steps"])\n')
    cell_file = os.path.join(bench, "cells",
                             bench_paths.FIXTURE_CELL + ".json")
    cell = loader.read_json(cell_file)          # (the PR's own new file)
    cell["per_layer"].append("throwaway.dispatches")
    with open(cell_file, "w") as f:
        json.dump(cell, f)
    manifest = loader.load_manifest(root)
    manifest["per_layer"].append({
        "name": "throwaway.dispatches", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "fit loops",
        "moves": "train_items_per_s",
        "workloads": [bench_paths.FIXTURE_CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    # nothing that was there changed: files ...
    after = _digest(bench)
    assert {k: after[k] for k in before} == before, "an existing file changed"
    assert len(after) == len(before) + 7
    # ... and manifest entries
    for key, was in original.items():
        now = manifest[key]
        assert (now[:len(was)] if isinstance(was, list) else now) == was, key

    got = loader.resolve_cell(root, bench_paths.FIXTURE_CELL)
    assert got.config["units"] == 256 and got.traffic["sequences"] == 4096
    assert got.traffic["driver"] == "fit_tbptt_fused"
    assert "throwaway.dispatches" in [m["name"] for m in got.per_layer]
    assert got.layer_reader("throwaway.dispatches")(
        {"raw": {"steps": 3}}) == 3.0
    # the cells that were there still resolve, and do not report the new one
    for w in original["workloads"]:
        old = loader.resolve_cell(root, w["name"])
        assert "throwaway.dispatches" not in [m["name"]
                                              for m in old.per_layer]

    # and the copy's own run.py drives the new cell end to end
    out = subprocess.run(
        [sys.executable, os.path.join(bench, "run.py"), "--workload",
         bench_paths.FIXTURE_CELL, "--seed", "5", "--seconds", "0.5",
         "--rehearse"],
        env=bench_paths.run_env(), capture_output=True, text=True,
        timeout=600, cwd=root)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["attempted"] >= 1


def test_run_fails_where_only_the_benchmark_is(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under its
    paths has no program to measure: non-zero exit, no result."""
    root = str(tmp_path)
    bench_paths.copy_benchmark(root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "resnet50_train_1chip", "--seed", "5", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=300, cwd=root)
    assert out.returncode != 0
    assert "deeplearning4j_tpu" in out.stderr
    assert not any(l.startswith("{") for l in out.stdout.splitlines())

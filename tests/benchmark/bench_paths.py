"""Where the benchmark lives, for its tests (tests/ has no __init__.py, so
each test file imports this helper by its unique name), and the second
configuration the tests drive.

``fixtures/`` holds a whole cell as files: the zoo's character LSTM trained
through ``fit_tbptt_fused`` (configuration, builder, plain reference,
driver, traffic mix, cell). It is small enough to run end to end on the CPU
in seconds, which ResNet50 is not, and it is the cell PERF.md section 7
keeps for a later PR. ``overlay`` lays it over a throw-away copy of the
benchmark exactly as a later PR would add a cell: new files, new manifest
entries, no edit to a file that is there."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
FIXTURES = os.path.join(HERE, "fixtures")
FIXTURE_CELL = "charrnn_train_tbptt"
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def copy_benchmark(root: str) -> None:
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)


def overlay(root: str) -> str:
    """A copy of the benchmark under ``root`` with the fixture cell added."""
    copy_benchmark(root)
    for kind in ("configs", "references", "drivers", "traffic", "cells"):
        for name in os.listdir(os.path.join(FIXTURES, kind)):
            target = os.path.join(root, "benchmark", kind, name)
            assert not os.path.exists(target), f"{target} would be edited"
            shutil.copy(os.path.join(FIXTURES, kind, name), target)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(FIXTURES, "manifest_entries.json")) as f:
        extra = json.load(f)
    for key, entries in extra.items():
        manifest[key].extend(entries)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def run_env(**extra) -> dict:
    """Environment of a child ``run.py`` that lives in a copy: the program
    itself comes from the real checkout."""
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **extra)

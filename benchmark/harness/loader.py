"""Finds a cell's files by the names in ``BENCHMARK.json``. Nothing here
lists cells, configurations, mixes, drivers or metrics: a later PR adds a
cell as new files plus new manifest entries and edits no file that exists.

    BENCHMARK.json workloads[i].name      -> benchmark/cells/<name>.json
    cell "config" -> manifest configs[].file (the sizes as run), whose
        "build" = "<module>:<function>" lives in benchmark/configs/<module>.py
        and whose "reference" names benchmark/references/<reference>.py
    cell "traffic"  -> benchmark/traffic/<traffic>.json, whose "driver"
        names benchmark/drivers/<driver>.py
    a per-layer metric <name> -> benchmark/layer_metrics/<name>.py"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List

BENCH_DIR = "benchmark"


def repo_root() -> str:
    """The checkout: two levels above this file's directory."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str) -> dict:
    return read_json(os.path.join(root, "BENCHMARK.json"))


def import_file(path: str, kind: str):
    """Import one file under its own module name (``bench_<kind>_<stem>``),
    so that a configuration's builder and its reference, which share a file
    name, stay apart."""
    stem = os.path.splitext(os.path.basename(path))[0]
    name = f"bench_{kind}_{stem}".replace(".", "_").replace("-", "_")
    cached = sys.modules.get(name)
    if cached is not None and getattr(cached, "__file__", None) == path:
        return cached
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot import {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r} "
                   f"(it has {[e['name'] for e in entries]})")


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    cell: dict                 # benchmark/cells/<name>.json
    config: dict               # the configuration's file, as run
    traffic: dict              # benchmark/traffic/<mix>.json
    end_to_end: List[dict]     # manifest entries this cell reports
    per_layer: List[dict]
    driver: Any                # module
    reference: Any             # module
    build: Callable            # the configuration's builder
    adapter: Any               # module that holds ``build``

    def layer_reader(self, metric: str) -> Callable:
        path = os.path.join(self.root, BENCH_DIR, "layer_metrics",
                            metric + ".py")
        return import_file(path, "layer_metric").read


def _reports(metric: dict, cell_name: str, cell_metrics: List[str]) -> bool:
    """A manifest metric belongs to a cell when the cell's own file lists
    it; a ``workloads`` key on the metric, where the manifest has one, has
    to agree."""
    listed = metric["name"] in cell_metrics
    named = metric.get("workloads")
    if named is not None and (cell_name in named) != listed:
        raise ValueError(
            f"metric {metric['name']!r}: BENCHMARK.json lists cells {named} "
            f"but benchmark/cells/{cell_name}.json "
            f"{'lists' if listed else 'does not list'} it")
    return listed


def resolve_cell(root: str, name: str, rehearse: bool = False) -> Cell:
    manifest = load_manifest(root)
    bench = os.path.join(root, BENCH_DIR)
    entry = _by_name(manifest["workloads"], name, "workload")
    cell = read_json(os.path.join(bench, "cells", name + ".json"))
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise ValueError(f"cell {name}: {key} is {cell[key]!r} in its file "
                             f"and {entry[key]!r} in BENCHMARK.json")
    cfg_entry = _by_name(manifest["configs"], cell["config"], "config")
    config = read_json(os.path.join(root, cfg_entry["file"]))
    traffic = read_json(os.path.join(bench, "traffic",
                                     cell["traffic"] + ".json"))
    if rehearse:
        # tiny shapes for the CPU tests: each file says what shrinks
        config = {**config, **config.get("rehearse", {})}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    module, _, function = config["build"].partition(":")
    adapter = import_file(os.path.join(bench, "configs", module + ".py"),
                          "config")
    return Cell(
        root=root, name=name, chips=int(entry["chips"]), cell=cell,
        config=config, traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"]
                    if _reports(m, name, cell["end_to_end"])],
        per_layer=[m for m in manifest["per_layer"]
                   if _reports(m, name, cell["per_layer"])],
        driver=import_file(os.path.join(bench, "drivers",
                                        traffic["driver"] + ".py"), "driver"),
        reference=import_file(os.path.join(bench, "references",
                                           config["reference"] + ".py"),
                              "reference"),
        build=getattr(adapter, function), adapter=adapter)

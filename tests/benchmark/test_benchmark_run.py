"""run.py end to end at tiny shapes on the CPU (``--rehearse``), its refusal
to measure without a TPU, and the check that a broken timed path comes out
as not correct."""

import contextlib
import importlib.util
import io
import json
import os
import subprocess
import sys

import pytest

import bench_paths


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with the fixture cell laid over it."""
    return bench_paths.overlay(str(tmp_path_factory.mktemp("bench")))


def _run(root, *args, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        env=bench_paths.run_env(BENCH_RUN="ignored"), capture_output=True,
        text=True, timeout=timeout, cwd=root)


def test_refuses_to_measure_without_a_tpu(root):
    out = _run(root, "--workload", bench_paths.FIXTURE_CELL, "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    for line in out.stdout.splitlines():
        assert not line.startswith("{"), "no result may be printed"


def test_unknown_cell_is_an_error(root):
    out = _run(root, "--workload", "no_such_cell", "--seed", "1", "--rehearse")
    assert out.returncode != 0 and "no_such_cell" in out.stderr


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearse_prints_a_well_formed_last_line_and_no_metric(root, trace):
    out = _run(root, "--workload", bench_paths.FIXTURE_CELL, "--seed",
               "3000000001",
               "--seconds", "1", "--trace", trace, "--rehearse")
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert last["metrics"] == {} and last["rehearse"] is True
    assert last["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" not in last["device"]
    assert "busy_s" not in last["device"] and "breakdown" not in last
    # every number compared is printed beside its limit, on earlier lines
    checks = [l for l in out.stdout.splitlines() if l.startswith("check ")]
    assert len(checks) >= 3 and all("limit=" in l for l in checks)
    assert "compiles inside the window: 0" in out.stdout


def _main_in_process(root, argv):
    spec = importlib.util.spec_from_file_location(
        "bench_run_main", os.path.join(root, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = mod.main(argv)
    return rc, json.loads(buf.getvalue().strip().splitlines()[-1])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    """The rest of a run is driven as it is; underneath, the fused step is
    broken: it runs, and then the parameters it started from come back."""
    import jax
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    real = MultiLayerNetwork.fit_tbptt_fused

    def unchanged(self, x, y):
        before = jax.tree_util.tree_map(lambda a: a.copy(), self.params)
        real(self, x, y)
        self.params = before
        return self

    argv = ["--workload", bench_paths.FIXTURE_CELL, "--seed", "77",
            "--seconds", "0.5", "--trace", "0", "--rehearse"]
    rc, sound = _main_in_process(root, argv)
    assert rc == 0 and sound["correct"] is True
    monkeypatch.setattr(MultiLayerNetwork, "fit_tbptt_fused", unchanged)
    rc, broken = _main_in_process(root, argv)
    assert rc == 0 and broken["correct"] is False
    assert broken["attempted"] >= 1

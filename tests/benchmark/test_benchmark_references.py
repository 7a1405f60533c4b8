"""Each configuration's plain reference against the package at tiny sizes:
the same seeded weights and rows through the program's public path and
through the reference have to agree, and the control precision has to
differ."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_paths
from harness import check, loader


def test_charrnn_reference_follows_the_package_float32(tmp_path):
    """On the CPU both sides are exact float32: the fused dispatches the
    check follows agree to rounding in loss, RmsProp state and parameter
    change."""
    import jax

    cell = loader.resolve_cell(bench_paths.overlay(str(tmp_path)),
                               bench_paths.FIXTURE_CELL, rehearse=True)
    session = cell.driver.setup(cell, jax.devices()[:1], 2_147_483_999,
                                lambda *a: None)
    ok, rows = cell.driver.check(session, lambda *a: None)
    assert ok, rows
    assert len(rows) == cell.traffic["check_steps"] + 3
    assert max(r["value"] for r in rows) < 1e-5
    # the control (float8 matmul operands) is told apart at those limits
    ok, rows = cell.driver.control(session, lambda *a: None)
    assert not ok, rows


_RESNET_F64 = r"""
import sys, json
sys.path.insert(0, {bench!r}); sys.path.insert(0, {root!r})
import numpy as np, jax, jax.numpy as jnp
from harness import loader
from deeplearning4j_tpu.datasets.dataset import DataSet
cell = loader.resolve_cell({root!r}, "resnet50_train_1chip", rehearse=True)
cfg = dict(cell.config, compute_dtype="float64", input_shape=[32, 32, 3])
ref = cell.reference
p0 = {{k: a.astype(jnp.float64) for k, a in ref.init_params(cfg, 11).items()}}
net = cell.build(cfg, p0)
rng = np.random.default_rng(0)
x = rng.standard_normal((4, 32, 32, 3)); y = np.eye(10)[rng.integers(0, 10, 4)]
net.fit([DataSet(x, y)])
grads = cell.adapter.first_gradient_flat(net, cfg)
val, g_ref = jax.value_and_grad(lambda q: ref.loss(cfg, q, x, y))(p0)
worst = max(float(jnp.linalg.norm(grads[k] - g_ref[k])
                  / jnp.maximum(jnp.linalg.norm(g_ref[k]), 1e-300))
            for k in g_ref)
# the control: the same reference with fp8 operands, in the program's place
from harness import check
low = jax.grad(lambda q: ref.loss(cfg, q, x, y, "fp8"))(p0)
norm = lambda g: {{k: float(jnp.linalg.norm(a)) for k, a in g.items()}}
control_gap, _ = check.worst_leaf_gap(norm(low), norm(g_ref))
print(json.dumps({{"loss_program": net.score(), "loss_reference": float(val),
                  "worst_leaf_gradient_gap": worst, "leaves": len(g_ref),
                  "control_gap": control_gap}}))
"""


def test_resnet50_reference_follows_the_package_float64():
    """In float32 at a tiny batch, batch norm over a handful of values
    amplifies rounding to per cent, so the semantics are held in float64:
    loss and every leaf of the first gradient (read back from Adam's
    state) agree to 1e-9. A child process, because x64 is process-wide."""
    env = dict(os.environ, JAX_ENABLE_X64="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _RESNET_F64.format(bench=bench_paths.BENCH,
                                                  root=bench_paths.ROOT)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["leaves"] == 161
    assert abs(got["loss_program"] - got["loss_reference"]) < 1e-9
    assert got["worst_leaf_gradient_gap"] < 1e-9
    # ... and the control is far from it: float8 operands move the worst
    # leaf's gradient norm by more than a tenth even at this tiny size
    assert got["control_gap"] > 0.1


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    gap, leaf = check.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 2e-9}, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    assert check.median_leaf_gap({"a": 1.1, "b": 2.1, "c": 2e-9},
                                 ref) == pytest.approx(0.05)
    # an all-but-zero leaf is held against the median leaf, not itself
    gap, leaf = check.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 0.5}, ref)
    assert leaf == "c" and gap == pytest.approx(0.5)
    with pytest.raises(ValueError):
        check.worst_leaf_gap({"a": 1.0}, ref)
    gap, _ = check.worst_leaf_gap({"a": float("nan"), "b": 2.0, "c": 0.0},
                                  ref)
    assert gap == float("inf")


@pytest.mark.parametrize("broken,expect", [
    ({}, True),
    ({"losses": [1.0, 2.5]}, False),                 # a loss off by 25 %
    ({"grad_norms": {"w": 1.3, "v": 1.0, "u": 1.0}}, False),   # one leaf off
    ({"grad_norms": {"w": 1.04, "v": 1.04, "u": 1.04}}, False),  # all a little
    ({"grad_norms": {"w": 1.04, "v": 1.0, "u": 1.0}}, True),
    ({"delta_norms": {"w": 0.0, "v": 0.0, "u": 0.0}}, False),  # state unchanged
    ({"losses": [float("nan"), 2.0]}, False),
    ({"losses": [1.0]}, False),                      # a step missing
])
def test_compare_training_holds_each_number_to_its_own_limit(broken, expect):
    leaves = {"w": 1.0, "v": 1.0, "u": 1.0}
    reference = {"losses": [1.0, 2.0], "grad_norms": dict(leaves),
                 "delta_norms": dict(leaves)}
    program = {**reference, **broken}
    limits = {"loss": [0.01, 0.1], "grad_norm_worst": 0.05,
              "grad_norm_median": 0.02, "delta_norm_worst": 0.2}
    lines = []
    ok, rows = check.compare_training(program, reference, limits,
                                      lines.append)
    assert ok is expect
    assert len(lines) == len(rows) and all("limit=" in l for l in lines)

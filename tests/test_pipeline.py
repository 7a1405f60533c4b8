"""GPipe pipeline-parallelism tests (parallel/pipeline.py): the pipelined
schedule must match the plain sequential stack — outputs AND gradients —
and train end to end. Runs on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.parallel.pipeline import (
    GPipeTrainer, make_pipeline_mesh, pipeline_apply, stage_shardings,
)

S, M, MB, D = 4, 6, 4, 8


def block_fn(p, x):
    return jnp.tanh(x @ p["W"] + p["b"])


def sequential(params, x):
    for s in range(S):
        x = block_fn(jax.tree_util.tree_map(lambda a: a[s], params), x)
    return x


def _stacked_params(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "W": jnp.asarray(rng.standard_normal((S, D, D), np.float32) * 0.4),
        "b": jnp.asarray(rng.standard_normal((S, D), np.float32) * 0.1),
    }


def test_pipeline_matches_sequential_forward(devices):
    mesh = make_pipeline_mesh(S)
    params = jax.device_put(_stacked_params(), stage_shardings(mesh, _stacked_params()))
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.standard_normal((M, MB, D), np.float32))
    with mesh:
        got = pipeline_apply(block_fn, params, xs, mesh)
    want = jax.vmap(lambda x: sequential(_stacked_params(), x))(xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_gradients_match_sequential(devices):
    mesh = make_pipeline_mesh(S)
    params0 = _stacked_params()
    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.standard_normal((M, MB, D), np.float32))
    ys = jnp.asarray(rng.standard_normal((M, MB, D), np.float32))

    def loss_pipe(p):
        with mesh:
            preds = pipeline_apply(block_fn, p, xs, mesh)
        return jnp.mean((preds - ys) ** 2)

    def loss_seq(p):
        preds = jax.vmap(lambda x: sequential(p, x))(xs)
        return jnp.mean((preds - ys) ** 2)

    p_sharded = jax.device_put(params0, stage_shardings(mesh, params0))
    g_pipe = jax.jit(jax.grad(loss_pipe))(p_sharded)
    g_seq = jax.jit(jax.grad(loss_seq))(params0)
    for k in ("W", "b"):
        np.testing.assert_allclose(np.asarray(g_pipe[k]),
                                   np.asarray(g_seq[k]),
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f"grad {k} diverged")


def test_gpipe_trainer_learns_and_matches_reference_steps(devices):
    mesh = make_pipeline_mesh(S)
    tr = GPipeTrainer(block_fn,
                      lambda pred, y: jnp.mean((pred - y) ** 2),
                      Sgd(learning_rate=0.1), mesh=mesh)
    params = tr.place(_stacked_params())
    opt = tr.init_opt(params)
    rng = np.random.default_rng(3)
    xs = rng.standard_normal((M, MB, D)).astype(np.float32)
    # a learnable target: outputs of a fixed random stack
    ys = np.asarray(jax.vmap(
        lambda x: sequential(_stacked_params(seed=9), x))(jnp.asarray(xs)))

    # reference: same SGD steps on the sequential formulation
    import optax
    ref_p = _stacked_params()
    ref_tx = Sgd(learning_rate=0.1).to_optax()
    ref_opt = ref_tx.init(ref_p)

    def ref_loss(p):
        preds = jax.vmap(lambda x: sequential(p, x))(jnp.asarray(xs))
        return jnp.mean(jax.vmap(lambda a, b: jnp.mean((a - b) ** 2))(
            preds, jnp.asarray(ys)))

    losses = []
    for i in range(5):
        params, opt, loss = tr.step(params, opt, xs, ys)
        l, g = jax.value_and_grad(ref_loss)(ref_p)
        upd, ref_opt = ref_tx.update(g, ref_opt, ref_p)
        ref_p = optax.apply_updates(ref_p, upd)
        losses.append(float(loss))
        np.testing.assert_allclose(float(loss), float(l), rtol=1e-4,
                                   err_msg=f"step {i} loss diverged")
    assert losses[-1] < losses[0], losses
    for k in ("W", "b"):
        np.testing.assert_allclose(np.asarray(params[k]),
                                   np.asarray(ref_p[k]),
                                   rtol=1e-3, atol=1e-4,
                                   err_msg=f"params {k} diverged after 5 steps")


def test_pipeline_single_stage_degenerates(devices):
    mesh = make_pipeline_mesh(1)
    params = {"W": _stacked_params()["W"][:1], "b": _stacked_params()["b"][:1]}
    params = jax.device_put(params, stage_shardings(mesh, params))
    xs = jnp.asarray(np.random.default_rng(4).standard_normal(
        (3, MB, D)).astype(np.float32))
    with mesh:
        got = pipeline_apply(block_fn, params, xs, mesh)
    want = jax.vmap(lambda x: block_fn(
        jax.tree_util.tree_map(lambda a: a[0], params), x))(xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)

"""Pipeline parallelism (GPipe-style microbatch pipelining).

Beyond-reference capability (the reference has no pipeline parallelism;
SURVEY §2.4 covers only data-parallel wrappers): stacks of identical blocks
are sharded layer-wise over a mesh axis ``stage`` and microbatches stream
through the stages with ``lax.ppermute`` forwarding activations — the
standard TPU pipelining recipe (GPipe, Huang et al. 2019; the
jax-ml scaling-book "pipelining" chapter's shard_map formulation).

Design:

* Block params are STACKED on a leading (S, ...) axis and sharded
  ``P('stage')`` — each device holds one stage's weights. SPMD requires the
  per-stage computation to be the same program, so pipelining applies to
  homogeneous block stacks (the practical case: repeated transformer/dense/
  recurrent blocks). Heterogeneous first/last layers (embedding, head) run
  outside the pipelined region.
* A global batch is split into M microbatches. The wrapped step runs
  M + S - 1 ticks of ``lax.scan``; at tick t, stage s processes microbatch
  t - s (bubble fraction = (S-1)/(M+S-1)).
* The whole schedule lives inside ONE shard_map-ed jit program;
  ``jax.grad`` differentiates straight through the ppermute ring (its
  transpose is the reverse permute), so backward is pipelined too and the
  optimizer update is a per-stage-local optax step on the stacked params.
  Microbatch gradients accumulate exactly (GPipe semantics: one optimizer
  step per global batch).

``pipeline_apply`` is the schedule; ``GPipeTrainer`` wires it to a loss and
an optax transformation. Parity contract (tests/test_pipeline.py): outputs
and gradients equal the plain sequential stack to float tolerance.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

STAGE_AXIS = "stage"


def make_pipeline_mesh(n_stages: Optional[int] = None, devices=None) -> Mesh:
    """1-D mesh over the ``stage`` axis."""
    devices = list(devices if devices is not None else jax.devices())
    n = n_stages or len(devices)
    if n > len(devices):
        raise ValueError(
            f"Requested {n} pipeline stages but only {len(devices)} devices "
            "are available")
    return Mesh(np.asarray(devices[:n]), (STAGE_AXIS,))


def stage_shardings(mesh: Mesh, stacked_params):
    """NamedShardings placing each stage's slice of the stacked params on
    its device (leading axis over 'stage')."""
    return jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, P(STAGE_AXIS)), stacked_params)


def pipeline_apply(block_fn: Callable, stacked_params, x_microbatches,
                   mesh: Mesh):
    """Run M microbatches through S pipelined stages.

    ``block_fn(params_slice, x) -> y`` is one stage's computation (same
    shapes in and out). ``stacked_params`` leaves are (S, ...) and sharded
    over 'stage'; ``x_microbatches`` is (M, mb, ...) (replicated input).
    Returns (M, mb, ...) outputs of the LAST stage (replicated).
    """
    S = mesh.shape[STAGE_AXIS]
    M = x_microbatches.shape[0]

    def per_stage(params_slice, xs):
        # params_slice leaves arrive as (1, ...): this stage's weights
        p_local = jax.tree_util.tree_map(lambda a: a[0], params_slice)
        s = jax.lax.axis_index(STAGE_AXIS)
        T = M + S - 1
        # the carry becomes stage-varying after the first tick; mark the
        # initial zeros accordingly (shard_map varying-axes typing)
        zero = jax.lax.pcast(jnp.zeros_like(xs[0]), (STAGE_AXIS,),
                             to="varying")
        fwd = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            send = carry
            # activations from the previous stage (stage 0 receives junk)
            recv = jax.lax.ppermute(send, STAGE_AXIS, fwd) if S > 1 else send
            # stage 0 consumes microbatch t (while t < M); others consume recv
            mb = jnp.take(xs, jnp.clip(t, 0, M - 1), axis=0)
            x_in = jnp.where(s == 0, mb, recv)
            out = block_fn(p_local, x_in)
            # collect: the LAST stage finished microbatch t-(S-1) this tick
            ready = (s == S - 1) & (t >= S - 1)
            return out, jnp.where(ready, out, jnp.zeros_like(out))

        _, collected = jax.lax.scan(tick, zero, jnp.arange(T))
        # collected[t] holds microbatch t-(S-1): shift into order; only the
        # last stage contributed non-zeros, so a psum broadcasts the result
        outs = collected[S - 1:]
        return jax.lax.psum(outs, STAGE_AXIS)

    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(P(STAGE_AXIS), P()),
                       out_specs=P())
    return fn(stacked_params, x_microbatches)


class GPipeTrainer:
    """Train a homogeneous block stack with pipelined fwd+bwd.

    Example::

        mesh = make_pipeline_mesh(4)
        tr = GPipeTrainer(block_fn, loss_fn, updater, mesh)
        params = tr.place(stacked_params)         # shard stages
        params, opt, loss = tr.step(params, opt, x_microbatches, y_microbatches)

    ``loss_fn(y_pred, y) -> scalar`` is applied per microbatch and averaged
    (exact GPipe gradient accumulation).
    """

    def __init__(self, block_fn: Callable, loss_fn: Callable, updater,
                 mesh: Optional[Mesh] = None):
        self.block_fn = block_fn
        self.loss_fn = loss_fn
        self.mesh = mesh if mesh is not None else make_pipeline_mesh()
        self.tx = updater.to_optax() if hasattr(updater, "to_optax") \
            else updater
        self._step = None

    def place(self, stacked_params):
        return jax.device_put(stacked_params,
                              stage_shardings(self.mesh, stacked_params))

    def init_opt(self, stacked_params):
        return self.tx.init(stacked_params)

    def _build(self):
        def loss_over_pipeline(params, xs, ys):
            preds = pipeline_apply(self.block_fn, params, xs, self.mesh)
            losses = jax.vmap(self.loss_fn)(preds, ys)
            return jnp.mean(losses)

        grad_fn = jax.value_and_grad(loss_over_pipeline)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt_state, xs, ys):
            import optax
            loss, grads = grad_fn(params, xs, ys)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        return step

    def step(self, params, opt_state, x_microbatches, y_microbatches):
        if self._step is None:
            self._step = self._build()
        with self.mesh:
            return self._step(params, opt_state,
                              jnp.asarray(x_microbatches),
                              jnp.asarray(y_microbatches))


__all__ = ["GPipeTrainer", "make_pipeline_mesh", "pipeline_apply",
           "stage_shardings", "STAGE_AXIS"]

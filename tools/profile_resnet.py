"""ResNet50 MFU ablation harness (VERDICT r3 item 1).

Runs a series of on-device experiments to locate where the 85% idle time
goes: batch-size scaling, dispatch-granularity (scan-of-K inner steps vs
per-batch dispatch), fp32 vs bf16, and XLA cost analysis to validate the
FLOP denominator used by bench.py.

Each timing ends in a fetch of the run's last loss VALUE, which forces the
whole dependency chain.

Usage: python tools/profile_resnet.py [outfile]
"""

from __future__ import annotations

import dataclasses as dc
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.models import ResNet50
from deeplearning4j_tpu.nn.conf.layers import apply_constraints
from deeplearning4j_tpu.nn.graph import ComputationGraph

SIDE = 224
def _train_flops_per_img(net):
    import bench
    return 3 * bench._model_fwd_flops_per_image(net)  # graph-derived (r4)
PEAK = 197e12  # v5e bf16


def emit(out, **kw):
    line = json.dumps(kw)
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def make(batch, dtype="bfloat16"):
    conf = dc.replace(
        ResNet50(num_classes=1000, input_shape=(SIDE, SIDE, 3)).conf(),
        dtype=dtype)
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, SIDE, SIDE, 3), np.float32))
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, batch)])
    return net, x, y


def bench_per_batch(out, batch, dtype="bfloat16", steps=30, warmup=3,
                    cost=False):
    net, x, y = make(batch, dtype)
    step = net._get_jitted("train")
    if cost:
        try:
            c = step.lower(net.params, net.state, net.opt_state, net._rng,
                           [x], [y], None, None).compile().cost_analysis()
            if isinstance(c, (list, tuple)):
                c = c[0]
            emit(out, exp="cost_analysis", batch=batch, dtype=dtype,
                 xla_flops=c.get("flops"),
                 xla_flops_per_img=c.get("flops", 0) / batch,
                 bench_assumed_per_img=_train_flops_per_img(net))
        except Exception as e:
            emit(out, exp="cost_analysis", error=repr(e))
    loss = None

    def one():
        nonlocal loss
        net._rng, k = jax.random.split(net._rng)
        net.params, net.state, net.opt_state, loss = step(
            net.params, net.state, net.opt_state, k, [x], [y], None, None)

    t_c0 = time.perf_counter()
    one()
    float(loss)
    compile_s = time.perf_counter() - t_c0
    for _ in range(warmup):
        one()
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        one()
    float(loss)
    dt = time.perf_counter() - t0
    ips = steps * batch / dt
    emit(out, exp="per_batch", batch=batch, dtype=dtype, steps=steps,
         imgs_per_sec=round(ips, 1), ms_per_step=round(1000 * dt / steps, 2),
         mfu=round(ips * _train_flops_per_img(net) / PEAK, 4),
         compile_s=round(compile_s, 1))
    return ips


def bench_scan(out, batch, K=8, outer=5, dtype="bfloat16"):
    """Same train step, but K steps fused into one dispatch via lax.scan.
    If this beats per-batch dispatch, the gap is dispatch overhead, not
    device compute."""
    net, x, y = make(batch, dtype)
    vag = jax.value_and_grad(net._loss_fn, has_aux=True)

    def single(carry, _):
        params, state, opt, rng = carry
        rng, k = jax.random.split(rng)
        (loss, new_state), grads = vag(params, state, [x], [y], k, None, None)
        new_params = dict(params)
        new_opt = dict(opt)
        for n in net._layer_names:
            g = net._gnorms[n](grads[n])
            up, os_ = net._txs[n].update(g, opt[n], params[n])
            new_params[n] = apply_constraints(
                net.vertices[n][0], optax.apply_updates(params[n], up))
            new_opt[n] = os_
        return (new_params, new_state, new_opt, rng), loss

    @jax.jit
    def multi(params, state, opt, rng):
        (p, s, o, r), losses = jax.lax.scan(
            single, (params, state, opt, rng), None, length=K)
        return p, s, o, r, losses[-1]

    carry = (net.params, net.state, net.opt_state, net._rng)
    p, s, o, r, loss = multi(*carry)
    float(loss)
    p, s, o, r, loss = multi(p, s, o, r)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(outer):
        p, s, o, r, loss = multi(p, s, o, r)
    float(loss)
    dt = time.perf_counter() - t0
    ips = outer * K * batch / dt
    emit(out, exp="scan_fused", batch=batch, K=K, dtype=dtype,
         imgs_per_sec=round(ips, 1),
         ms_per_step=round(1000 * dt / (outer * K), 2),
         mfu=round(ips * _train_flops_per_img(net) / PEAK, 4))
    return ips


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/profile_resnet.jsonl"
    out = open(path, "w")
    emit(out, devices=str(jax.devices()))
    # 1. reproduce r3 baseline + XLA flop count
    bench_per_batch(out, 128, cost=True)
    # 2. batch scaling
    for b in (256, 512):
        try:
            bench_per_batch(out, b)
        except Exception as e:
            emit(out, exp="per_batch", batch=b, error=repr(e))
    # 3. dispatch-granularity ablation at batch 128 and 256
    for b in (128, 256):
        try:
            bench_scan(out, b)
        except Exception as e:
            emit(out, exp="scan_fused", batch=b, error=repr(e))
    # 4. fp32 reference point at 128
    bench_per_batch(out, 128, dtype="float32", steps=15)
    emit(out, done=True)


if __name__ == "__main__":
    main()

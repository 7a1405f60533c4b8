"""fp32 ResNet50 precision-mode ablation (VERDICT r4 #6).

Times the full train step at batch 128 under each jax matmul-precision mode
so the fp32 row in BENCH and the "use bf16" guidance are backed by numbers:

  default  — TPU lowers f32 convs to bf16xbf16->f32 MXU passes (1 pass)
  float32/highest — bf16_6x-style multi-pass emulation of true f32

Also reports the bf16 compute-dtype step for reference. Honest sync: value
fetch. Run: PYTHONPATH=. python tools/perf_fp32_ablation.py
"""
import dataclasses as dc
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models import ResNet50
from deeplearning4j_tpu.nn.graph import ComputationGraph

BATCH = 128
PEAK = 197e12


def build(dtype):
    conf = dc.replace(
        ResNet50(num_classes=1000, input_shape=(224, 224, 3)).conf(),
        dtype=dtype)
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((BATCH, 224, 224, 3), np.float32))
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, BATCH)])
    step = net._get_jitted("train")
    return net, step, x, y


def time_step(net, step, x, y, steps=15, warmup=4):
    loss = [None]

    def run_one():
        net._rng, k = jax.random.split(net._rng)
        net.params, net.state, net.opt_state, loss[0] = step(
            net.params, net.state, net.opt_state, k, [x], [y], None, None)

    for _ in range(warmup):
        run_one()
    float(loss[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        run_one()
    float(loss[0])
    return (time.perf_counter() - t0) / steps


def main():
    import bench
    fwd_flops = None
    rows = []
    for dtype, prec in [("float32", "default"), ("float32", "float32"),
                        ("bfloat16", "default")]:
        with jax.default_matmul_precision(prec):
            net, step, x, y = build(dtype)
            if fwd_flops is None:
                fwd_flops = bench._model_fwd_flops_per_image(net)
            dt = time_step(net, step, x, y)
        imgs = BATCH / dt
        tflops = 3 * fwd_flops * imgs / 1e12
        rows.append((dtype, prec, dt * 1e3, imgs, tflops, tflops * 1e12 / PEAK))
        print(f"{dtype:9s} precision={prec:8s}: {dt*1e3:6.1f} ms/step "
              f"{imgs:7.1f} imgs/s  {tflops:6.1f} TF/s  mfu {tflops*1e12/PEAK:.3f}",
              flush=True)
    return rows


if __name__ == "__main__":
    main()

"""ResNet50 perf decomposition on one real TPU chip.

Times fwd-only, fwd+bwd, and the full train step at several batch sizes so
we can see where the MFU goes. Sync: fetch a scalar VALUE derived from the
last step's output. Run: PYTHONPATH=. python tools/perf_resnet.py
"""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models import ResNet50
from deeplearning4j_tpu.nn.graph import ComputationGraph

SIDE = 224
PEAK = 197e12


def _fwd_flops(net):
    import bench
    return bench._model_fwd_flops_per_image(net)


def bench(run_one, fetch, steps=20, warmup=3):
    from deeplearning4j_tpu.obs import Stopwatch
    for _ in range(warmup):
        run_one()
    fetch()
    sw = Stopwatch().start()
    for _ in range(steps):
        run_one()
    fetch()  # the sync: reads a VALUE derived from the last step's output
    return sw.stop() / steps


def main():
    import os
    batches = [int(b) for b in os.environ.get("PERF_BATCHES", "128,256").split(",")]
    for batch in batches:
        conf = dc.replace(
            ResNet50(num_classes=1000, input_shape=(SIDE, SIDE, 3)).conf(),
            dtype="bfloat16")
        net = ComputationGraph(conf).init()
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((batch, SIDE, SIDE, 3), np.float32))
        y = jnp.asarray(np.eye(1000, dtype=np.float32)[
            rng.integers(0, 1000, batch)])

        # fwd only
        fwd = jax.jit(lambda p, s, xi: net._forward(p, s, [xi], False, None,
                                                    None)[0]["output"])
        out = [None]

        def run_fwd():
            out[0] = fwd(net.params, net.state, x)
        t_f = bench(run_fwd, lambda: float(out[0][0, 0]))

        # fwd + bwd (grad wrt params), reduced to one scalar per leaf chain
        grad_fn = jax.jit(jax.grad(
            lambda p, s, xi, yi: net._loss_fn(p, s, [xi], [yi],
                                              jax.random.key(0), None, None)[0]))

        def run_grad():
            out[0] = grad_fn(net.params, net.state, x, y)
        t_g = bench(run_grad,
                    lambda: float(out[0]["output"]["W"][0, 0]))

        # full train step (donating buffers, like the real bench)
        step = net._get_jitted("train")
        loss = [None]

        def run_step():
            net._rng, k = jax.random.split(net._rng)
            net.params, net.state, net.opt_state, loss[0] = step(
                net.params, net.state, net.opt_state, k, [x], [y], None, None)
        t_s = bench(run_step, lambda: float(loss[0]))

        train_flops = batch * 3 * _fwd_flops(net)
        print(f"batch={batch}: fwd {t_f*1e3:7.1f} ms | grad {t_g*1e3:7.1f} ms "
              f"| step {t_s*1e3:7.1f} ms | imgs/s {batch/t_s:8.1f} "
              f"| mfu {train_flops/t_s/PEAK:.3f}", flush=True)


if __name__ == "__main__":
    main()

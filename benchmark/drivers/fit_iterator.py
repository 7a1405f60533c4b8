"""Training through the public fit path: ``net.fit(iterator)`` on one chip
or ``ParallelWrapper(net, mesh).fit(iterator)`` across chips, the iterator
being the program's own ``DevicePrefetchIterator`` over the benchmark's
endless feed. One set of traffic parameters picks which:

    batch_per_chip, pool_batches, check_steps, warmup_steps,
    wrapper: "none" | "parallel_wrapper"

Set-up builds ONE network with weights from the seed, drives it through
its first ``check_steps`` steps with the window's own call and feed (those
steps are what the reference follows), and hands that same object to the
window. The window's clock stops after ``block_until_ready`` of the
parameters, and items are counted from the steps that ran
(``net.iteration``), not from the batches handed out."""

from __future__ import annotations

import gc
import math

import numpy as np

from harness import check as checking
from harness import feed


def _make_pool(cfg: dict, batch: int, n: int, seed: int, reference):
    """``n`` host batches of ``batch`` seeded images (N(0,1) float32) with
    one-hot labels, drawn on the device, one jitted call a batch."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import DataSet

    h, w, c = cfg["input_shape"]
    classes = cfg["num_classes"]

    @jax.jit
    def draw(key):
        kx, ky = jax.random.split(key)
        return (jax.random.normal(kx, (batch, h, w, c), jnp.float32),
                jax.random.randint(ky, (batch,), 0, classes))

    key = jax.random.fold_in(reference.seed_key(seed), 0x5EED)
    eye = np.eye(classes, dtype=np.float32)
    pool = []
    for i in range(n):
        x, ids = draw(jax.random.fold_in(key, i))
        pool.append(DataSet(np.asarray(x), eye[np.asarray(ids)]))
    return pool


class Session:
    pass


def setup(cell, devices, seed: int, say=print) -> Session:
    import jax
    from deeplearning4j_tpu.perf import DevicePrefetchIterator

    s = Session()
    cfg, tr = cell.config, cell.traffic
    s.cell, s.cfg, s.tr, s.seed = cell, cfg, tr, seed
    s.batch = tr["batch_per_chip"] * len(devices)
    laps, t = {}, feed.clock()
    s.pool = _make_pool(cfg, s.batch, tr["pool_batches"], seed,
                        cell.reference)
    laps["pool"], t = feed.clock() - t, feed.clock()
    params0 = cell.reference.init_params(cfg, seed)
    s.net = cell.build(cfg, params0)
    laps["weights_and_build"], t = feed.clock() - t, feed.clock()
    s.mesh = None
    if tr["wrapper"] == "parallel_wrapper":
        from deeplearning4j_tpu.parallel import ParallelWrapper
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        s.mesh = make_mesh(dp=len(devices), tp=1, devices=devices)
        s.trainer = ParallelWrapper(s.net, mesh=s.mesh)
    elif tr["wrapper"] == "none":
        if len(devices) != 1:
            raise ValueError("plain fit drives one chip")
        s.trainer = s.net
    else:
        raise ValueError(f"unknown wrapper {tr['wrapper']!r}")

    def fit(source, on_batch=None):
        """THE call: set-up's first steps and the window both go here."""
        stream = feed.TimedStream(
            DevicePrefetchIterator(source, mesh=s.mesh), on_batch)
        with feed.span("fit_call"):
            s.trainer.fit(stream)
        return stream

    s.fit = fit

    # the first steps, one batch a call so that each loss can be read
    adapter = cell.adapter
    steps = tr["check_steps"]
    program = {"losses": []}
    for i in range(steps):
        fit(feed.PoolFeed(s.pool, start=i, limit=1))
        program["losses"].append(float(s.net.score()))
        if i == 0:
            grads = adapter.first_gradient_flat(s.net, cfg)
            program["grad_norms"] = checking.leaf_norms(grads)
            del grads
    program["delta_norms"] = checking.leaf_delta_norms(
        adapter.params_flat(s.net), params0)
    del params0
    s.program = program
    laps["first_steps"], t = feed.clock() - t, feed.clock()
    say(f"first steps: losses {program['losses']}")
    # a few more through the endless feed: queues and allocator warm
    fit(feed.PoolFeed(s.pool, start=steps, limit=tr["warmup_steps"]))
    jax.block_until_ready(s.net.params)
    laps["warmup"] = feed.clock() - t
    say("set-up laps (s): " + ", ".join(f"{k} {v:.2f}"
                                        for k, v in laps.items()))
    s.compiles_before = s.net.compile_watch.compiles("train")
    return s


def run_window(s: Session, seconds: float, trace_slice=None) -> dict:
    import jax

    it0 = s.net.iteration
    if trace_slice is not None:
        trace_slice.arm()
    t0 = feed.clock()
    source = feed.PoolFeed(s.pool, start=s.tr["check_steps"]
                           + s.tr["warmup_steps"], deadline=t0 + seconds)
    stream = s.fit(source, trace_slice.tick if trace_slice else None)
    jax.block_until_ready(s.net.params)
    elapsed = feed.clock() - t0
    if trace_slice is not None:
        trace_slice.finish()
    steps = s.net.iteration - it0
    last = float(s.net.score())
    compiles = s.net.compile_watch.compiles("train") - s.compiles_before
    items = steps * s.batch
    return {"end_to_end": {"train_items_per_s": items / elapsed},
            "items": items, "elapsed_s": elapsed, "attempted": steps,
            # a loss that is not finite poisons every later step
            "failed": 0 if math.isfinite(last) else steps,
            "steps": steps, "input_wait_s": stream.wait_s,
            "last_loss": last, "compiles_in_window": compiles}


def _reference(s: Session, precision: str) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    cell, cfg = s.cell, s.cfg
    batches = [(ds.features, ds.labels)
               for ds in s.pool[:s.tr["check_steps"]]]
    params = cell.reference.init_params(cfg, s.seed)
    place = None
    if s.mesh is not None:
        # rows split over the chips, weights on each: one batch's arithmetic
        everywhere = NamedSharding(s.mesh, P())
        params = {k: jax.device_put(a, everywhere) for k, a in params.items()}

        def place(a):
            spec = P(s.mesh.axis_names[0], *([None] * (a.ndim - 1)))
            return jax.device_put(a, NamedSharding(s.mesh, spec))
    return cell.reference.train_steps(cfg, params, batches,
                                      precision=precision, place=place)


def check(s: Session, say=print):
    """After the window: free the program, then let the reference follow
    the same first steps from the same seeded weights and rows."""
    s.net = s.trainer = s.fit = None
    gc.collect()
    s.reference = _reference(s, "highest")
    return checking.compare_training(s.program, s.reference,
                                     s.cell.cell["limits"], say)


def control(s: Session, say=print):
    """The reference in the program's place, computed in the precision
    below the configuration's (``control_precision`` of its file): has to
    come out as not correct. Run after ``check``; no benchmark run does."""
    numbers = _reference(s, s.cfg["control_precision"])
    return checking.compare_training(numbers, s.reference,
                                     s.cell.cell["limits"], say)

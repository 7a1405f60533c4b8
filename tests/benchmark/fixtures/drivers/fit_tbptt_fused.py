"""Character-level training through ``MultiLayerNetwork.fit_tbptt_fused``:
every tBPTT window of one (sequences, length, vocabulary) batch in one
dispatch, then ``net.score()``, in a loop over a small pool of seeded host
batches until the deadline. The driver copies each batch to the device
itself, before the call, so that the copy can be timed: that is the
``input_wait`` of this path, and nothing overlaps it.

The text is a seeded Markov chain over the vocabulary (each character has
a few likely successors), so there is something to learn and the loss
stays finite under RmsProp; labels are the next character.

Set-up builds ONE network with weights from the seed, drives it through
its first ``check_steps`` dispatches with the window's own call (what the
reference follows), and hands that same object to the window."""

from __future__ import annotations

import gc
import math

import numpy as np

from harness import check as checking
from harness import feed


def _make_pool(cfg: dict, tr: dict, seed: int):
    """``pool_batches`` host pairs (x, y) of contiguous one-hot float32."""
    v = cfg["vocab_size"]
    b, t = tr["sequences"], tr["sequence_length"]
    k = tr["text"]["successors"]
    rng = np.random.default_rng([int(seed), 0xC4A2])
    successors = np.stack([rng.permutation(v)[:k] for _ in range(v)])
    eye = np.eye(v, dtype=np.float32)
    pool = []
    for _ in range(tr["pool_batches"]):
        ids = np.empty((b, t + 1), np.int32)
        ids[:, 0] = rng.integers(0, v, b)
        pick = rng.choice(k, size=(b, t), p=tr["text"]["probabilities"])
        for i in range(t):
            ids[:, i + 1] = successors[ids[:, i], pick[:, i]]
        pool.append((eye[ids[:, :-1]], eye[ids[:, 1:]]))
    return pool


class Session:
    pass


def setup(cell, devices, seed: int, say=print) -> Session:
    import jax

    if len(devices) != 1:
        raise ValueError("fit_tbptt_fused drives one chip")
    s = Session()
    cfg, tr = cell.config, cell.traffic
    s.cell, s.cfg, s.tr, s.seed = cell, cfg, tr, seed
    if tr["sequence_length"] % cfg["tbptt_length"]:
        raise ValueError("sequence_length must be whole tBPTT windows")
    s.windows = tr["sequence_length"] // cfg["tbptt_length"]
    s.items_per_dispatch = tr["sequences"] * tr["sequence_length"]
    laps, t = {}, feed.clock()
    s.pool = _make_pool(cfg, tr, seed)
    laps["pool"], t = feed.clock() - t, feed.clock()
    params0 = cell.reference.init_params(cfg, seed)
    s.net = cell.build(cfg, params0)
    laps["weights_and_build"], t = feed.clock() - t, feed.clock()
    s.input_wait_s = 0.0

    def dispatch(i: int) -> float:
        """THE call: set-up's first dispatches and the window's."""
        x, y = s.pool[i % len(s.pool)]
        t0 = feed.clock()
        with feed.span("input_wait"):
            xd, yd = jax.block_until_ready(
                (jax.device_put(x, devices[0]), jax.device_put(y, devices[0])))
        s.input_wait_s += feed.clock() - t0
        with feed.span("fit_call"):
            s.net.fit_tbptt_fused(xd, yd)
            return float(s.net.score())

    s.dispatch = dispatch
    adapter = cell.adapter
    program = {"losses": []}
    for i in range(tr["check_steps"]):
        program["losses"].append(dispatch(i))
        if i == 0:
            program["grad_norms"] = checking.leaf_norms(
                adapter.first_gradient_flat(s.net, cfg))
    program["delta_norms"] = checking.leaf_delta_norms(
        adapter.params_flat(s.net), params0)
    del params0
    s.program = program
    say(f"first dispatches: losses {program['losses']}")
    s.done = tr["check_steps"]
    for _ in range(tr["warmup_steps"]):
        dispatch(s.done)
        s.done += 1
    laps["first_dispatches"] = feed.clock() - t
    say("set-up laps (s): " + ", ".join(f"{k} {v:.2f}"
                                        for k, v in laps.items()))
    s.compiles_before = s.net.compile_watch.compiles("tbptt_fused")
    return s


def run_window(s: Session, seconds: float, trace_slice=None) -> dict:
    import jax

    s.input_wait_s = 0.0
    it0 = s.net.iteration
    if trace_slice is not None:
        trace_slice.arm()
    t0 = feed.clock()
    dispatches = failed = 0
    last = float("nan")
    while feed.clock() - t0 < seconds:
        last = s.dispatch(s.done + dispatches)
        dispatches += 1
        failed += 0 if math.isfinite(last) else 1
        if trace_slice is not None:
            trace_slice.tick()
    jax.block_until_ready(s.net.params)
    elapsed = feed.clock() - t0
    if trace_slice is not None:
        trace_slice.finish()
    updates = s.net.iteration - it0
    if updates != dispatches * s.windows:
        raise RuntimeError(f"{dispatches} dispatches ran {updates} updates, "
                           f"expected {dispatches * s.windows}")
    items = dispatches * s.items_per_dispatch
    compiles = (s.net.compile_watch.compiles("tbptt_fused")
                - s.compiles_before)
    return {"end_to_end": {"train_items_per_s": items / elapsed},
            "items": items, "elapsed_s": elapsed, "attempted": dispatches,
            "failed": failed, "steps": dispatches,
            "input_wait_s": s.input_wait_s, "last_loss": last,
            "compiles_in_window": compiles}


def _reference(s: Session, precision: str) -> dict:
    cell, cfg = s.cell, s.cfg
    params = cell.reference.init_params(cfg, s.seed)
    return cell.reference.train_steps(cfg, params,
                                      s.pool[:s.tr["check_steps"]],
                                      precision=precision)


def check(s: Session, say=print):
    """After the window: free the program, then let the reference follow
    the same first dispatches from the same seeded weights and rows."""
    s.net = s.dispatch = None
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

"""Training a token model through the public fit path:
``net.fit(DevicePrefetchIterator(feed))`` on one chip, the feed handing out
seeded (ids, next ids) batches of integers without end. Traffic parameters:

    sequences_per_step, sequence_length, pool_batches, check_steps,
    warmup_steps, wrapper: "none"

The contract is ``fit_iterator``'s: set-up builds ONE network with weights
from the seed, drives it through its first ``check_steps`` steps with the
window's own call and feed (those steps are what the reference follows),
and hands that same object to the window. The window's clock stops after
``block_until_ready`` of the parameters; items are TOKENS, counted from the
steps that ran (``net.iteration``): steps x sequences x length.

What differs is size. The weights and Adam's state fill most of the chip,
so nothing is held twice: the seeded weights are handed to the network, not
copied (``ComputationGraph.init(params=)``); the first gradient is read as
norms of Adam's first moment; the parameters' change is taken against
starting weights made again from the seed; and the reference's step is
given its arguments up. A traced run also keeps, for the per-layer
readers, the HLO text of the step's executable (``cell.program_view[
"hlo_text"]``: the device trace names operations by instruction, the text
says which layer each came from) and the routed layers' load counters over
the window and over the traced slice's own steps; and it keeps the host at
most two steps ahead of the device and lets the device finish what is
queued before the profiler starts and before it stops (``_SliceSwitch``)."""

from __future__ import annotations

import gc
import math
import os

import numpy as np

from harness import check as checking
from harness import feed


def _make_pool(cfg: dict, sequences: int, length: int, n: int, seed: int,
               reference):
    """``n`` host batches of ``sequences`` x (``length`` + 1) seeded ids,
    uniform over the vocabulary slice, drawn on the device in one call;
    features are ids[:, :-1], labels ids[:, 1:]."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import DataSet

    key = jax.random.fold_in(reference.seed_key(seed), 0x5EED)
    ids = np.asarray(jax.jit(lambda k: jax.random.randint(
        k, (n, sequences, length + 1), 0, cfg["vocab_size"], jnp.int32))(key))
    return [DataSet(np.ascontiguousarray(b[:, :-1]),
                    np.ascontiguousarray(b[:, 1:])) for b in ids]


class Session:
    pass


def setup(cell, devices, seed: int, say=print) -> Session:
    import jax
    from deeplearning4j_tpu.obs import get_registry, watch_moe
    from deeplearning4j_tpu.perf import DevicePrefetchIterator

    s = Session()
    cfg, tr = cell.config, cell.traffic
    s.cell, s.cfg, s.tr, s.seed = cell, cfg, tr, seed
    if tr["wrapper"] != "none" or len(devices) != 1:
        raise ValueError("this driver runs plain fit on one chip")
    if tr["sequence_length"] != cfg["sequence_length"]:
        raise ValueError("traffic and configuration disagree on the "
                         "sequence length")
    s.sequences, s.length = tr["sequences_per_step"], tr["sequence_length"]
    laps, t = {}, feed.clock()
    s.pool = _make_pool(cfg, s.sequences, s.length, tr["pool_batches"], seed,
                        cell.reference)
    laps["pool"], t = feed.clock() - t, feed.clock()
    s.net = cell.build(cfg, cell.reference.init_params(cfg, seed))
    s.moe_watch = watch_moe(get_registry(), s.net)
    laps["weights_and_build"], t = feed.clock() - t, feed.clock()

    def fit(source, on_batch=None):
        """THE call: set-up's first steps and the window both go here."""
        stream = feed.TimedStream(DevicePrefetchIterator(source), on_batch)
        with feed.span("fit_call"):
            s.net.fit(stream)
        return stream

    s.fit = fit

    adapter = cell.adapter
    steps = tr["check_steps"]
    program = {"losses": []}
    for i in range(steps):
        fit(feed.PoolFeed(s.pool, start=i, limit=1))
        program["losses"].append(float(s.net.score()))
        if i == 0:
            scale = 1.0 / (1.0 - cfg["updater"]["beta1"])
            program["grad_norms"] = {
                k: v * scale for k, v in checking.leaf_norms(
                    adapter.first_moment_flat(s.net)).items()}
    # against starting weights made again from the seed: nothing was kept
    program["delta_norms"] = cell.reference.change_norms(
        cfg, seed, adapter.params_flat(s.net))
    s.program = program
    laps["first_steps"], t = feed.clock() - t, feed.clock()
    say(f"first steps: losses {program['losses']}")
    fit(feed.PoolFeed(s.pool, start=steps, limit=tr["warmup_steps"]))
    jax.block_until_ready(s.net.params)
    laps["warmup"] = feed.clock() - t
    say("set-up laps (s): " + ", ".join(f"{k} {v:.2f}"
                                        for k, v in laps.items()))
    s.compiles_before = s.net.compile_watch.compiles("train")
    return s


_backend_compiles = []


def _count_backend_compiles() -> list:
    """Every XLA compile of this process from the first call on, as
    ``jax.monitoring`` reports them (a hit of the persistent cache is not
    one)."""
    import jax

    if not _backend_compiles:
        _backend_compiles.append(0)

        def on_duration(name, _seconds, **_):
            if name.endswith("backend_compile_duration"):
                _backend_compiles[0] += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
    return _backend_compiles


def _step_hlo_text(net, batch) -> tuple:
    """(HLO text of the train step's executable, XLA compiles the call
    made: 0). ``lower`` at the shapes the step ran at finds the lowering
    that the step's own call made in jax's in-memory cache, and that
    lowering holds its executable: ``compile()`` hands back the very
    program that ran and was traced, and compiles nothing."""
    import jax

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    compiles = _count_backend_compiles()
    before = compiles[0]
    args = (struct(net.params), struct(net.state), struct(net.opt_state),
            struct(net._rng), [struct(batch.features)],
            [struct(batch.labels)], None, None)
    text = net._get_jitted("train").lower(*args).compile().as_text()
    return text, compiles[0] - before


class _SliceSwitch:
    """The harness's ``TraceSlice.tick`` for a program whose host runs many
    steps ahead of the device. Here the host queues about nine steps of
    0.6 s, and the profiler's start and stop hold it for tens of seconds
    (a step is 18,000 operations). The harness takes those holds out of the
    elapsed time, which is right only if the device stands still while the
    host does. So a traced run (1) waits at every turn for the step before
    the last one dispatched: at most two steps in flight, the device never
    without work; (2) lets the device finish what is queued before the
    profiler starts and before it stops; (3) reads the routed layers'
    counters at both moments, so that the slice's own steps say how many
    pairs its grouped products had."""

    def __init__(self, trace_slice, net, counters):
        self.slice, self.net, self.counters = trace_slice, net, counters
        self.marks = []              # (iteration, counters) at start, stop
        self._before_last = None

    def mark(self) -> None:
        import jax
        jax.block_until_ready(self.net.params)
        self.marks.append((self.net.iteration, self.counters(self.net)))

    def tick(self) -> None:
        import jax
        ts = self.slice
        if ts.done:
            return
        if self._before_last is not None:
            jax.block_until_ready(self._before_last)
        self._before_last = self.net._score   # the last dispatched step's
        now = feed.clock() - ts._t0
        if (now >= ts.begin_s if ts._on_at is None
                else now - ts._on_at >= ts.length_s):
            self.mark()
        ts.tick()

    def slice_view(self) -> dict:
        (it0, c0), (it1, c1) = self.marks
        return {"steps": it1 - it0, "layers": _counters_delta(c0, c1)}


def _counters_delta(before: dict, after: dict) -> dict:
    return {layer: {
        "expert_tokens": [b - a for a, b in zip(before[layer]["expert_tokens"],
                                                c["expert_tokens"])],
        "pairs_held": c["pairs_held"] - before[layer]["pairs_held"],
        "pairs_dropped": c["pairs_dropped"] - before[layer]["pairs_dropped"]}
        for layer, c in after.items()}


def run_window(s: Session, seconds: float, trace_slice=None) -> dict:
    import jax
    from deeplearning4j_tpu.obs import get_registry

    adapter = s.cell.adapter
    it0 = s.net.iteration
    moe0 = adapter.moe_counters(s.net)
    switch = None
    if trace_slice is not None:
        switch = _SliceSwitch(trace_slice, s.net, adapter.moe_counters)
        trace_slice.arm()
    t0 = feed.clock()
    source = feed.PoolFeed(s.pool, start=s.tr["check_steps"]
                           + s.tr["warmup_steps"], deadline=t0 + seconds)
    stream = s.fit(source, switch.tick if switch else None)
    jax.block_until_ready(s.net.params)
    elapsed = feed.clock() - t0
    if switch is not None:
        if len(switch.marks) == 1:       # the window ended inside the slice
            switch.mark()
        trace_slice.finish()
    steps = s.net.iteration - it0
    last = float(s.net.score())
    compiles = s.net.compile_watch.compiles("train") - s.compiles_before
    items = steps * s.sequences * s.length
    # the routed layers' counters: read once, here, after the clock
    moe = _counters_delta(moe0, adapter.moe_counters(s.net))
    scraped = get_registry().as_dict()          # runs watch_moe's callback
    dropped = scraped.get("moe_dropped_tokens_total", {}).get("value", 0.0)
    view = {"moe": moe, "tokens_per_step": s.sequences * s.length}
    hlo_compiles = 0
    if switch is not None:
        if len(switch.marks) == 2:
            view["moe_slice"] = switch.slice_view()
        view["hlo_text"], hlo_compiles = _step_hlo_text(s.net, s.pool[0])
        # beside the trace, for benchmark/scope_table.py
        with open(os.path.join(trace_slice.out_dir, "step_hlo.txt"), "w",
                  encoding="utf-8") as f:
            f.write(view["hlo_text"])
    s.cell.program_view = view
    return {"end_to_end": {"train_items_per_s": items / elapsed},
            "items": items, "elapsed_s": elapsed, "attempted": steps,
            # a loss that is not finite poisons every later step; a pair
            # on a held expert that no product computed is a failed step
            "failed": (0 if math.isfinite(last) and not dropped
                       and not any(c["pairs_dropped"] for c in moe.values())
                       else steps),
            "steps": steps, "input_wait_s": stream.wait_s,
            "last_loss": last, "compiles_in_window": compiles,
            "compiles_for_hlo_text": hlo_compiles,
            "moe_dropped_tokens_total": dropped,
            "moe_tokens_held_total":
                scraped.get("moe_tokens_held_total", {}).get("value", 0.0),
            "moe_pairs_held_in_window":
                {layer: c["pairs_held"] for layer, c in moe.items()}}


def _reference(s: Session, precision: str) -> dict:
    cell, cfg = s.cell, s.cfg
    batches = [(ds.features, ds.labels)
               for ds in s.pool[:s.tr["check_steps"]]]
    return cell.reference.train_steps(
        cfg, cell.reference.init_params(cfg, s.seed), batches,
        precision=precision,
        seed=s.seed)


def check(s: Session, say=print):
    """After the window: free the program, then let the reference follow
    the same first steps from the same seeded weights and ids."""
    s.net = s.fit = s.moe_watch = None
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

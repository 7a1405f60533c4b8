"""The training engine under both network classes.

``MultiLayerNetwork`` (a sequential stack, parameters in a list by layer
index) and ``ComputationGraph`` (a DAG, parameters in a dict by vertex name)
are fronts over ``Network``: the regularization penalty, the optimizer
application, the jitted train and tBPTT steps, the cache of jitted programs,
the handling of one batch and what follows its dispatch live here, once.
``run_epochs`` is the one epoch loop: both classes' ``fit``,
``ParallelWrapper.fit`` and ``ClusterTrainer.fit_local_shard`` call it.

What a network class keeps is what differs between a stack and a DAG:

- ``_param_layers``: the ``(key, layer)`` pairs of the layers that own
  parameters, in order (``params[key]`` reads a list and a dict alike), and
  ``_collect(entries, like)``: a container like ``like`` with ``entries``
  replaced;
- ``_forward`` and ``_forward_loss``: the forward pass and the loss over its
  output layers (without the penalty);
- ``_augment``: which inputs the on-device augmentation applies to;
- ``_stage(ds)``: a ``DataSet`` / ``MultiDataSet`` as the step's
  ``(x, y, fmask, lmask)`` (arrays or lists of arrays), ``_rows(x)`` and
  ``_sample(x)`` (the one-row slice listeners read activations from);
- ``_wants_tbptt(x)`` and ``_windows(x, y, fmask, lmask)``: whether and how a
  batch is cut into tBPTT windows;
- ``_make_program(kind)``: the jitted programs other than ``train`` and
  ``tbptt`` (``output``, ``score``, ``rnn_step``, ...).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.nn.conf.layers import (_bias_keys, apply_constraints,
                                               regularization_coefficients,
                                               resolve_param_path)
from deeplearning4j_tpu.obs.owners import (LOSS_PENALTY, LOSS_SCORE, OPTIM,
                                           PARAMS_CAST)
from deeplearning4j_tpu.obs.registry import count_train_steps
from deeplearning4j_tpu.obs.trace import get_tracer
from deeplearning4j_tpu.optimize.fused_update import bucketed_apply
from deeplearning4j_tpu.optimize.listeners import any_reads_features
from deeplearning4j_tpu.optimize.updaters import gradient_normalization
from deeplearning4j_tpu.perf.compile_watch import CompileWatch


def _compute_dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16, "float64": jnp.float64}[name]


def _f32(a):
    return a.astype(jnp.float32) if a.dtype in (jnp.bfloat16,
                                                jnp.float16) else a


class Network:
    """What ``MultiLayerNetwork`` and ``ComputationGraph`` share (see the
    module docstring for what each of them provides)."""

    def __init__(self, conf):
        """The subclass has set ``_param_layers`` (and whatever its
        ``_collect`` reads) before it calls this."""
        self.conf = conf
        self._dtype = _compute_dtype(conf.dtype)
        # per-layer optax transforms (reference BaseMultiLayerUpdater
        # blocks). Every layer gets its updater: a layer whose init()
        # returns an empty param dict makes the transform a no-op, and
        # layers with non-regularizable trainables (e.g. batchnorm
        # gamma/beta) still train.
        updaters = {k: (l.updater if getattr(l, "updater", None) is not None
                        else conf.updater) for k, l in self._param_layers}
        self._updaters = self._collect(updaters)
        self._txs = self._collect({k: u.to_optax()
                                   for k, u in updaters.items()})
        self._gnorms = self._collect({
            k: gradient_normalization(
                getattr(l, "gradient_normalization", None),
                getattr(l, "gradient_normalization_threshold", 1.0))
            for k, l in self._param_layers})
        self.params = None
        self.state = None
        self.opt_state = None
        self.listeners: list = []
        self.iteration = 0
        self.epoch = 0
        self.last_batch_size: Optional[int] = None
        self._score = None
        self._rng = None
        self._jit_cache = {}
        # per-network compile/dispatch counters (perf/compile_watch.py);
        # every jitted program minted by _get_jitted records here
        self.compile_watch = CompileWatch(type(self).__name__)
        self._rnn_carries = None  # stateful rnnTimeStep carries
        self._last_features = None  # last fit minibatch (listener sampling)
        # set by checkpoint.CheckpointManager.restore_latest; consumed by
        # the next fit() for exact-step resume (skip already-seen batches).
        # _restored_from is informational provenance (also set by
        # restore_best) and never consumed.
        self._resume_state = None
        self._restored_from = None
        # compressed gradient collectives (parallel/compress.py): the
        # scheme config plus device-resident error-feedback state threaded
        # through the jitted step next to opt_state. Set via
        # enable_grad_compression / ParallelWrapper(grad_compression=);
        # restored from checkpoint metadata by utils/serialization.
        self.grad_compression = None
        self.compress_state = None
        # on-device augmentation (datasets/augment.py): applied to the
        # features INSIDE the jitted train step, seeded from the step rng.
        # Part of the jit-cache key — see set_augmentation.
        self.augmentation = None

    # ------------------------------------------------------- small surface
    def set_augmentation(self, augmentation):
        """Enable on-device augmentation (a frozen
        ``datasets.augment.ImageAugmentation``, or None to disable): the
        train step augments its feature batch in-graph (a graph: its 4-D
        NHWC inputs), seeded from the step rng key, so epochs stay
        deterministic and resume replays bitwise. Inference/score paths
        are unaffected (no rng there)."""
        self.augmentation = augmentation
        return self

    def num_params(self) -> int:
        if self.params is None:
            return 0
        return sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(self.params))

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)

    def score(self) -> Optional[float]:
        """Most recent minibatch score (reference Model.score())."""
        return None if self._score is None else float(self._score)

    def rnn_clear_previous_state(self):
        """reference rnnClearPreviousState."""
        self._rnn_carries = None

    def rnn_get_previous_state(self):
        return self._rnn_carries

    def _seeded_key(self, seed: Optional[int], validate: Optional[bool]):
        """``init``'s first half: ``conf.validate()``, so misconfigurations
        fail here with a layer-named message instead of seconds later
        inside an XLA trace (opt out per call with ``validate=False`` or
        process-wide with ``DL4J_TPU_VALIDATE=0``), then the key the
        parameters are drawn from."""
        if validate is None:
            import os
            validate = os.environ.get("DL4J_TPU_VALIDATE", "1") != "0"
        if validate:
            self.conf.validate()
        return jax.random.key(self.conf.seed if seed is None else seed)

    def _init_drawn(self, rng):
        """``init``'s second half: parameters and state drawn by the
        subclass's ``_draw(rng)``, and the optimizer state for them."""
        self.params, self.state, self._rng = self._draw(rng)
        self.opt_state = self.init_opt_state(self.params)
        return self

    def init_opt_state(self, params):
        """Fresh optimizer state for ``params``: moment tensors take their
        parameters' shapes and shardings."""
        return self._collect({k: self._txs[k].init(params[k])
                              for k, _ in self._param_layers})

    def _zero_carries(self, batch: int):
        return self._collect({
            k: l.init_carry(batch) if hasattr(l, "init_carry") else {}
            for k, l in self._param_layers})

    def score_dataset(self, ds) -> float:
        """Loss on a dataset (reference score(DataSet))."""
        x, y, fm, lm = self._stage(ds)
        return float(self._get_jitted("score")(self.params, self.state, x,
                                               y, fm, lm))

    # ---------------------------------------------------------------- loss
    def _to_compute_dtype(self, params, inputs):
        """``params`` and the floating ones of ``inputs`` (an array or a
        list of them) in the compute dtype: the master weights' cast (and,
        under autodiff, the gradient's cast back) and the features', which
        no layer owns, under the ``params.cast`` scope (obs/owners.py)."""
        cdt = self._dtype
        if cdt == jnp.float32:
            return params, inputs
        with jax.named_scope(PARAMS_CAST):
            return jax.tree_util.tree_map(
                lambda a: a.astype(cdt)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                (params, inputs))

    def _regularization(self, params):
        """L1/L2 penalty (reference BaseLayer.calcL2/calcL1; score term added in
        BaseOutputLayer.computeScore fullNetworkL1/L2)."""
        total = 0.0
        for key, layer in self._param_layers:
            p = params[key]
            l1, l2, l1b, l2b = regularization_coefficients(layer)
            for name in layer.regularizable():
                w = resolve_param_path(p, name)
                if w is not None:
                    w = _f32(w)
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(w * w)
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(w))
            if l1b or l2b:
                # _bias_keys, not just "b": nested attention biases (q/b,
                # k/b, ...) are penalized as attention.py's docstring claims
                for bk in _bias_keys(layer, p):
                    b = _f32(resolve_param_path(p, bk))
                    if l2b:
                        total = total + 0.5 * l2b * jnp.sum(b * b)
                    if l1b:
                        total = total + l1b * jnp.sum(jnp.abs(b))
        return total

    @staticmethod
    def _output_score(layer, y, preout, lmask, forward_mask):
        """One output layer's loss: labels in f32, and the mask the forward
        pass carried to the layer where the batch brings no label mask."""
        with jax.named_scope(LOSS_SCORE):
            return layer.compute_score(
                _f32(y), preout,
                lmask if lmask is not None else forward_mask)

    def _loss_fn(self, params, state, x, y, rng, fmask, lmask, carries=None):
        """Loss over the output layers plus the penalty; with ``carries``
        the recurrent layers run their stateful path and the aux also
        returns the new carries."""
        if self.augmentation is not None and rng is not None:
            # in-graph augmentation off a split of the STEP key: train-mode
            # only (score/eval call with rng=None) and deterministic per
            # (seed, step) — the dropout reproducibility contract
            rng, ak = jax.random.split(rng)
            x = self._augment(x, ak)
        loss, aux = self._forward_loss(params, state, x, y, rng, fmask,
                                       lmask, carries)
        with jax.named_scope(LOSS_PENALTY):
            return loss + self._regularization(params), aux

    # ---------------------------------------------------------- the steps
    def _apply_updates(self, params, grads, opt_state):
        """Optimizer application shared by the standard, fused and tBPTT
        steps.

        Per-layer update chains are kept (vs one whole-tree optax
        transform, measured r4: no step-time difference on ResNet50) —
        they preserve wrapper-layer constraints, tensor-parallel opt-state
        placement, and checkpoint compatibility. Small leaves additionally
        run through ``bucketed_apply`` (optimize/fused_update.py), which
        computes the identical math over one concatenated vector per
        updater config so XLA emits a handful of fusions instead of one
        per leaf (ResNet50: 244 small fusions ~8 ms/step).

        Everything here lies under the ``optim.update`` scope
        (obs/owners.py): the optimizer is the owner of these operations in
        every step program, as a layer is of its own."""
        with jax.named_scope(OPTIM):
            results = bucketed_apply([k for k, _ in self._param_layers],
                                     self._updaters, self._txs, self._gnorms,
                                     params, grads, opt_state)
            new_params, new_opt = {}, {}
            for k, layer in self._param_layers:
                updates, new_opt[k] = results[k]
                new_params[k] = apply_constraints(
                    layer, optax.apply_updates(params[k], updates))
        return (self._collect(new_params, params),
                self._collect(new_opt, opt_state))

    def _make_train_step(self):
        value_and_grad = jax.value_and_grad(self._loss_fn, has_aux=True)
        comp = self.grad_compression
        if comp is not None:
            # compressed collectives (parallel/compress.py): the encode→
            # decode + error-feedback residual update runs INSIDE the
            # compiled step on the gradient pytree; cstate is donated
            # alongside opt_state
            def train_step_compressed(params, state, opt_state, cstate, rng,
                                      inputs, labels, fmasks, lmasks):
                (loss, new_state), grads = value_and_grad(
                    params, state, inputs, labels, rng, fmasks, lmasks)
                grads, cstate = comp.apply(grads, cstate)
                new_params, new_opt = self._apply_updates(params, grads,
                                                          opt_state)
                return new_params, new_state, new_opt, cstate, loss

            return jax.jit(train_step_compressed, donate_argnums=(0, 1, 2, 3))

        # the function's name is the program's in a profiler trace
        # (jit_train_step), and its arguments' names are those of the
        # compiled program's parameters: keep them stable
        def train_step(params, state, opt_state, rng, inputs, labels, fmasks,
                       lmasks):
            (loss, new_state), grads = value_and_grad(
                params, state, inputs, labels, rng, fmasks, lmasks)
            new_params, new_opt = self._apply_updates(params, grads, opt_state)
            return new_params, new_state, new_opt, loss

        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    def _make_tbptt_step(self):
        """One tBPTT window update (reference doTruncatedBPTT —
        MultiLayerNetwork.java:1393, ComputationGraph.java:1158). Incoming
        carries are constants of the traced program, so gradients truncate
        at the window boundary exactly like the reference's stored-state
        scheme."""
        value_and_grad = jax.value_and_grad(self._loss_fn_tbptt, has_aux=True)
        comp = self.grad_compression
        if comp is not None:
            def tbptt_step_compressed(params, state, opt_state, cstate,
                                      carries, rng, inputs, labels, fmasks,
                                      lmasks):
                (loss, (new_state, new_carries)), grads = value_and_grad(
                    params, state, carries, inputs, labels, rng, fmasks,
                    lmasks)
                grads, cstate = comp.apply(grads, cstate)
                new_params, new_opt = self._apply_updates(params, grads,
                                                          opt_state)
                return (new_params, new_state, new_opt, cstate, new_carries,
                        loss)

            return jax.jit(tbptt_step_compressed,
                           donate_argnums=(0, 1, 2, 3, 4))

        def tbptt_step(params, state, opt_state, carries, rng, inputs, labels,
                       fmasks, lmasks):
            (loss, (new_state, new_carries)), grads = value_and_grad(
                params, state, carries, inputs, labels, rng, fmasks, lmasks)
            new_params, new_opt = self._apply_updates(params, grads,
                                                      opt_state)
            return new_params, new_state, new_opt, new_carries, loss

        return jax.jit(tbptt_step, donate_argnums=(0, 1, 2, 3))

    # ------------------------------------------------------- the jit cache
    def _get_jitted(self, kind, key=()):
        # the compression scheme AND the augmentation config are part of
        # the cache key: enabling (or changing) either mints a fresh step
        # instead of reusing the old compiled program under the same name
        k = (kind, self.grad_compression, self.augmentation) + tuple(key)
        fn = self._jit_cache.get(k)
        if fn is None:
            if kind == "train":
                fn = self._make_train_step()
            elif kind == "tbptt":
                fn = self._make_tbptt_step()
            else:
                fn = self._make_program(kind)
            if isinstance(fn, tuple):  # train_fused: (masked, nomask) pair
                fn = tuple(self.compile_watch.wrap(f, f"{kind}.{tag}")
                           for f, tag in zip(fn, ("masked", "nomask")))
            else:
                fn = self.compile_watch.wrap(fn, kind)
            self._jit_cache[k] = fn
        return fn

    # ----------------------------------------------------------- one batch
    def _run_step(self, step, *args):
        """Call a step program, plain or compressed, on the network's
        trees and keep the trees it hands back; returns what follows them
        in its result (the loss, behind whatever ``args`` it threads)."""
        if self.grad_compression is not None:
            if self.compress_state is None:
                from deeplearning4j_tpu.parallel.compress import (
                    ensure_compress_state)
                ensure_compress_state(self)
            (self.params, self.state, self.opt_state, self.compress_state,
             *rest) = step(self.params, self.state, self.opt_state,
                           self.compress_state, *args)
        else:
            self.params, self.state, self.opt_state, *rest = step(
                self.params, self.state, self.opt_state, *args)
        return rest

    def _fit_batch(self, train_step, ds):
        """One optimizer step on one batch (a DataSet; a graph also takes
        a MultiDataSet), under the inner spans of the fit loops' tree
        (obs/trace.py): opened here, where the work is, so that every
        caller (``fit``, ``ParallelWrapper.fit_batch``) gets them once,
        inside its own ``train.step_host``."""
        tracer = get_tracer()
        step = self.iteration
        with tracer.span("train.stage", step=step):
            x, y, fm, lm = self._stage(ds)
        if self._wants_tbptt(x):
            self._fit_tbptt(x, y, fm, lm)
            return
        with tracer.span("train.dispatch", step=step, program="train"):
            self._rng, k = jax.random.split(self._rng)
            loss, = self._run_step(train_step, k, x, y, fm, lm)
        self._finish_step(tracer, loss, self._rows(x), self._sample(x))

    def _fit_tbptt(self, x, y, fm, lm):
        """Chunked fit over time windows (reference doTruncatedBPTT
        MultiLayerNetwork.java:1393): one optimizer update per forward-length
        window, with RNN state carried (but not differentiated) across
        windows."""
        tracer = get_tracer()
        step = self._get_jitted("tbptt")
        rows = self._rows(x)
        carries = self._zero_carries(rows)
        for xs, ys, fs, ls, sample in self._windows(x, y, fm, lm):
            # one optimizer update per window == one iteration: each
            # window's spans carry its own step
            with tracer.span("train.dispatch", step=self.iteration,
                             program="tbptt"):
                self._rng, k = jax.random.split(self._rng)
                carries, loss = self._run_step(step, carries, k, xs, ys, fs,
                                               ls)
            self._finish_step(tracer, loss, rows, sample)

    def _finish_step(self, tracer, loss, batch: int, sample, steps: int = 1):
        """What follows a dispatch in every fit path: ``train.post`` (the
        score handle, counters and, only on an iteration some listener
        reads it (``reads_features``), ``sample()``: the slice listeners
        read activations from, a device program of its own; a path that
        has none passes None), then ``train.listeners``, then the
        iteration counter. ``steps`` is the optimizer steps the dispatch
        ran (fused paths: the group)."""
        step = self.iteration
        last = step + steps - 1  # what iteration_done is told
        sampled = int(sample is not None
                      and any_reads_features(self.listeners, last))
        with tracer.span("train.post", step=step, sampled=sampled):
            self._score = loss
            self.last_batch_size = batch
            # first sample only: listeners sample activations, and pinning
            # the whole batch keeps large device buffers alive after fit().
            # None on every other turn: no stale sample of an earlier
            # batch, and no device program behind the step
            self._last_features = sample() if sampled else None
            count_train_steps(steps, steps * batch, sampled)
        if self.listeners:
            with tracer.span("train.listeners", step=step):
                for listener in self.listeners:
                    listener.iteration_done(self, last, self.epoch)
        self.iteration += steps


def bind_epoch(model, data) -> None:
    """Epoch-aware sharded readers (datasets/sharded.py) follow the
    MODEL's epoch counter, so a restored model replays exactly the
    interrupted epoch's shuffle order, at any world size."""
    if hasattr(data, "bind_epoch"):
        data.bind_epoch(lambda: model.epoch)


def step_host(tracer, model, items: int):
    """The span of a turn's own work on its batch, under the turn's
    ``train.iteration`` (obs/trace.py)."""
    return tracer.span("train.step_host", step=model.iteration, items=items)


def run_epochs(model, data, num_epochs: int,
               one_batch: Optional[Callable] = None, *,
               prefetch: Optional[dict] = None, checkpoint_manager=None,
               turn: Optional[Callable] = None,
               epoch_start: Optional[Callable] = None,
               epoch_drained: Optional[Callable] = None,
               epoch_done: Optional[Callable] = None) -> None:
    """THE epoch loop of every fit path. A model that
    ``CheckpointManager.restore_latest()`` returned carries a resume
    marker: ``num_epochs`` is then the run's TOTAL target, and the batches
    its checkpoint had consumed in its epoch are skipped.

    ``one_batch(ds)`` trains one batch; when it answers False the batch
    was dropped and no checkpoint trigger sees it. ``prefetch`` (None:
    none) holds the caller's placement for ``DevicePrefetchIterator``
    (``{}``, ``{"mesh": ...}``, ``{"place_fn": ...}``). ``turn(ds, seen)``
    takes the standard turn's place (``train.step_host`` around
    ``one_batch`` and ``checkpoint_manager.step_end``) for a caller that
    runs its step elsewhere (the cluster trainer's watchdog).
    ``epoch_start()`` runs before the listeners' ``on_epoch_start``,
    ``epoch_drained(seen, trained, resumed_mid_epoch)`` when the stream
    has ended and before their ``on_epoch_end`` (it may raise),
    ``epoch_done()`` after ``checkpoint_manager.epoch_end``."""
    from deeplearning4j_tpu.checkpoint.manager import (resume_plan,
                                                       skip_consumed_batches)
    tracer = get_tracer()
    epochs_to_run, skip = resume_plan(model, num_epochs)
    bind_epoch(model, data)
    if turn is None:
        def turn(ds, seen):
            with step_host(tracer, model, ds.num_examples()):
                trained = one_batch(ds)
                if trained is not False and checkpoint_manager is not None:
                    checkpoint_manager.step_end(model, batch_in_epoch=seen)
            return trained
    if prefetch is not None:
        from deeplearning4j_tpu.perf.prefetch import DevicePrefetchIterator
    for _ in range(epochs_to_run):
        if epoch_start is not None:
            epoch_start()
        for listener in model.listeners:
            listener.on_epoch_start(model)
        # skip UNDER the prefetch wrapper: batches consumed before the
        # checkpoint are never placed or transferred just to be discarded
        # (and no rng split / update runs for them — the restored chain
        # stays exact)
        stream = skip_consumed_batches(data, skip)
        if prefetch is not None:
            stream = DevicePrefetchIterator(stream, **prefetch)
        # the fit loops' span tree (obs/trace.py): one train.iteration a
        # turn, its data-wait ABOVE prefetch (what the step loop actually
        # waits for, which prefetch exists to hide), then the loop's own
        # work on the batch. No span waits for the device.
        stream = tracer.wrap_iter(stream, "train.data_wait",
                                  turn="train.iteration",
                                  step=lambda: model.iteration)
        seen, trained = skip, 0
        for ds in stream:
            seen += 1
            if turn(ds, seen) is not False:
                trained += 1
        if epoch_drained is not None:
            epoch_drained(seen, trained, skip > 0)
        skip = 0
        for listener in model.listeners:
            listener.on_epoch_end(model)
        model.epoch += 1
        if checkpoint_manager is not None:
            checkpoint_manager.epoch_end(model)
        if epoch_done is not None:
            epoch_done()

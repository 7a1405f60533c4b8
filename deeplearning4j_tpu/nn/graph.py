"""ComputationGraph — arbitrary-DAG network with multi-input/multi-output.

Parity surface: reference deeplearning4j-nn/.../nn/graph/ComputationGraph.java
(:370 init, :1190 topologicalSortOrder, :1428 feedForward vertex loop,
:1629 calcBackpropGradients, :978 fit(MultiDataSet)).

TPU-native: the topo-order vertex loop runs at *trace time* — the whole DAG
(all vertices, losses on every output layer, backward pass, optimizer)
compiles to one XLA program per input signature. Multi-output losses sum, as
in the reference (score summed over output layers).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf.graph import (
    ComputationGraphConfiguration, DuplicateToTimeSeriesVertex, LastTimeStepVertex,
)
from deeplearning4j_tpu.nn.conf.layers import (Layer, apply_constraints,
                                               apply_layer, dropout_input,
                                               noisy_params)
from deeplearning4j_tpu.optimize.fused_update import bucketed_apply
from deeplearning4j_tpu.optimize.updaters import gradient_normalization
from deeplearning4j_tpu.perf.compile_watch import CompileWatch


def _compute_dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16, "float64": jnp.float64}[name]


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.order: List[str] = conf.topological_order()
        self.vertices = conf.wired_vertices()
        self.vertex_input_types = conf.vertex_input_types()
        self._vpre = conf.resolved_vertex_preprocessors()
        self._dtype = _compute_dtype(conf.dtype)
        self._layer_names = [n for n in self.order
                             if isinstance(self.vertices[n][0], Layer)]
        self._txs = {}
        self._gnorms = {}
        self._updaters = {}
        for n in self._layer_names:
            layer = self.vertices[n][0]
            upd = getattr(layer, "updater", None) or conf.updater
            self._updaters[n] = upd
            self._txs[n] = upd.to_optax()
            self._gnorms[n] = gradient_normalization(
                getattr(layer, "gradient_normalization", None),
                getattr(layer, "gradient_normalization_threshold", 1.0))
        for out in conf.network_outputs:
            obj = self.vertices[out][0]
            if not (isinstance(obj, Layer) and obj.is_output_layer()):
                raise ValueError(f"Network output '{out}' must be an output/loss layer")
        self.params: Optional[Dict[str, dict]] = None
        self.state: Optional[Dict[str, dict]] = None
        self.opt_state: Optional[Dict[str, object]] = None
        self.listeners: list = []
        self.iteration = 0
        self.epoch = 0
        self.last_batch_size: Optional[int] = None
        self._score = None
        self._rng = None
        self._rnn_carries = None
        self._last_features = None  # last fit minibatch (listener sampling)
        # set by checkpoint.CheckpointManager.restore_latest; consumed by
        # the next fit() for exact-step resume (skip already-seen batches).
        # _restored_from is informational provenance (also set by
        # restore_best) and never consumed.
        self._resume_state = None
        self._restored_from = None
        # compressed gradient collectives (parallel/compress.py) — same
        # contract as MultiLayerNetwork: scheme config + device-resident
        # error-feedback state threaded through the jitted step
        self.grad_compression = None
        self.compress_state = None
        # on-device augmentation (datasets/augment.py) — applied to every
        # 4-D (NHWC) network input inside the jitted train step; part of
        # the jit-cache key (see set_augmentation)
        self.augmentation = None
        self._jit_cache = {}
        # per-network compile/dispatch counters (perf/compile_watch.py)
        self.compile_watch = CompileWatch("ComputationGraph")

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None,
             validate: Optional[bool] = None,
             params: Optional[Dict[str, dict]] = None) -> "ComputationGraph":
        """Initialize params/optimizer state. Runs ``conf.validate()`` first
        (vertex-named errors before any XLA trace); opt out per call with
        ``validate=False`` or process-wide with ``DL4J_TPU_VALIDATE=0``.

        ``params`` ({vertex: {name: array}}, a leaf for every parameter the
        layers would draw, in their shapes) starts the network from GIVEN
        weights: nothing is drawn, the arrays are taken as they are (not
        copied: the train step donates them), and the non-trained state and
        the optimizer state are made in one jitted call. At 600M parameters
        a draw that is at once replaced, leaf by leaf, costs more than the
        first steps."""
        if validate is None:
            import os
            validate = os.environ.get("DL4J_TPU_VALIDATE", "1") != "0"
        if validate:
            self.conf.validate()
        rng = jax.random.key(self.conf.seed if seed is None else seed)
        if params is not None:
            return self._init_from(params, rng)
        params, state = {}, {}
        for name in self.order:
            obj, _ = self.vertices[name]
            if isinstance(obj, Layer):
                rng, k = jax.random.split(rng)
                p, s = obj.init(k, self.vertex_input_types[name][0], jnp.float32)
            else:
                p, s = {}, {}
            params[name] = p
            state[name] = s
        self.params = params
        self.state = state
        self.opt_state = {n: self._txs[n].init(params[n])
                          for n in self._layer_names}
        self._rng = rng
        return self

    def _init_from(self, given: Dict[str, dict], rng) -> "ComputationGraph":
        def own(rng):
            """What ``init`` makes, with the same splits of ``rng``; under
            ``jit`` the draws nobody reads are never computed."""
            drawn, state = {}, {}
            for name in self.order:
                obj, _ = self.vertices[name]
                if isinstance(obj, Layer):
                    rng, k = jax.random.split(rng)
                    drawn[name], state[name] = obj.init(
                        k, self.vertex_input_types[name][0], jnp.float32)
                else:
                    drawn[name], state[name] = {}, {}
            return drawn, state, rng

        want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                      jax.eval_shape(own, rng)[0])
        params = {name: dict(given.get(name, {})) for name in self.order}
        have = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
        if have != want:
            odd = sorted(
                {(n, k, str(v)) for n, d in want.items()
                 for k, v in d.items()}
                ^ {(n, k, str(v)) for n, d in have.items()
                   for k, v in d.items()})[:6]
            raise ValueError(f"given weights do not fit the network: {odd}")

        def rest(rng, params):
            _, state, rng = own(rng)
            return state, {n: self._txs[n].init(params[n])
                           for n in self._layer_names}, rng

        self.params = params
        self.state, self.opt_state, self._rng = jax.jit(rest)(rng, params)
        return self

    def num_params(self) -> int:
        if self.params is None:
            return 0
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(self.params))

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def score(self):
        return None if self._score is None else float(self._score)

    # --------------------------------------------------------------- forward
    def _forward(self, params, state, inputs: Sequence, train: bool, rng,
                 masks, carries=None):
        """Trace the DAG. Returns (activations dict, preouts dict, new_state,
        mask dict[, new_carries when ``carries`` is given]).

        ``carries`` (dict vertex->carry pytree) selects the stateful
        sequence path of recurrent layer vertices (``apply_seq``), mirroring
        the MLN carry threading — the graph analogue of the reference's
        rnnActivateUsingStoredState (ComputationGraph.java:2402)."""
        cdt = self._dtype
        if cdt != jnp.float32:
            params = jax.tree_util.tree_map(lambda a: a.astype(cdt), params)
        acts: Dict[str, jnp.ndarray] = {}
        mask_of: Dict[str, Optional[jnp.ndarray]] = {}
        for i, name in enumerate(self.conf.network_inputs):
            x = inputs[i]
            acts[name] = x.astype(cdt) if (cdt != jnp.float32 and
                                           jnp.issubdtype(x.dtype, jnp.floating)) else x
            mask_of[name] = None if masks is None else masks[i]
        new_state = {}
        new_carries = {}
        preouts = {}
        for name in self.order:
            obj, in_names = self.vertices[name]
            xs = [acts[i] for i in in_names]
            in_mask = next((mask_of[i] for i in in_names if mask_of[i] is not None), None)
            k = None
            if rng is not None:
                rng, k = jax.random.split(rng)
            if isinstance(obj, Layer):
                if name in self._vpre:
                    xs = list(xs)
                    xs[0], in_mask = self._vpre[name].apply(xs[0], in_mask)
                p_v = noisy_params(obj, params[name], k, train)
                if obj.is_output_layer():
                    x_in = dropout_input(xs[0], obj.dropout, train, k)
                    z = obj.pre_output(p_v, x_in)
                    # loss math in f32 (z may be a pytree: CenterLoss/YOLO)
                    z = jax.tree_util.tree_map(
                        lambda a: a.astype(jnp.float32)
                        if a.dtype in (jnp.bfloat16, jnp.float16) else a, z)
                    preouts[name] = z
                    out = obj.output_activations(z)
                    new_state[name] = state[name]
                elif (carries is not None and hasattr(obj, "apply_seq")
                      and getattr(obj, "supports_stateful", True)):
                    x_in = dropout_input(xs[0], obj.dropout, train, k)
                    out, nc = obj.apply_seq(p_v, carries[name], x_in,
                                            train=train, rng=None,
                                            mask=in_mask)
                    new_carries[name] = nc
                    new_state[name] = state[name]
                else:
                    # fused conv→BN→act blocks with residual=True take the
                    # residual-add operand as a second vertex input
                    extra = ({"res": xs[1]}
                             if getattr(obj, "residual", False) and len(xs) > 1
                             else None)
                    # apply_layer lowers through jax.checkpoint when the
                    # layer's remat= knob is set (perf/fusion.py policies)
                    out, st = apply_layer(obj, p_v, state[name], xs[0],
                                          train=train, rng=k, mask=in_mask,
                                          name=name, extra=extra)
                    new_state[name] = st
                out_kind = obj.output_type(self.vertex_input_types[name][0]).kind
                mask_of[name] = in_mask if out_kind in ("rnn", "cnn1d") else None
            else:
                if isinstance(obj, LastTimeStepVertex):
                    m = in_mask
                    if obj.mask_input is not None:
                        m = mask_of.get(obj.mask_input)
                    out = obj.apply(*xs, mask=m)
                    mask_of[name] = None
                elif isinstance(obj, DuplicateToTimeSeriesVertex):
                    t = acts[obj.reference_input].shape[1]
                    out = obj.apply(*xs, time_steps=t)
                    mask_of[name] = mask_of.get(obj.reference_input)
                else:
                    out = obj.apply(*xs)
                    mask_of[name] = in_mask
                new_state[name] = state[name]
            acts[name] = out
        if carries is not None:
            for n in carries:
                new_carries.setdefault(n, carries[n])
            return acts, preouts, new_state, mask_of, new_carries
        return acts, preouts, new_state, mask_of

    def _regularization(self, params):
        from deeplearning4j_tpu.nn.conf.layers import (
            _bias_keys, regularization_coefficients, resolve_param_path,
        )
        total = 0.0
        for name in self._layer_names:
            layer = self.vertices[name][0]
            p = params[name]
            l1, l2, l1b, l2b = regularization_coefficients(layer)
            for key in layer.regularizable():
                w = resolve_param_path(p, key)
                if w is not None:
                    if w.dtype in (jnp.bfloat16, jnp.float16):
                        w = w.astype(jnp.float32)
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(w * w)
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(w))
            if l1b or l2b:
                # bias terms were silently skipped here (MLN parity):
                # _bias_keys covers both top-level 'b' and nested wrapper/
                # attention biases (q/b, k/b, ...)
                for bk in _bias_keys(layer, p):
                    b = resolve_param_path(p, bk)
                    if b.dtype in (jnp.bfloat16, jnp.float16):
                        b = b.astype(jnp.float32)
                    if l2b:
                        total = total + 0.5 * l2b * jnp.sum(b * b)
                    if l1b:
                        total = total + l1b * jnp.sum(jnp.abs(b))
        return total

    # ------------------------------------------------------------ train step
    def _loss_fn(self, params, state, inputs, labels, rng, fmasks, lmasks,
                 carries=None):
        """Loss over all output layers; with ``carries`` the recurrent
        vertices run their stateful path and the aux also returns the new
        carries (shared by the standard and tBPTT steps)."""
        if self.augmentation is not None and rng is not None:
            # in-graph augmentation of every image-shaped input, seeded per
            # input off ONE split of the step key (train-mode only; the
            # score path calls with rng=None)
            rng, ak = jax.random.split(rng)
            inputs = [self.augmentation.apply(x, jax.random.fold_in(ak, i))
                      if x.ndim == 4 else x for i, x in enumerate(inputs)]
        fwd = self._forward(params, state, inputs, True, rng, fmasks, carries)
        if carries is None:
            acts, preouts, new_state, mask_of = fwd
            aux = new_state
        else:
            acts, preouts, new_state, mask_of, new_carries = fwd
            aux = (new_state, new_carries)
        loss = 0.0
        for j, out_name in enumerate(self.conf.network_outputs):
            layer = self.vertices[out_name][0]
            y = labels[j]
            if y.dtype in (jnp.bfloat16, jnp.float16):
                y = y.astype(jnp.float32)
            lm = None if lmasks is None else lmasks[j]
            if lm is None:
                lm = mask_of.get(out_name)
            loss = loss + layer.compute_score(y, preouts[out_name], lm)
        return loss + self._regularization(params), aux

    # ----------------------------------------------- truncated BPTT / state
    def _zero_carries(self, batch: int):
        return {n: (self.vertices[n][0].init_carry(batch)
                    if hasattr(self.vertices[n][0], "init_carry") else {})
                for n in self._layer_names}

    def _loss_fn_tbptt(self, params, state, carries, inputs, labels, rng,
                       fmasks, lmasks):
        """Window loss with carried (but not differentiated) RNN state —
        graph analogue of reference ComputationGraph.java:1158
        (doTruncatedBPTT dispatch in fit)."""
        return self._loss_fn(params, state, inputs, labels, rng, fmasks,
                             lmasks, carries=carries)

    def _make_tbptt_step(self):
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
            new_params, new_opt = self._apply_updates(params, grads, opt_state)
            return new_params, new_state, new_opt, new_carries, loss

        return jax.jit(tbptt_step, donate_argnums=(0, 1, 2, 3))

    def _time_sliceable(self, i, x):
        """Whether graph input i carries a time axis to window over."""
        if x.ndim == 3:
            return True
        its = self.conf.input_types
        it = its[i] if i < len(its) else None
        return (x.ndim == 2 and it is not None and it.kind == "rnn"
                and jnp.issubdtype(x.dtype, jnp.integer))

    def _fit_tbptt(self, inputs, labels, fmasks, lmasks):
        """Chunked fit over time windows (reference ComputationGraph.java:1158
        doTruncatedBPTT): one optimizer update per window, RNN state carried
        but gradients truncated at window boundaries."""
        from deeplearning4j_tpu.obs.trace import get_tracer
        tracer = get_tracer()
        step = self._get_jitted("tbptt")
        T = max(x.shape[1] for i, x in enumerate(inputs)
                if self._time_sliceable(i, x))
        L = self.conf.tbptt_fwd_length
        carries = self._zero_carries(int(inputs[0].shape[0]))
        loss = None
        for s in range(0, T, L):
            e = min(s + L, T)
            xs = [x[:, s:e] if self._time_sliceable(i, x) else x
                  for i, x in enumerate(inputs)]
            ys = [y[:, s:e] if y.ndim == 3 else y for y in labels]
            fms = (None if fmasks is None else
                   [None if m is None else m[:, s:e] for m in fmasks])
            lms = (None if lmasks is None else
                   [None if m is None else m[:, s:e] for m in lmasks])
            # one optimizer update per window == one iteration (MLN
            # parity): each window's spans carry its own step
            with tracer.span("train.dispatch", step=self.iteration,
                             program="tbptt"):
                self._rng, k = jax.random.split(self._rng)
                if self.grad_compression is not None:
                    if self.compress_state is None:
                        from deeplearning4j_tpu.parallel.compress import (
                            ensure_compress_state)
                        ensure_compress_state(self)
                    (self.params, self.state, self.opt_state,
                     self.compress_state, carries, loss) = step(
                        self.params, self.state, self.opt_state,
                        self.compress_state, carries, k, xs, ys, fms, lms)
                else:
                    (self.params, self.state, self.opt_state, carries,
                     loss) = step(self.params, self.state, self.opt_state,
                                  carries, k, xs, ys, fms, lms)
            self._finish_step(tracer, loss, int(inputs[0].shape[0]))

    def rnn_time_step(self, *inputs) -> List[np.ndarray]:
        """Stateful step-by-step inference for recurrent graphs (reference
        ComputationGraph.rnnTimeStep :2362): carries (h, c) across calls."""
        for n in self._layer_names:
            obj = self.vertices[n][0]
            if not getattr(obj, "supports_stateful", True):
                raise NotImplementedError(
                    f"rnn_time_step is not supported with {type(obj).__name__}"
                    " in vertex '" + n + "': the backward direction needs the"
                    " full sequence")
        xs = []
        squeeze = False
        for i, x in enumerate(inputs):
            x = jnp.asarray(x)
            its = self.conf.input_types
            it = its[i] if i < len(its) else None
            if it is not None and it.kind == "rnn":
                if jnp.issubdtype(x.dtype, jnp.integer):
                    if x.ndim == 1:     # (batch,) single timestep of ids
                        x, squeeze = x[:, None], True
                    elif x.ndim == 2 and x.shape[1] == 1:
                        squeeze = True  # (batch, 1) ids: MLN parity
                elif x.ndim == 2:       # (batch, features) single timestep
                    x, squeeze = x[:, None, :], True
            xs.append(x)
        b = int(xs[0].shape[0])
        if self._rnn_carries is None:
            self._rnn_carries = self._zero_carries(b)
        else:
            leaves = jax.tree_util.tree_leaves(self._rnn_carries)
            if leaves and leaves[0].shape[0] != b:
                raise ValueError(
                    f"rnn_time_step batch size {b} does not match stored "
                    f"state batch {leaves[0].shape[0]}; call "
                    "rnn_clear_previous_state() first")
        fn = self._get_jitted("rnn_step")
        outs, self._rnn_carries = fn(self.params, self.state,
                                     self._rnn_carries, xs)
        outs = [np.asarray(o) for o in outs]
        if squeeze:
            outs = [o[:, -1, :] if o.ndim == 3 else o for o in outs]
        return outs

    def rnn_clear_previous_state(self):
        """reference ComputationGraph.rnnClearPreviousState."""
        self._rnn_carries = None

    def rnn_get_previous_state(self):
        return self._rnn_carries

    def _apply_updates(self, params, grads, opt_state):
        """Optimizer application shared by the standard and tBPTT steps.

        Per-vertex update chains are kept (vs one whole-tree optax
        transform, measured r4: no step-time difference on ResNet50) —
        they preserve wrapper-layer constraints, tensor-parallel opt-state
        placement, and checkpoint compatibility. Small leaves additionally
        run through ``bucketed_apply`` (optimize/fused_update.py), which
        computes the identical math over one concatenated vector per
        updater config so XLA emits a handful of fusions instead of one
        per leaf (ResNet50: 244 small fusions ~8 ms/step)."""
        results = bucketed_apply(self._layer_names, self._updaters,
                                 self._txs, self._gnorms, params, grads,
                                 opt_state)
        new_params = dict(params)
        new_opt = dict(opt_state)
        for n in self._layer_names:
            updates, os = results[n]
            new_params[n] = apply_constraints(
                self.vertices[n][0], optax.apply_updates(params[n], updates))
            new_opt[n] = os
        return new_params, new_opt

    def _make_train_step(self):
        value_and_grad = jax.value_and_grad(self._loss_fn, has_aux=True)
        comp = self.grad_compression
        if comp is not None:
            # compressed collectives (parallel/compress.py): encode→decode
            # + error-feedback residual update inside the compiled step
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
        # (jit_train_step): keep it stable
        def train_step(params, state, opt_state, rng, inputs, labels, fmasks,
                       lmasks):
            (loss, new_state), grads = value_and_grad(
                params, state, inputs, labels, rng, fmasks, lmasks)
            new_params, new_opt = self._apply_updates(params, grads, opt_state)
            return new_params, new_state, new_opt, loss

        return jax.jit(train_step, donate_argnums=(0, 1, 2))

    def set_augmentation(self, augmentation) -> "ComputationGraph":
        """Enable on-device augmentation (datasets/augment.py) for the
        jitted train step — same contract as
        MultiLayerNetwork.set_augmentation; applied to 4-D (NHWC) inputs
        only."""
        self.augmentation = augmentation
        return self

    def _get_jitted(self, kind):
        # the compression scheme AND augmentation config are part of the
        # cache key (see multilayer.py): changing either mints a fresh step
        key = (kind, self.grad_compression, self.augmentation)
        fn = self._jit_cache.get(key)
        if fn is None:
            if kind == "train":
                fn = self._make_train_step()
            elif kind == "tbptt":
                fn = self._make_tbptt_step()
            elif kind == "rnn_step":
                def rnn_step(params, state, carries, xs):
                    acts, _, _, _, nc = self._forward(
                        params, state, xs, False, None, None, carries)
                    return [acts[n] for n in self.conf.network_outputs], nc
                fn = jax.jit(rnn_step)
            elif kind == "output":
                def output(params, state, inputs, fmasks):
                    acts, _, _, _ = self._forward(params, state, inputs, False,
                                                  None, fmasks)
                    return [acts[n] for n in self.conf.network_outputs]
                fn = jax.jit(output)
            elif kind == "score":
                def score(params, state, inputs, labels, fmasks, lmasks):
                    return self._loss_fn(params, state, inputs, labels, None,
                                         fmasks, lmasks)[0]
                fn = jax.jit(score)
            else:
                raise KeyError(kind)
            fn = self.compile_watch.wrap(fn, kind)
            self._jit_cache[key] = fn
        return fn

    # ------------------------------------------------------------------- fit
    def fit(self, data, num_epochs: int = 1, bucket_policy=None,
            prefetch: bool = False, checkpoint_manager=None):
        """Train on MultiDataSets (reference ComputationGraph.fit :978); plain
        DataSets are adapted for single-input/single-output graphs.

        ``bucket_policy`` (a perf.BucketPolicy, or True for the default)
        pads every batch — DataSet or MultiDataSet — to a canonical bucket
        shape with the padded rows masked out of every output's loss
        (perf/bucketing.py pad_dataset / pad_multi_dataset), so an epoch
        with a ragged final batch is ONE compiled program: MLN parity.
        ``prefetch=True`` stages batch N+1 onto the device while step N
        runs (perf/prefetch.py). ``checkpoint_manager`` checkpoints per its
        triggers and makes the run resumable at the exact step — same
        semantics as MultiLayerNetwork.fit (num_epochs is the TOTAL target
        when resuming a restored model)."""
        if self.params is None:
            self.init()
        if isinstance(data, (DataSet, MultiDataSet)):
            data = [data]
        if bucket_policy is not None:
            from deeplearning4j_tpu.perf.bucketing import (
                BucketPadDataSetIterator, BucketPolicy)
            policy = (BucketPolicy() if bucket_policy is True
                      else bucket_policy)
            # above the resume skip: pad targets must evolve exactly as in
            # the uninterrupted run (see multilayer.py fit)
            data = BucketPadDataSetIterator(data, policy)
        prefetch_cls = None
        if prefetch:
            from deeplearning4j_tpu.perf.prefetch import DevicePrefetchIterator
            prefetch_cls = DevicePrefetchIterator
        from deeplearning4j_tpu.checkpoint.manager import (
            resume_plan, skip_consumed_batches)
        epochs_to_run, skip = resume_plan(self, num_epochs)
        if hasattr(data, "bind_epoch"):
            # epoch-aware sharded readers follow the model's epoch
            # counter (see multilayer.py fit)
            data.bind_epoch(lambda: self.epoch)
        step = self._get_jitted("train")
        from deeplearning4j_tpu.obs.trace import get_tracer
        tracer = get_tracer()
        for _ in range(epochs_to_run):
            # epoch-boundary listener hooks: MLN parity (epoch-scoped
            # listeners — and the chaos harness's epoch-boundary fault
            # injection — were MLN-only before)
            for listener in self.listeners:
                listener.on_epoch_start(self)
            # skip UNDER the prefetch wrapper: already-consumed batches are
            # never transferred just to be discarded (no rng split, no
            # update — the restored chain stays exact)
            stream = skip_consumed_batches(data, skip)
            if prefetch_cls is not None:
                stream = prefetch_cls(stream)
            # the fit loops' span tree, as in multilayer.py fit (host-side
            # only, nothing waits for the device; see obs/trace.py)
            stream = tracer.wrap_iter(stream, "train.data_wait",
                                      turn="train.iteration",
                                      step=lambda: self.iteration)
            bi = skip
            for ds in stream:
                bi += 1
                with tracer.span("train.step_host", step=self.iteration,
                                 items=ds.num_examples()):
                    self._fit_batch(step, ds)
                    if checkpoint_manager is not None:
                        checkpoint_manager.step_end(self, batch_in_epoch=bi)
            skip = 0
            for listener in self.listeners:
                listener.on_epoch_end(self)
            self.epoch += 1
            if checkpoint_manager is not None:
                checkpoint_manager.epoch_end(self)
        return self

    def _fit_batch(self, step, ds):
        """One optimizer step on one DataSet or MultiDataSet, under the
        inner spans of the fit loops' tree (obs/trace.py): opened here,
        where the work is, so that every caller (``fit``,
        ``ParallelWrapper.fit_batch``) gets them once, inside its own
        ``train.step_host``."""
        from deeplearning4j_tpu.obs.trace import get_tracer
        tracer = get_tracer()
        at = self.iteration
        with tracer.span("train.stage", step=at):
            mds = (MultiDataSet.from_dataset(ds) if isinstance(ds, DataSet)
                   else ds)
            inputs = [jnp.asarray(f) for f in mds.features]
            labels = [jnp.asarray(l) for l in mds.labels]
            fmasks = (None if mds.features_masks is None else
                      [None if m is None else jnp.asarray(m)
                       for m in mds.features_masks])
            lmasks = (None if mds.labels_masks is None else
                      [None if m is None else jnp.asarray(m)
                       for m in mds.labels_masks])
        if self.conf.backprop_type == "tbptt":
            sliceable = [x.shape[1] for i, x in enumerate(inputs)
                         if self._time_sliceable(i, x)]
            if sliceable and max(sliceable) > self.conf.tbptt_fwd_length:
                self._fit_tbptt(inputs, labels, fmasks, lmasks)
                return
        with tracer.span("train.dispatch", step=at, program="train"):
            self._rng, k = jax.random.split(self._rng)
            if self.grad_compression is not None:
                if self.compress_state is None:
                    from deeplearning4j_tpu.parallel.compress import (
                        ensure_compress_state)
                    ensure_compress_state(self)
                (self.params, self.state, self.opt_state,
                 self.compress_state, loss) = step(
                    self.params, self.state, self.opt_state,
                    self.compress_state, k, inputs, labels, fmasks, lmasks)
            else:
                self.params, self.state, self.opt_state, loss = step(
                    self.params, self.state, self.opt_state, k, inputs,
                    labels, fmasks, lmasks)
        # first sample per input only (see multilayer.py note)
        self._finish_step(tracer, loss, int(inputs[0].shape[0]),
                          lambda: [f[:1] for f in inputs])

    def _finish_step(self, tracer, loss, batch: int, sample=None):
        """What follows a dispatch: ``train.post`` (the score handle,
        counters and, only on an iteration some listener reads it
        (``reads_features``), ``sample()``: the slices listeners read
        activations from, each a device program of its own), then
        ``train.listeners``, then the iteration counter."""
        from deeplearning4j_tpu.obs.registry import count_train_steps
        from deeplearning4j_tpu.optimize.listeners import any_reads_features
        at = self.iteration
        sampled = int(sample is not None
                      and any_reads_features(self.listeners, at))
        with tracer.span("train.post", step=at, sampled=sampled):
            self._score = loss
            self.last_batch_size = batch
            # None on every other turn: no stale sample of an earlier
            # batch, no device program behind the step, nothing pinned
            self._last_features = sample() if sampled else None
            count_train_steps(1, batch, sampled)
        if self.listeners:
            with tracer.span("train.listeners", step=at):
                for listener in self.listeners:
                    listener.iteration_done(self, at, self.epoch)
        self.iteration += 1

    # ---------------------------------------------------------------- output
    def output(self, *inputs, features_masks=None) -> List[np.ndarray]:
        """Multi-output inference (reference ComputationGraph.output; the
        mask-threading overload ComputationGraph.java:1428 — masked sequence
        vertices like Bidirectional/LastTimeStep read only valid steps)."""
        if self.params is None:
            self.init()
        fn = self._get_jitted("output")
        fmasks = (None if features_masks is None else
                  [None if m is None else jnp.asarray(m)
                   for m in features_masks])
        outs = fn(self.params, self.state,
                  [jnp.asarray(x) for x in inputs], fmasks)
        return [np.asarray(o) for o in outs]

    def output_single(self, *inputs, features_masks=None) -> np.ndarray:
        return self.output(*inputs, features_masks=features_masks)[0]

    def predict(self, *inputs, features_masks=None) -> np.ndarray:
        return np.argmax(
            self.output_single(*inputs, features_masks=features_masks), axis=-1)

    def score_dataset(self, ds) -> float:
        mds = MultiDataSet.from_dataset(ds) if isinstance(ds, DataSet) else ds
        fn = self._get_jitted("score")
        fmasks = (None if mds.features_masks is None else
                  [None if m is None else jnp.asarray(m) for m in mds.features_masks])
        lmasks = (None if mds.labels_masks is None else
                  [None if m is None else jnp.asarray(m) for m in mds.labels_masks])
        return float(fn(self.params, self.state,
                        [jnp.asarray(f) for f in mds.features],
                        [jnp.asarray(l) for l in mds.labels], fmasks, lmasks))

    def evaluate(self, iterator):
        """Classification eval over an iterator (reference
        ComputationGraph.evaluate), threading the dataset's feature masks
        through inference like the MLN path does."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        e = Evaluation()
        for ds in iterator:
            fm = None if ds.features_mask is None else [ds.features_mask]
            out = self.output_single(ds.features, features_masks=fm)
            e.eval(ds.labels, out, mask=ds.labels_mask)
        return e

"""MultiLayerNetwork — the sequential-stack network façade.

Parity surface: reference
deeplearning4j-nn/.../nn/multilayer/MultiLayerNetwork.java:90 (class), :541
(init), :852-964 (feedForward), :1156 (fit(DataSetIterator)), :1267 (backprop),
:2206 (computeGradientAndScore), :1947 (output).

TPU-native design: everything between ``setInput`` and the optimizer step —
forward, loss, backward, updater — is ONE jit-compiled XLA program
(``_train_step``) executed per minibatch, with buffer donation for params /
optimizer state (replacing ND4J workspaces). The Java-side per-layer
interpretive loop and the Solver/StepFunction machinery dissolve into the
traced program; listeners and iterators remain host-side, as in the reference.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.layers import (apply_constraints, apply_layer,
                                               dropout_input, noisy_params)
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.engine import (Network, _f32, bind_epoch,
                                          run_epochs)
from deeplearning4j_tpu.obs.owners import layer_marker
from deeplearning4j_tpu.obs.trace import get_tracer
from deeplearning4j_tpu.optimize.updaters import is_sgd_family
import optax


class MultiLayerNetwork(Network):
    """Sequential network with fit/output/score (see module docstring):
    the stack's forward pass, staging and programs over ``nn/engine.py``'s
    ``Network``, which holds the steps and the fit path."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.layers = conf.wired_layers()
        self._pre = conf.resolved_preprocessors()
        if not self.layers:
            raise ValueError("Empty layer list")
        self._param_layers = list(enumerate(self.layers))
        super().__init__(conf)
        # whether each layer's OUTPUT still has a time axis the feature mask
        # applies to; a per-step mask must not survive layers that collapse
        # time (cnn/ff) or it breaks the loss shape (graph.py does the same)
        try:
            self._mask_survives = [
                l.output_type(it).kind in ("rnn", "cnn1d")
                for l, it in zip(self.layers, conf.layer_input_types())]
        except Exception:
            self._mask_survives = [True] * len(self.layers)
        self._window_scores = None  # fit_tbptt_fused: every window's loss

    def _collect(self, entries, like=None):
        # every layer of a stack owns an entry: nothing of ``like`` is kept
        return [entries[i] for i in range(len(self.layers))]

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None,
             validate: Optional[bool] = None) -> "MultiLayerNetwork":
        """Initialize params/optimizer state (reference MultiLayerNetwork.init :541).

        Runs ``conf.validate()`` first so misconfigurations fail here with a
        layer-named message instead of seconds later inside an XLA trace.
        Opt out per call with ``validate=False`` or process-wide with
        ``DL4J_TPU_VALIDATE=0``."""
        return self._init_drawn(self._seeded_key(seed, validate))

    def _draw(self, rng):
        params, state = [], []
        for layer, it in zip(self.layers, self.conf.layer_input_types()):
            rng, k = jax.random.split(rng)
            p, s = layer.init(k, it, jnp.float32)  # master params in f32
            params.append(p)
            state.append(s)
        return params, state, rng

    # --------------------------------------------------------------- forward
    def _forward(self, params, state, x, train: bool, rng, fmask, carries=None):
        """Full forward pass; returns (activations list, preout of output
        layer, new_state, final mask, new_carries). Traced by jit — the
        reference's feedForwardToLayer loop unrolls into one XLA graph.

        ``carries`` (list of per-layer RNN state pytrees, {} for
        non-recurrent layers) enables stateful recurrence: truncated BPTT
        (reference doTruncatedBPTT — MultiLayerNetwork.java:1393) and
        rnnTimeStep (:2615)."""
        acts = []
        new_state = []
        new_carries = []
        preout = None
        cur_mask = fmask
        params, x = self._to_compute_dtype(params, x)
        n = len(self.layers)
        for i, layer in enumerate(self.layers):
            # what runs for a layer outside ``apply_layer`` lies under the
            # layer's marker too (obs/owners.py)
            marker = layer_marker(layer, i)
            k = None
            if rng is not None:
                rng, k = jax.random.split(rng)
            with jax.named_scope(marker):
                if i in self._pre:
                    x, cur_mask = self._pre[i].apply(x, cur_mask)
                p_i = noisy_params(layer, params[i], k, train)
            if i == n - 1 and layer.is_output_layer():
                with jax.named_scope(marker):
                    x_in = dropout_input(x, layer.dropout, train, k)
                    preout = layer.pre_output(p_i, x_in)
                    # loss math in f32 (preout may be a pytree:
                    # CenterLoss/YOLO)
                    preout = jax.tree_util.tree_map(_f32, preout)
                    x = layer.output_activations(preout)
                new_state.append(state[i])
                new_carries.append({})
            elif (carries is not None and hasattr(layer, "apply_seq")
                  and getattr(layer, "supports_stateful", True)):
                with jax.named_scope(marker):
                    x_in = dropout_input(x, layer.dropout, train, k)
                    x, nc = layer.apply_seq(p_i, carries[i], x_in,
                                            train=train, rng=None,
                                            mask=cur_mask)
                new_state.append(state[i])
                new_carries.append(nc)
            else:
                # apply_layer lowers through jax.checkpoint when the layer's
                # remat= knob is set (perf/fusion.py policies)
                x, st = apply_layer(layer, p_i, state[i], x, train=train,
                                    rng=k, mask=cur_mask, name=i)
                new_state.append(st)
                new_carries.append({})
            if not self._mask_survives[i]:
                cur_mask = None
            acts.append(x)
        return acts, preout, new_state, cur_mask, new_carries

    # ------------------------------------------------- the engine's hooks
    def _augment(self, x, rng):
        return self.augmentation.apply(x, rng)

    def _forward_loss(self, params, state, x, y, rng, fmask, lmask, carries):
        out_layer = self.layers[-1]
        if not out_layer.is_output_layer():
            raise ValueError("Last layer must be an output/loss layer to fit()")
        _, preout, new_state, cur_mask, new_carries = self._forward(
            params, state, x, True, rng, fmask, carries)
        loss = self._output_score(out_layer, y, preout, lmask, cur_mask)
        return loss, (new_state if carries is None
                      else (new_state, new_carries))

    def _loss_fn_tbptt(self, params, state, carries, x, y, rng, fmask, lmask):
        # a stack's window step does not augment (a graph's does: it goes
        # through _loss_fn)
        loss, aux = self._forward_loss(params, state, x, y, rng, fmask,
                                       lmask, carries)
        return loss + self._regularization(params), aux

    def _stage(self, ds: DataSet):
        return (jnp.asarray(ds.features), jnp.asarray(ds.labels),
                None if ds.features_mask is None
                else jnp.asarray(ds.features_mask),
                None if ds.labels_mask is None
                else jnp.asarray(ds.labels_mask))

    def _rows(self, x) -> int:
        return int(x.shape[0])

    def _sample(self, x):
        return lambda: x[:1]

    def _wants_tbptt(self, x) -> bool:
        if self.conf.backprop_type != "tbptt":
            return False
        # tbptt applies when the input has a time axis: 3-D dense sequences or
        # 2-D integer index sequences (EmbeddingSequenceLayer) under an RNN
        # input type
        has_time_axis = x.ndim == 3 or (
            x.ndim == 2 and self.conf.input_type is not None
            and self.conf.input_type.kind == "rnn"
            and not self.layers[0].input_kind() == "ff")
        return has_time_axis and x.shape[1] > self.conf.tbptt_fwd_length

    def _windows(self, x, y, fm, lm):
        T = x.shape[1]
        L = self.conf.tbptt_fwd_length
        for s in range(0, T, L):
            e = min(s + L, T)
            # keep window length static where possible: last ragged window
            # gets its own jit specialization
            xs = x[:, s:e]
            yield (xs, y[:, s:e] if y.ndim == 3 else y,
                   None if fm is None else fm[:, s:e],
                   None if lm is None else lm[:, s:e], self._sample(xs))

    def _scan_steps(self, loss_fn, loss_args, stacked, params, state,
                    opt_state, rng, cstate=None, carries=None):
        """The fused programs' body: one optimizer step a slice of
        ``stacked`` under ``lax.scan``, with the same per-step rng split
        chain as ``fit``. Under a compression scheme ``cstate`` (the
        error-feedback residual and controller) threads through the scan
        carry exactly like opt_state, so K fused steps evolve it
        identically to K per-batch fit() calls; tBPTT threads its
        ``carries`` the same way. ``loss_args(carries, slice, key)`` is
        what follows (params, state) in a call of ``loss_fn``. Returns
        what it was given, in the programs' order (params, state,
        opt_state[, cstate][, carries], rng), and the losses."""
        value_and_grad = jax.value_and_grad(loss_fn, has_aux=True)
        comp = self.grad_compression

        def body(carry, inp):
            params, state, opt_state, cstate, carries, rng = carry
            rng, k = jax.random.split(rng)   # same chain as fit()
            (loss, aux), grads = value_and_grad(
                params, state, *loss_args(carries, inp, k))
            if comp is not None:
                grads, cstate = comp.apply(grads, cstate)
            new_params, new_opt = self._apply_updates(params, grads,
                                                      opt_state)
            new_state, carries = (aux, None) if carries is None else aux
            return (new_params, new_state, new_opt, cstate, carries,
                    rng), loss

        carry, losses = jax.lax.scan(
            body, (params, state, opt_state, cstate, carries, rng), stacked)
        return tuple(t for t in (*carry, losses) if t is not None)

    def _make_fused_train_step(self):
        """K sequential optimizer steps fused into ONE dispatch via lax.scan
        over stacked (K, batch, ...) minibatches — identical math to K
        ``fit`` calls, but the host pays one dispatch instead of K. On
        dispatch-latency-bound paths (small models, high-latency links)
        this is the throughput path; see ``fit_fused``. Two compiled
        variants: with and without masks (None is not scannable, so
        maskless groups pass no mask operands)."""
        def masked(_, inp, k):
            x, y, fm, lm = inp
            return x, y, k, fm, lm

        def nomask(_, inp, k):
            x, y = inp
            return x, y, k, None, None

        if self.grad_compression is not None:
            def train_fused_step_compressed(params, state, opt_state, cstate,
                                            rng, xs, ys, fmasks, lmasks):
                return self._scan_steps(
                    self._loss_fn, masked, (xs, ys, fmasks, lmasks), params,
                    state, opt_state, rng, cstate)

            def train_fused_step_compressed_nomask(params, state, opt_state,
                                                   cstate, rng, xs, ys):
                return self._scan_steps(self._loss_fn, nomask, (xs, ys),
                                        params, state, opt_state, rng, cstate)

            return (jax.jit(train_fused_step_compressed,
                            donate_argnums=(0, 1, 2, 3)),
                    jax.jit(train_fused_step_compressed_nomask,
                            donate_argnums=(0, 1, 2, 3)))

        def train_fused_step(params, state, opt_state, rng, xs, ys, fmasks,
                             lmasks):
            return self._scan_steps(
                self._loss_fn, masked, (xs, ys, fmasks, lmasks), params,
                state, opt_state, rng)

        def train_fused_step_nomask(params, state, opt_state, rng, xs, ys):
            return self._scan_steps(self._loss_fn, nomask, (xs, ys), params,
                                    state, opt_state, rng)

        return (jax.jit(train_fused_step, donate_argnums=(0, 1, 2)),
                jax.jit(train_fused_step_nomask, donate_argnums=(0, 1, 2)))

    def fit_fused(self, datasets, bucket_policy=None) -> "MultiLayerNetwork":
        """Train on a list of equally-shaped DataSets — or a pre-stacked
        ``(xs, ys)`` pair of (K, batch, ...) arrays — in ONE device dispatch
        (lax.scan over the stack). Equivalent to ``fit`` on each in order
        for the jitted SGD-family path (raises for solver/tbptt configs);
        per-step feature/label masks are threaded when any DataSet carries
        them. Listeners fire once per fused group (with the last step's
        score) and ``iteration`` advances by the group size. Pass
        device-resident stacked arrays when re-fitting the same data (a
        fresh host stack re-uploads the whole group each call).

        ``bucket_policy`` (perf.BucketPolicy, or True for the default) lets
        the DataSet-list form carry a ragged final batch: every batch pads
        to one bucket shape with the padding masked out of the loss, and
        the whole group still runs as ONE compiled scan program."""
        if self.params is None:
            self.init()
        # a restored model's resume marker is only meaningful to fit()'s
        # batch loop; consume it so it can't mis-skip a LATER fit call
        self._resume_state = None
        if not is_sgd_family(self.conf):
            raise ValueError("fit_fused supports the jitted SGD-family path "
                             "only; use fit() for solver-based optimization")
        if self.conf.backprop_type == "tbptt":
            raise ValueError("fit_fused does not window tBPTT sequences; "
                             "use fit() for tbptt-configured networks")
        tracer = get_tracer()
        at = self.iteration
        # one call is one turn of the span tree (obs/trace.py), with no
        # stream to wait for; the dispatch runs the whole group
        with tracer.span("train.iteration", step=at), \
                tracer.span("train.step_host", step=at) as host:
            with tracer.span("train.stage", step=at):
                xs, ys, fmasks, lmasks, n_steps = self._stack_group(
                    datasets, bucket_policy)
            batch = int(xs.shape[1])
            host.set(items=n_steps * batch)
            with tracer.span("train.dispatch", step=at,
                             program="train_fused", steps=n_steps):
                losses = self._dispatch_fused(xs, ys, fmasks, lmasks)
            self._finish_step(tracer, losses[-1], batch,
                              lambda: xs[-1][:1], steps=n_steps)
        return self

    def _stack_group(self, datasets, bucket_policy):
        """fit_fused's staging: ``(xs, ys, fmasks, lmasks, n_steps)`` with
        (K, batch, ...) stacks on the device."""
        fmasks = lmasks = None
        if isinstance(datasets, tuple) and len(datasets) == 2:
            xa, ya = datasets
            if not (hasattr(xa, "shape") and hasattr(ya, "shape")):
                raise TypeError(
                    "fit_fused((a, b)) expects pre-stacked (K, batch, ...) "
                    "ARRAYS; pass multiple DataSets as a list")
            xs, ys = jnp.asarray(xa), jnp.asarray(ya)
            if xs.ndim < 3:
                raise ValueError(
                    "pre-stacked inputs must be (K, batch, ...); for one "
                    "batch of (features, labels) use fit()")
            n_steps = int(xs.shape[0])
        else:
            datasets = list(datasets)
            if bucket_policy is not None:
                from deeplearning4j_tpu.perf.bucketing import (BucketPolicy,
                                                               pad_dataset)
                policy = (BucketPolicy() if bucket_policy is True
                          else bucket_policy)
                sizes = [d.num_examples() for d in datasets]
                target = policy.bucket(max(sizes))
                if any(s != target for s in sizes):
                    datasets = [pad_dataset(d, target) for d in datasets]
            xs = jnp.stack([jnp.asarray(d.features) for d in datasets])
            ys = jnp.stack([jnp.asarray(d.labels) for d in datasets])
            n_steps = len(datasets)
            # Mixed mask presence across the group: fill the gaps with
            # all-ones masks of the SAME shape the carried masks have (a
            # fabricated features.shape[:2] mask is only meaningful for
            # (batch, T, ...) sequence features, not 2-D/4-D inputs).
            def _stack_masks(masks):
                present = [np.asarray(m) for m in masks if m is not None]
                if not present:
                    return None
                shape = present[0].shape
                if any(p.shape != shape for p in present):
                    raise ValueError(
                        "fit_fused requires identical mask shapes across the "
                        f"group; got {sorted({p.shape for p in present})}")
                return jnp.stack([
                    jnp.asarray(np.ones(shape, np.float32) if m is None
                                else np.asarray(m)) for m in masks])
            fmasks = _stack_masks([d.features_mask for d in datasets])
            lmasks = _stack_masks([d.labels_mask for d in datasets])
        return xs, ys, fmasks, lmasks, n_steps

    def _dispatch_fused(self, xs, ys, fmasks, lmasks):
        """The one call of the fused program; returns the K losses. A
        compressed fused step threads cstate through the scan carry (same
        error-feedback evolution as K per-batch fit() calls)."""
        step_masked, step_nomask = self._get_jitted("train_fused")
        if fmasks is not None or lmasks is not None:
            self._rng, losses = self._run_step(step_masked, self._rng, xs,
                                               ys, fmasks, lmasks)
        else:
            self._rng, losses = self._run_step(step_nomask, self._rng, xs,
                                               ys)
        return losses

    # ------------------------------------------------- truncated BPTT / state
    def _check_stateful(self):
        for layer in self.layers:
            if not getattr(layer, "supports_stateful", True):
                raise NotImplementedError(
                    f"rnn_time_step is not supported with {type(layer).__name__}: "
                    "the backward direction needs the full sequence (reference "
                    "GravesBidirectionalLSTM.rnnTimeStep throws the same)")

    def rnn_time_step(self, x) -> np.ndarray:
        """Stateful step-by-step inference (reference
        MultiLayerNetwork.rnnTimeStep :2615): carries (h, c) across calls.

        Single-timestep calls — the autoregressive decode shape — ride the
        SAME jitted single-step program the serving decode tier uses
        (``rnn_single_step``): the time axis is added inside the trace, so
        every step after the first dispatches one warmed program with no
        per-call tracing or host-side reshaping. Multi-timestep inputs
        keep the full-sequence ``rnn_step`` program."""
        self._check_stateful()
        x = np.asarray(x)
        squeeze = False
        index_seq = getattr(self.layers[0], "takes_index_sequence", False)
        if index_seq:
            if x.ndim == 1:  # single timestep of ids (batch,)
                squeeze = True
            elif x.ndim == 2 and x.shape[1] == 1:
                x = x[:, 0]
                squeeze = True
            # else: (batch, time) id sequence — already has a time axis
        elif x.ndim == 2:  # single timestep (batch, features)
            squeeze = True
        b = x.shape[0]
        if self._rnn_carries is None:
            self._rnn_carries = self._zero_carries(b)
        else:
            leaves = jax.tree_util.tree_leaves(self._rnn_carries)
            if leaves and leaves[0].shape[0] != b:
                raise ValueError(
                    f"rnn_time_step batch size {b} does not match stored state "
                    f"batch {leaves[0].shape[0]}; call rnn_clear_previous_state() first")
        if squeeze:
            fn = self._get_jitted("rnn_single_step")
            out, self._rnn_carries = fn(self.params, self.state,
                                        self._rnn_carries, jnp.asarray(x))
            return np.asarray(out)
        fn = self._get_jitted("rnn_step")
        out, self._rnn_carries = fn(self.params, self.state,
                                    self._rnn_carries, jnp.asarray(x))
        return np.asarray(out)

    def decode_step_fn(self):
        """Single-step decode lowering for the serving tier
        (serving/decode.py): returns ``f(params, state, carries, tokens)``
        with ``tokens`` a ``(batch,)`` int32 id vector, producing
        ``(logits, new_carries)`` where ``logits`` is the output layer's
        f32 PRE-activation ``(batch, n_out)`` — the sampling input. Token
        ids are mapped to the network's input encoding IN-GRAPH (embedding
        gather for index-sequence nets, one-hot for distribution-input
        nets), so the caller never materializes features on the host. The
        returned callable is pure and jit-ready; the engine owns jitting
        and CompileWatch wrapping."""
        self._check_stateful()
        out_layer = self.layers[-1]
        if not out_layer.is_output_layer():
            raise ValueError("decode_step_fn needs an output layer last "
                             "(RnnOutputLayer) to expose sampling logits")
        index_seq = getattr(self.layers[0], "takes_index_sequence", False)
        n_in = self.conf.layer_input_types()[0].size

        def step(params, state, carries, tokens):
            ids = tokens.astype(jnp.int32)
            if index_seq:
                x = ids[:, None]                              # (b, 1) ids
            else:
                x = jax.nn.one_hot(ids, n_in,
                                   dtype=jnp.float32)[:, None, :]
            _, preout, _, _, new_carries = self._forward(
                params, state, x, False, None, None, carries)
            if not hasattr(preout, "shape"):
                raise ValueError(
                    "decode_step_fn needs a plain-tensor output layer; "
                    f"{type(out_layer).__name__} produces a structured "
                    "pre-output")
            return preout[:, 0, :].astype(jnp.float32), new_carries

        return step

    def decode_vocab_size(self) -> int:
        """Token-id space of the decode loop: the input size (one-hot
        width / embedding vocab). The output layer's n_out must match it
        for closed-loop generation; serving/decode.py enforces that."""
        return int(self.conf.layer_input_types()[0].size)

    def _make_program(self, kind):
        if kind == "train_fused":
            return self._make_fused_train_step()
        if kind == "tbptt_fused":
            return self._make_tbptt_scan_step()
        if kind == "rnn_step":
            def rnn_step(params, state, carries, x):
                r = self._forward(params, state, x, False, None, None,
                                  carries)
                return r[0][-1], r[4]

            return jax.jit(rnn_step)
        if kind == "rnn_single_step":
            # one decode timestep: x has NO time axis ((b,) ids or
            # (b, f) features) — it is added inside the trace and the
            # output squeezed back, so rnn_time_step and the serving
            # decode tier share one warmed program shape per batch
            index_seq = getattr(self.layers[0], "takes_index_sequence",
                                False)

            def rnn_single_step(params, state, carries, x):
                xt = x[:, None] if index_seq else x[:, None, :]
                r = self._forward(params, state, xt, False, None, None,
                                  carries)
                return r[0][-1][:, 0, :], r[4]

            return jax.jit(rnn_single_step)
        if kind == "output":
            def output(params, state, x, fmask):
                return self._forward(params, state, x, False, None,
                                     fmask)[0][-1]

            return jax.jit(output)
        if kind == "score":
            def score(params, state, x, y, fmask, lmask):
                _, preout, _, cur_mask, _ = self._forward(
                    params, state, x, False, None, fmask)
                return (self._output_score(self.layers[-1], y, preout, lmask,
                                           cur_mask)
                        + self._regularization(params))

            return jax.jit(score)
        raise KeyError(kind)

    # -------------------------------------------------------------- pretrain
    def _featurize(self, params, state, x, upto: int):
        """Inference-mode forward through layers[0:upto] (+ the preprocessor
        feeding layer ``upto``) — the input to the pretraining layer."""
        cur_mask = None
        for j in range(upto):
            if j in self._pre:
                x, cur_mask = self._pre[j].apply(x, cur_mask)
            x, _ = self.layers[j].apply(params[j], state[j], x, train=False,
                                        rng=None, mask=cur_mask)
        if upto in self._pre:
            x, _ = self._pre[upto].apply(x, cur_mask)
        return x

    def pretrain(self, data, num_epochs: int = 1):
        """Greedy layerwise pretraining of every pretrainable layer (AE/VAE),
        in order (reference MultiLayerNetwork.pretrain :1172 /
        pretrainLayer)."""
        if self.params is None:
            self.init()
        for i, layer in enumerate(self.layers):
            if getattr(layer, "is_pretrainable", lambda: False)():
                self.pretrain_layer(i, data, num_epochs)
        return self

    def pretrain_layer(self, i: int, data, num_epochs: int = 1):
        """Pretrain one layer: featurize through the frozen stack below, then
        minimize the layer's unsupervised ``pretrain_loss`` — one jitted step
        per minibatch, updating only that layer's params (reference
        pretrainLayer(int layerIdx, DataSetIterator))."""
        layer = self.layers[i]
        if not getattr(layer, "is_pretrainable", lambda: False)():
            raise ValueError(f"layer {i} ({type(layer).__name__}) is not "
                             "pretrainable")
        if self.params is None:
            self.init()
        if isinstance(data, DataSet):
            data = [data]
        key = ("pretrain", i)
        step = self._jit_cache.get(key)
        if step is None:
            # frozen stack below passed separately from the (donated)
            # trainable layer params — the same buffer must not be both
            def loss_fn(p_i, below_params, below_state, s_i, x, rng):
                feats = self._featurize(below_params, below_state, x, i)
                return layer.pretrain_loss(p_i, s_i, feats, rng)

            grad_fn = jax.value_and_grad(loss_fn)

            def pretrain_step(p_i, opt_i, below_params, below_state, s_i,
                              rng, x):
                loss, g = grad_fn(p_i, below_params, below_state, s_i, x, rng)
                g = self._gnorms[i](g)
                updates, opt_i = self._txs[i].update(g, opt_i, p_i)
                new_p = apply_constraints(self.layers[i],
                                          optax.apply_updates(p_i, updates))
                return new_p, opt_i, loss

            step = jax.jit(pretrain_step, donate_argnums=(0, 1))
            self._jit_cache[key] = step
        for _ in range(num_epochs):
            for ds in data:
                x = jnp.asarray(ds.features if isinstance(ds, DataSet) else ds)
                self._rng, k = jax.random.split(self._rng)
                p_i, opt_i, loss = step(self.params[i], self.opt_state[i],
                                        self.params[:i], self.state[:i],
                                        self.state[i], k, x)
                self.params[i] = p_i
                self.opt_state[i] = opt_i
                self._score = loss
                for listener in self.listeners:
                    listener.iteration_done(self, self.iteration, self.epoch)
                self.iteration += 1
        return self

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, num_epochs: int = 1,
            bucket_policy=None, prefetch: bool = False,
            checkpoint_manager=None):
        """Train (reference MultiLayerNetwork.fit(DataSetIterator) :1156 and
        fit(INDArray, INDArray)). ``data`` may be a DataSetIterator-like
        iterable of DataSets, a DataSet, or a features array with ``labels``.

        ``bucket_policy`` (a perf.BucketPolicy, or True for the default)
        pads every batch to a canonical bucket shape with the padded rows
        masked out of the loss — an epoch with a ragged final batch then
        runs ONE compiled program instead of recompiling the train step for
        the tail (perf/bucketing.py; exact math for row-independent models,
        see pad_dataset). ``prefetch=True`` stages each batch onto the
        device while the previous step runs (perf/prefetch.py).

        ``checkpoint_manager`` (checkpoint.CheckpointManager) snapshots
        params + updater state + rng + counters per its triggers after
        each optimizer step, asynchronously and crash-consistently. A model
        returned by ``restore_latest()`` carries a resume marker: its next
        ``fit`` treats ``num_epochs`` as the run's TOTAL epoch target,
        skipping the batches the checkpoint already consumed in its epoch
        and continuing the restored rng chain — resume is bitwise-identical
        to the uninterrupted run (``data`` must replay deterministically,
        e.g. a list or a re-iterable iterator in a fixed order)."""
        if self.params is None:
            self.init()
        caller_iterator = labels is None and not isinstance(data, DataSet)
        if labels is not None:
            data = [DataSet(np.asarray(data), np.asarray(labels))]
        elif isinstance(data, DataSet):
            data = [data]
        if not is_sgd_family(self.conf):
            # full-batch solver path (reference Solver.java dispatch on
            # OptimizationAlgorithm — LBFGS / CG / line gradient descent)
            if bucket_policy is not None or prefetch:
                import logging
                logging.getLogger(__name__).warning(
                    "fit(bucket_policy=/prefetch=) is ignored on the "
                    "solver path (%s): these options apply to the jitted "
                    "SGD step loop only", self.conf.optimization_algo)
            from deeplearning4j_tpu.optimize.solvers import Solver
            solver = Solver(self.conf.optimization_algo)

            def solve_batch(ds):
                solver.optimize(self, ds)
                self.last_batch_size = ds.num_examples()
                for listener in self.listeners:
                    listener.iteration_done(self, self.iteration, self.epoch)
                self.iteration += 1

            run_epochs(self, data, num_epochs, solve_batch,
                       checkpoint_manager=checkpoint_manager)
            return self
        train_step = self._get_jitted("train")
        source = data
        record = getattr(self, "_tuning_record", None)
        if (caller_iterator and record is not None
                and getattr(record, "batch_size", 0)):
            # the tuned batch size is not advisory: a caller-supplied
            # iterator is re-sliced to the size the record was tuned at,
            # ABOVE the resume skip (like bucketing) so replay after a
            # restore sees the identical batch stream
            tuned = int(record.batch_size)
            bs = getattr(data, "batch_size", None)
            declared = bs() if callable(bs) else None
            if declared != tuned:
                from deeplearning4j_tpu.perf.bucketing import (
                    RebatchDataSetIterator)
                data = RebatchDataSetIterator(data, tuned)
        if bucket_policy is not None:
            from deeplearning4j_tpu.perf.bucketing import (
                BucketPadDataSetIterator, BucketPolicy)
            policy = (BucketPolicy() if bucket_policy is True
                      else bucket_policy)
            # bucketing sits ABOVE the resume skip: pad targets must evolve
            # exactly as in the uninterrupted run (they feed the jit shapes
            # and, for batch-coupled layers like BN, the math)
            data = BucketPadDataSetIterator(data, policy)
        if data is not source:
            # neither wrapper hands bind_epoch on: the reader under them
            # is bound here, the loop binds what it is given
            bind_epoch(self, source)
        run_epochs(self, data, num_epochs,
                   lambda ds: self._fit_batch(train_step, ds),
                   prefetch={} if prefetch else None,
                   checkpoint_manager=checkpoint_manager)
        return self

    def _make_tbptt_scan_step(self):
        """All tBPTT windows of one sequence batch fused into ONE dispatch:
        lax.scan over (W, batch, L, ...) window stacks, threading the RNN
        carries through the scan carry. Gradient truncation semantics are
        IDENTICAL to the per-window loop — each scan iteration runs its own
        value_and_grad, and the carries passed forward are values, not
        differentiated across windows. Same rng split chain as _fit_tbptt."""
        def window(carries, inp, k):
            x, y = inp
            return carries, x, y, k, None, None

        if self.grad_compression is not None:
            def tbptt_fused_step_compressed(params, state, opt_state, cstate,
                                            carries, rng, xw, yw):
                return self._scan_steps(
                    self._loss_fn_tbptt, window, (xw, yw), params, state,
                    opt_state, rng, cstate, carries)

            return jax.jit(tbptt_fused_step_compressed,
                           donate_argnums=(0, 1, 2, 3, 4))

        def tbptt_fused_step(params, state, opt_state, carries, rng, xw, yw):
            return self._scan_steps(
                self._loss_fn_tbptt, window, (xw, yw), params, state,
                opt_state, rng, carries=carries)

        return jax.jit(tbptt_fused_step, donate_argnums=(0, 1, 2, 3))

    def fit_tbptt_fused(self, x, y) -> "MultiLayerNetwork":
        """Train one (batch, T, ...) sequence batch with ALL full tBPTT
        windows fused into one dispatch (T must be a multiple of
        ``tbptt_fwd_length``; masks unsupported — use ``fit``). Exactly
        equivalent to the per-window path; listeners fire once per call and
        ``iteration`` advances by the window count."""
        if self.params is None:
            self.init()
        self._resume_state = None  # see fit_fused note
        if self.conf.backprop_type != "tbptt":
            raise ValueError("fit_tbptt_fused requires backprop_type='tbptt' "
                             "(this network is 'standard'; use fit/fit_fused)")
        L = self.conf.tbptt_fwd_length
        T = int(np.shape(x)[1])
        if T % L != 0:
            raise ValueError(f"sequence length {T} must be a multiple of "
                             f"tbptt_fwd_length {L} for the fused path")
        w = T // L
        b = int(np.shape(x)[0])
        tracer = get_tracer()
        at = self.iteration
        # one call is one turn of the span tree (obs/trace.py), with no
        # stream to wait for; the dispatch runs all w windows
        with tracer.span("train.iteration", step=at), \
                tracer.span("train.step_host", step=at, items=b * w):
            with tracer.span("train.stage", step=at):
                x = jnp.asarray(x)
                y = jnp.asarray(y)
                # (b, T, ...) -> (W, b, L, ...)
                xw = jnp.moveaxis(x.reshape((b, w, L) + x.shape[2:]), 1, 0)
                yw = (jnp.moveaxis(y.reshape((b, w, L) + y.shape[2:]), 1, 0)
                      if y.ndim == 3
                      else jnp.broadcast_to(y, (w,) + y.shape))
                carries = self._zero_carries(b)
            with tracer.span("train.dispatch", step=at,
                             program="tbptt_fused", steps=w):
                _, self._rng, losses = self._run_step(
                    self._get_jitted("tbptt_fused"), carries, self._rng, xw,
                    yw)
            self._window_scores = losses
            self._finish_step(tracer, losses[-1], b, lambda: x[:1], steps=w)
        return self

    def window_scores(self):
        """Every window's loss of the last ``fit_tbptt_fused`` call, in
        order: the (windows,) array the scan returned, still on the device
        (reading it is the caller's sync, not the fit's). None before the
        first call. ``score()`` is its last element."""
        return self._window_scores

    # ---------------------------------------------------------------- output
    def output(self, x, train: bool = False, features_mask=None) -> np.ndarray:
        """Inference forward pass (reference MultiLayerNetwork.output :1947;
        the 4-arg overload output(input, train, fMask, lMask) threads the
        features mask through the forward pass)."""
        if self.params is None:
            self.init()
        fn = self._get_jitted("output")
        fm = None if features_mask is None else jnp.asarray(features_mask)
        return np.asarray(fn(self.params, self.state, jnp.asarray(x), fm))

    def predict(self, x, features_mask=None) -> np.ndarray:
        """Class indices (reference MultiLayerNetwork.predict)."""
        return np.argmax(self.output(x, features_mask=features_mask), axis=-1)

    def feed_forward(self, x, train: bool = False):
        """All layer activations (reference feedForward :852)."""
        acts = self._forward(self.params, self.state, jnp.asarray(x),
                             train, None, None)[0]
        return [np.asarray(a) for a in acts]

    def evaluate(self, iterator):
        """Classification evaluation over an iterator (reference
        MultiLayerNetwork.evaluate)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        e = Evaluation()
        for ds in iterator:
            out = self.output(ds.features, features_mask=ds.features_mask)
            e.eval(ds.labels, out, mask=ds.labels_mask)
        return e

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation
        e = RegressionEvaluation()
        for ds in iterator:
            out = self.output(ds.features, features_mask=ds.features_mask)
            e.eval(ds.labels, out, mask=ds.labels_mask)
        return e

    # ------------------------------------------------------------- utilities
    def clone(self) -> "MultiLayerNetwork":
        # Deep-copy the buffers: train steps are jitted with buffer donation,
        # so aliasing the live arrays would leave the clone holding deleted
        # buffers after the next fit() on either network.
        other = MultiLayerNetwork(self.conf)
        if self.params is not None:
            other.params = jax.tree_util.tree_map(jnp.array, self.params)
            other.state = jax.tree_util.tree_map(jnp.array, self.state)
            other.opt_state = jax.tree_util.tree_map(jnp.array, self.opt_state)
            other._rng = self._rng
        other.grad_compression = self.grad_compression
        other.augmentation = self.augmentation
        if self.compress_state is not None:
            other.compress_state = jax.tree_util.tree_map(
                jnp.array, self.compress_state)
        return other

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

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.nn.conf.graph import (
    ComputationGraphConfiguration, DuplicateToTimeSeriesVertex, LastTimeStepVertex,
)
from deeplearning4j_tpu.nn.conf.layers import (Layer, apply_layer,
                                               dropout_input, noisy_params)
from deeplearning4j_tpu.nn.engine import Network, _f32, run_epochs
from deeplearning4j_tpu.obs.owners import LOSS_SCORE, layer_marker


def _arrays(items):
    """A MultiDataSet's list of arrays (or of masks, None where an input
    has none) as device arrays; None stays None."""
    return (None if items is None else
            [None if a is None else jnp.asarray(a) for a in items])


def run_vertices(order, vertices, vpre, input_types, params, state, acts,
                 mask_of, train: bool, rng, carries=None):
    """Walk wired vertices in ``order``: ``acts`` and ``mask_of`` hold the
    inputs' activations and masks on entry and every vertex's on return.
    ``params`` / ``state`` hold an entry for every vertex; a layer whose
    ``tied_to`` names another vertex is handed that vertex's parameters
    beside its own (``tied_params``). Returns (output layers' preouts, new
    state, new carries). The whole graph's forward pass
    and a ``LoopVertex``'s body both run here, so a layer is applied in one
    place (``apply_layer``: its ``<LayerClass>:<name>`` scope, its ``remat``
    knob).

    What a layer may read: its FIRST input as ``x`` (after the vertex's
    preprocessor), and every further input by the keyword its type names for
    it (``Layer.extra_inputs``: ``res``, ``memory``, ``kv``); a vertex that
    is no layer reads all its inputs in order. What a vertex may read is
    another vertex's output (``"l3_ffn"``) or a VALUE a layer hands on
    beside its output (``"l16_ssm.scan"``, ``"l17_attn.kv"``:
    ``Layer.shared_values``; such a layer's ``apply`` returns ``(out,
    {name: value})`` and the values lie in ``acts`` under
    ``<vertex>.<name>``). Autodiff sums a value's cotangents over its
    readers, whichever layer made it; under a layer's ``remat`` the values
    it hands on are outputs of its checkpoint like its output, so they are
    kept once for all their readers."""
    new_state = {}
    new_carries = {}
    preouts = {}
    for name in order:
        obj, in_names = vertices[name]
        xs = [acts[i] for i in in_names]
        in_mask = next((mask_of[i] for i in in_names if mask_of[i] is not None), None)
        k = None
        if rng is not None:
            rng, k = jax.random.split(rng)
        # what runs for a vertex outside ``apply_layer`` lies under the
        # vertex's marker too (obs/owners.py)
        marker = layer_marker(obj, name)
        if isinstance(obj, Layer):
            with jax.named_scope(marker):
                if name in vpre:
                    xs = list(xs)
                    xs[0], in_mask = vpre[name].apply(xs[0], in_mask)
                p_v = noisy_params(obj, params[name], k, train)
                if getattr(obj, "tied_to", ""):
                    # a head tied to the embedding reads that vertex's leaf
                    p_v = obj.tied_params(p_v, params[obj.tied_to])
            if obj.is_output_layer():
                with jax.named_scope(marker):
                    x_in = dropout_input(xs[0], obj.dropout, train, k)
                    z = obj.pre_output(p_v, x_in)
                    # loss math in f32 (z may be a pytree: CenterLoss/YOLO)
                    z = jax.tree_util.tree_map(_f32, z)
                    preouts[name] = z
                    out = obj.output_activations(z)
                new_state[name] = state[name]
            elif (carries is not None and hasattr(obj, "apply_seq")
                  and getattr(obj, "supports_stateful", True)):
                with jax.named_scope(marker):
                    x_in = dropout_input(xs[0], obj.dropout, train, k)
                    out, nc = obj.apply_seq(p_v, carries[name], x_in,
                                            train=train, rng=None,
                                            mask=in_mask)
                new_carries[name] = nc
                new_state[name] = state[name]
            else:
                # the vertex's inputs after the first, by the keywords the
                # layer's type names for them (``extra_inputs``: the fused
                # conv block's residual-add operand, a memory, another
                # layer's keys and values)
                extra = dict(zip(obj.extra_inputs, xs[1:])) or None
                # apply_layer lowers through jax.checkpoint when the
                # layer's remat= knob is set (perf/fusion.py policies)
                out, st = apply_layer(obj, p_v, state[name], xs[0],
                                      train=train, rng=k, mask=in_mask,
                                      name=name, extra=extra)
                new_state[name] = st
                shared = obj.shared_values(input_types[name][0])
                if shared:
                    # what the layer hands on beside its output: a later
                    # vertex reads it as ``<name>.<value>``
                    out, values = out
                    for key in shared:
                        acts[f"{name}.{key}"] = values[key]
                        mask_of[f"{name}.{key}"] = in_mask
            out_kind = obj.output_type(input_types[name][0]).kind
            mask_of[name] = in_mask if out_kind in ("rnn", "cnn1d") else None
        else:
            if isinstance(obj, LastTimeStepVertex):
                m = in_mask
                if obj.mask_input is not None:
                    m = mask_of.get(obj.mask_input)
                with jax.named_scope(marker):
                    out = obj.apply(*xs, mask=m)
                mask_of[name] = None
            elif isinstance(obj, DuplicateToTimeSeriesVertex):
                t = acts[obj.reference_input].shape[1]
                with jax.named_scope(marker):
                    out = obj.apply(*xs, time_steps=t)
                mask_of[name] = mask_of.get(obj.reference_input)
            else:
                with jax.named_scope(marker):
                    out = obj.apply(*xs)
                mask_of[name] = in_mask
            new_state[name] = state[name]
        acts[name] = out
    return preouts, new_state, new_carries


class ComputationGraph(Network):
    """The DAG's forward pass, staging and programs over ``nn/engine.py``'s
    ``Network``, which holds the steps and the fit path."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.order: List[str] = conf.topological_order()
        self.vertices = conf.wired_vertices()
        self.vertex_input_types = conf.vertex_input_types()
        self._vpre = conf.resolved_vertex_preprocessors()
        self._layer_names = [n for n in self.order
                             if isinstance(self.vertices[n][0], Layer)]
        self._param_layers = [(n, self.vertices[n][0])
                              for n in self._layer_names]
        super().__init__(conf)
        for out in conf.network_outputs:
            obj = self.vertices[out][0]
            if not (isinstance(obj, Layer) and obj.is_output_layer()):
                raise ValueError(f"Network output '{out}' must be an output/loss layer")

    def _collect(self, entries, like=None):
        # vertices that are no layers keep what ``like`` holds for them
        return {**like, **entries} if like is not None else dict(entries)

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
        rng = self._seeded_key(seed, validate)
        if params is not None:
            return self._init_from(params, rng)
        return self._init_drawn(rng)

    def _draw(self, rng):
        """What ``init`` makes: every vertex's parameters and state (none
        for a vertex that is no layer), and the key that is left."""
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

    def _init_from(self, given: Dict[str, dict], rng) -> "ComputationGraph":
        # the same splits of ``rng`` as a drawn init; under ``jit`` the
        # draws nobody reads are never computed
        want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                      jax.eval_shape(self._draw, rng)[0])
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
            _, state, rng = self._draw(rng)
            return state, self.init_opt_state(params), rng

        self.params = params
        self.state, self.opt_state, self._rng = jax.jit(rest)(rng, params)
        return self

    # --------------------------------------------------------------- forward
    def _forward(self, params, state, inputs: Sequence, train: bool, rng,
                 masks, carries=None):
        """Trace the DAG. Returns (activations dict, preouts dict, new_state,
        mask dict[, new_carries when ``carries`` is given]).

        ``carries`` (dict vertex->carry pytree) selects the stateful
        sequence path of recurrent layer vertices (``apply_seq``), mirroring
        the MLN carry threading — the graph analogue of the reference's
        rnnActivateUsingStoredState (ComputationGraph.java:2402)."""
        params, inputs = self._to_compute_dtype(params, list(inputs))
        acts: Dict[str, jnp.ndarray] = {}
        mask_of: Dict[str, Optional[jnp.ndarray]] = {}
        for i, name in enumerate(self.conf.network_inputs):
            acts[name] = inputs[i]
            mask_of[name] = None if masks is None else masks[i]
        preouts, new_state, new_carries = run_vertices(
            self.order, self.vertices, self._vpre, self.vertex_input_types,
            params, state, acts, mask_of, train, rng, carries)
        if carries is not None:
            for n in carries:
                new_carries.setdefault(n, carries[n])
            return acts, preouts, new_state, mask_of, new_carries
        return acts, preouts, new_state, mask_of

    # ------------------------------------------------- the engine's hooks
    def _augment(self, inputs, rng):
        # every image-shaped input, seeded per input off the ONE split of
        # the step key
        return [self.augmentation.apply(x, jax.random.fold_in(rng, i))
                if x.ndim == 4 else x for i, x in enumerate(inputs)]

    def _forward_loss(self, params, state, inputs, labels, rng, fmasks,
                      lmasks, carries):
        """Loss over all output layers, summed as in the reference."""
        fwd = self._forward(params, state, inputs, True, rng, fmasks, carries)
        _, preouts, new_state, mask_of = fwd[:4]
        scores = [self._output_score(
            self.vertices[out_name][0], labels[j], preouts[out_name],
            None if lmasks is None else lmasks[j], mask_of.get(out_name))
            for j, out_name in enumerate(self.conf.network_outputs)]
        with jax.named_scope(LOSS_SCORE):
            loss = sum(scores[1:], scores[0])
        return loss, (new_state if carries is None else (new_state, fwd[4]))

    def _loss_fn_tbptt(self, params, state, carries, inputs, labels, rng,
                       fmasks, lmasks):
        """Window loss with carried (but not differentiated) RNN state —
        graph analogue of reference ComputationGraph.java:1158
        (doTruncatedBPTT dispatch in fit)."""
        return self._loss_fn(params, state, inputs, labels, rng, fmasks,
                             lmasks, carries=carries)

    def _stage(self, ds):
        mds = MultiDataSet.from_dataset(ds) if isinstance(ds, DataSet) else ds
        return (_arrays(mds.features), _arrays(mds.labels),
                _arrays(mds.features_masks), _arrays(mds.labels_masks))

    def _rows(self, inputs) -> int:
        return int(inputs[0].shape[0])

    def _sample(self, inputs):
        # first sample per input only (see the engine's _finish_step)
        return lambda: [f[:1] for f in inputs]

    def _time_sliceable(self, i, x):
        """Whether graph input i carries a time axis to window over."""
        if x.ndim == 3:
            return True
        its = self.conf.input_types
        it = its[i] if i < len(its) else None
        return (x.ndim == 2 and it is not None and it.kind == "rnn"
                and jnp.issubdtype(x.dtype, jnp.integer))

    def _wants_tbptt(self, inputs) -> bool:
        if self.conf.backprop_type != "tbptt":
            return False
        sliceable = [x.shape[1] for i, x in enumerate(inputs)
                     if self._time_sliceable(i, x)]
        return bool(sliceable) and max(sliceable) > self.conf.tbptt_fwd_length

    def _windows(self, inputs, labels, fmasks, lmasks):
        T = max(x.shape[1] for i, x in enumerate(inputs)
                if self._time_sliceable(i, x))
        L = self.conf.tbptt_fwd_length
        for s in range(0, T, L):
            e = min(s + L, T)
            # a graph's windows bring no feature sample
            yield ([x[:, s:e] if self._time_sliceable(i, x) else x
                    for i, x in enumerate(inputs)],
                   [y[:, s:e] if y.ndim == 3 else y for y in labels],
                   None if fmasks is None else
                   [None if m is None else m[:, s:e] for m in fmasks],
                   None if lmasks is None else
                   [None if m is None else m[:, s:e] for m in lmasks], None)

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

    def _make_program(self, kind):
        if kind == "rnn_step":
            def rnn_step(params, state, carries, xs):
                acts, _, _, _, nc = self._forward(
                    params, state, xs, False, None, None, carries)
                return [acts[n] for n in self.conf.network_outputs], nc

            return jax.jit(rnn_step)
        if kind == "output":
            def output(params, state, inputs, fmasks):
                acts, _, _, _ = self._forward(params, state, inputs, False,
                                              None, fmasks)
                return [acts[n] for n in self.conf.network_outputs]

            return jax.jit(output)
        if kind == "score":
            def score(params, state, inputs, labels, fmasks, lmasks):
                return self._loss_fn(params, state, inputs, labels, None,
                                     fmasks, lmasks)[0]

            return jax.jit(score)
        raise KeyError(kind)

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
            # the uninterrupted run. The loop binds the epoch of what it
            # is given: the wrapper does not hand bind_epoch on, so an
            # epoch-aware reader under it stays unbound (a stack binds it:
            # ROADMAP "Bucket wrappers drop bind_epoch")
            data = BucketPadDataSetIterator(data, policy)
        step = self._get_jitted("train")
        run_epochs(self, data, num_epochs,
                   lambda ds: self._fit_batch(step, ds),
                   prefetch={} if prefetch else None,
                   checkpoint_manager=checkpoint_manager)
        return self

    # ---------------------------------------------------------------- output
    def output(self, *inputs, features_masks=None) -> List[np.ndarray]:
        """Multi-output inference (reference ComputationGraph.output; the
        mask-threading overload ComputationGraph.java:1428 — masked sequence
        vertices like Bidirectional/LastTimeStep read only valid steps)."""
        if self.params is None:
            self.init()
        fn = self._get_jitted("output")
        outs = fn(self.params, self.state, _arrays(inputs),
                  _arrays(features_masks))
        return [np.asarray(o) for o in outs]

    def output_single(self, *inputs, features_masks=None) -> np.ndarray:
        return self.output(*inputs, features_masks=features_masks)[0]

    def predict(self, *inputs, features_masks=None) -> np.ndarray:
        return np.argmax(
            self.output_single(*inputs, features_masks=features_masks), axis=-1)

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

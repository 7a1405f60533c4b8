"""Transfer learning: freeze/replace/append layers on trained networks.

Parity surface: reference
deeplearning4j-nn/.../nn/transferlearning/TransferLearning.java (847 LoC,
Builder API), FineTuneConfiguration.java, TransferLearningHelper.java.

Freezing is expressed as a per-layer ``NoOp`` updater (the mechanism the
reference's FrozenLayer uses underneath), so the frozen layers still live
inside the single jit-compiled train step — XLA dead-code-eliminates their
update math.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import NoOp, Updater


def _graph_ancestors(vertices, names):
    """Transitive input closure (incl. ``names``) over a vertex mapping
    name -> (obj, input_names)."""
    seen = set()
    stack = list(names)
    while stack:
        cur = stack.pop()
        if cur not in vertices:
            # ``<vertex>.<value>``: a value a layer hands on beside its output
            cur = cur.rpartition(".")[0]
        if cur in seen or cur not in vertices:
            continue
        seen.add(cur)
        stack.extend(vertices[cur][1])
    return seen


def _copy_matching(src_params, src_state, dst_params, dst_state, name):
    """Copy one vertex/layer's params+state when pytree structure and leaf
    shapes match. jnp.array copies because the source buffers may be
    donation targets of the source net's own jitted step. Returns True if
    copied."""
    src, dst = src_params[name], dst_params[name]
    if jax.tree_util.tree_structure(src) != jax.tree_util.tree_structure(dst):
        return False
    if not all(a.shape == b.shape for a, b in zip(
            jax.tree_util.tree_leaves(src), jax.tree_util.tree_leaves(dst))):
        return False
    dst_params[name] = jax.tree_util.tree_map(jnp.array, src)
    dst_state[name] = jax.tree_util.tree_map(jnp.array, src_state[name])
    return True


@dataclasses.dataclass(frozen=True)
class FineTuneConfiguration:
    """Global overrides applied to all non-frozen layers (reference
    FineTuneConfiguration.java)."""

    updater: Optional[Updater] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    dropout: Optional[float] = None
    weight_init: Optional[str] = None
    seed: Optional[int] = None

    def _apply(self, layer):
        updates = {}
        for f in ("updater", "l1", "l2", "dropout", "weight_init"):
            v = getattr(self, f)
            if v is not None and hasattr(layer, f):
                updates[f] = v
        return dataclasses.replace(layer, **updates) if updates else layer


class TransferLearning:
    """Entry point mirroring ``new TransferLearning.Builder(net)``."""

    class Builder:
        def __init__(self, net: MultiLayerNetwork):
            if net.params is None:
                net.init()
            self._net = net
            self._layers = list(net.conf.layers)
            self._keep_params: List[bool] = [True] * len(self._layers)
            self._frozen_upto = -1
            self._fine_tune: Optional[FineTuneConfiguration] = None

        def fine_tune_configuration(self, ftc: FineTuneConfiguration):
            self._fine_tune = ftc
            return self

        def set_feature_extractor(self, layer_index: int):
            """Freeze layers [0..layer_index] (reference setFeatureExtractor)."""
            self._frozen_upto = layer_index
            return self

        def remove_output_layer(self):
            return self.remove_layers_from_output(1)

        def remove_layers_from_output(self, n: int):
            for _ in range(n):
                self._layers.pop()
                self._keep_params.pop()
            return self

        def add_layer(self, layer):
            self._layers.append(layer)
            self._keep_params.append(False)
            return self

        def n_out_replace(self, layer_index: int, n_out: int,
                          weight_init: Optional[str] = None):
            """Replace layer's n_out, re-initializing it and widening the next
            layer's n_in (reference nOutReplace)."""
            layer = self._layers[layer_index]
            updates = {"n_out": n_out}
            if weight_init is not None:
                updates["weight_init"] = weight_init
            self._layers[layer_index] = dataclasses.replace(layer, **updates)
            self._keep_params[layer_index] = False
            if layer_index + 1 < len(self._layers):
                nxt = self._layers[layer_index + 1]
                if hasattr(nxt, "n_in"):
                    self._layers[layer_index + 1] = dataclasses.replace(nxt, n_in=None)
                    self._keep_params[layer_index + 1] = False
            return self

        def build(self) -> MultiLayerNetwork:
            layers = []
            for i, layer in enumerate(self._layers):
                if i <= self._frozen_upto:
                    if hasattr(layer, "updater"):
                        layer = dataclasses.replace(layer, updater=NoOp())
                elif self._fine_tune is not None:
                    layer = self._fine_tune._apply(layer)
                layers.append(layer)
            old = self._net.conf
            conf = dataclasses.replace(
                old, layers=tuple(layers),
                seed=(self._fine_tune.seed if self._fine_tune and
                      self._fine_tune.seed is not None else old.seed),
                updater=(self._fine_tune.updater if self._fine_tune and
                         self._fine_tune.updater is not None else old.updater))
            new_net = MultiLayerNetwork(conf).init()
            # copy retained params (reference: params view copy in build())
            for i, keep in enumerate(self._keep_params):
                if keep and i < len(self._net.params):
                    _copy_matching(self._net.params, self._net.state,
                                   new_net.params, new_net.state, i)
            return new_net


    class GraphBuilder:
        """Graph transfer learning (reference TransferLearning.java:447
        GraphBuilder: setFeatureExtractor / removeVertexAndConnections /
        addLayer / addVertex / setOutputs / nOutReplace on a trained
        ComputationGraph). Retained vertices keep their trained params;
        frozen vertices additionally train with a NoOp updater inside the
        same jitted step."""

        def __init__(self, net):
            from deeplearning4j_tpu.nn.graph import ComputationGraph
            if not isinstance(net, ComputationGraph):
                raise TypeError("GraphBuilder wraps a ComputationGraph; use "
                                "TransferLearning.Builder for MLNs")
            if net.params is None:
                net.init()
            self._net = net
            conf = net.conf
            self._vertices = {n: (obj, tuple(ins))
                              for n, (obj, ins) in conf.vertices.items()}
            self._outputs = list(conf.network_outputs)
            self._keep = {n: True for n in self._vertices}
            self._frozen: set = set()
            self._fine_tune: Optional[FineTuneConfiguration] = None

        def fine_tune_configuration(self, ftc: FineTuneConfiguration):
            self._fine_tune = ftc
            return self

        # ---- freezing -------------------------------------------------
        def _ancestors(self, names):
            return _graph_ancestors(self._vertices, names)

        def set_feature_extractor(self, *vertex_names: str):
            """Freeze the named vertices and everything upstream of them
            (reference setFeatureExtractor(String...))."""
            for v in vertex_names:
                if v not in self._vertices:
                    raise KeyError(f"Unknown vertex '{v}'")
            self._frozen = self._ancestors(vertex_names)
            return self

        # ---- surgery --------------------------------------------------
        def remove_vertex_and_connections(self, name: str):
            """Remove the vertex and its edges: consumers drop it from
            their input lists but otherwise survive (reference
            removeVertexAndConnections — downstream vertices are left for
            the caller to re-wire; a consumer left with no inputs fails
            DAG validation at build() with a clear error)."""
            if name not in self._vertices:
                raise KeyError(f"Unknown vertex '{name}'")
            del self._vertices[name]
            self._keep.pop(name, None)
            self._frozen.discard(name)
            for n, (obj, ins) in list(self._vertices.items()):
                if name in ins:
                    self._vertices[n] = (
                        obj, tuple(i for i in ins if i != name))
            self._outputs = [o for o in self._outputs if o != name]
            return self

        def remove_vertex_keep_connections(self, name: str):
            """Remove only the named vertex; callers must re-add a vertex
            with the same name before build() so consumers re-wire
            (reference removeVertexKeepConnections)."""
            if name not in self._vertices:
                raise KeyError(f"Unknown vertex '{name}'")
            del self._vertices[name]
            self._keep.pop(name, None)
            self._frozen.discard(name)
            return self

        def add_layer(self, name: str, layer, *inputs: str):
            self._vertices[name] = (layer, tuple(inputs))
            self._keep[name] = False
            return self

        def add_vertex(self, name: str, vertex, *inputs: str):
            self._vertices[name] = (vertex, tuple(inputs))
            self._keep[name] = False
            return self

        def set_outputs(self, *names: str):
            self._outputs = list(names)
            return self

        def n_out_replace(self, name: str, n_out: int,
                          weight_init: Optional[str] = None):
            """Resize a layer vertex's output, re-initializing it; consumers
            re-initialize automatically via the shape check at param-copy
            time (reference nOutReplace)."""
            obj, ins = self._vertices[name]
            updates = {"n_out": n_out}
            if weight_init is not None:
                updates["weight_init"] = weight_init
            self._vertices[name] = (dataclasses.replace(obj, **updates), ins)
            self._keep[name] = False
            return self

        def build(self):
            from deeplearning4j_tpu.nn.graph import ComputationGraph
            vertices = {}
            for n, (obj, ins) in self._vertices.items():
                from deeplearning4j_tpu.nn.conf.layers import Layer
                if isinstance(obj, Layer):
                    if n in self._frozen:
                        if hasattr(obj, "updater"):
                            obj = dataclasses.replace(obj, updater=NoOp())
                    elif self._fine_tune is not None:
                        obj = self._fine_tune._apply(obj)
                vertices[n] = (obj, ins)
            old = self._net.conf
            conf = dataclasses.replace(
                old, vertices=vertices, network_outputs=tuple(self._outputs),
                seed=(self._fine_tune.seed if self._fine_tune and
                      self._fine_tune.seed is not None else old.seed),
                updater=(self._fine_tune.updater if self._fine_tune and
                         self._fine_tune.updater is not None else old.updater))
            new_net = ComputationGraph(conf).init()
            for n, keep in self._keep.items():
                if keep and n in self._net.params:
                    _copy_matching(self._net.params, self._net.state,
                                   new_net.params, new_net.state, n)
            return new_net


class TransferLearningHelper:
    """Featurize-through-frozen-layers helper (reference
    TransferLearningHelper.java): split at the frozen boundary and train only
    the unfrozen tail on pre-computed features.

    MLN form: ``TransferLearningHelper(mln, frozen_upto_index)``.
    Graph form: ``TransferLearningHelper(graph, "boundary_vertex", ...)`` —
    the named vertices (and everything upstream) are the frozen trunk;
    ``featurize`` returns their outputs and ``unfrozen_graph()`` is a
    trainable sub-graph whose inputs are those boundary activations."""

    def __init__(self, net, *frozen_boundary, frozen_upto: Optional[int] = None):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        self._graph_mode = isinstance(net, ComputationGraph)
        self._net = net
        if not self._graph_mode:
            if frozen_upto is None:
                (frozen_upto,) = frozen_boundary
            self._split = frozen_upto + 1
            return
        if net.params is None:
            net.init()
        if not frozen_boundary:
            raise ValueError("graph helper needs >=1 frozen boundary vertex")
        self._boundary = [str(v) for v in frozen_boundary]
        conf = net.conf
        for v in self._boundary:
            if v not in conf.vertices:
                raise KeyError(f"Unknown vertex '{v}'")
        # frozen = ancestors of the boundary (incl. boundary)
        self._frozen = _graph_ancestors(conf.vertices, self._boundary)
        self._sub = None
        self._featurize_fn = None

    # ------------------------------------------------------------- MLN path
    def featurize(self, x):
        if not self._graph_mode:
            acts = self._net.feed_forward(x)
            return acts[self._split - 1]
        import numpy as np
        if self._featurize_fn is None:
            net, boundary = self._net, tuple(self._boundary)

            # only the boundary activations are jit outputs: XLA dead-code
            # eliminates every unfrozen branch instead of materializing all
            # intermediate feature maps
            def bfn(params, state, inputs):
                acts, _, _, _ = net._forward(params, state, inputs, False,
                                             None, None)
                return [acts[v] for v in boundary]

            self._featurize_fn = jax.jit(bfn)
        acts = self._featurize_fn(
            self._net.params, self._net.state,
            [jnp.asarray(f) for f in (x if isinstance(x, (list, tuple))
                                      else [x])])
        return [np.asarray(a) for a in acts]

    # ----------------------------------------------------------- graph path
    def unfrozen_graph(self):
        """Trainable sub-graph over the non-frozen vertices; its inputs are
        the boundary vertices (plus any original inputs an unfrozen vertex
        still reads directly). Params are shared-by-copy from the parent."""
        if not self._graph_mode:
            raise TypeError("unfrozen_graph() is graph-mode only")
        if self._sub is not None:
            return self._sub
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        conf = self._net.conf
        out_types = conf.vertex_output_types()
        keep = {n: v for n, v in conf.vertices.items() if n not in self._frozen}
        inputs, input_types = [], []
        for n in self._boundary:
            inputs.append(n)
            input_types.append(out_types[n])
        for n, (obj, ins) in keep.items():
            for i in ins:
                if (i in conf.network_inputs or i in self._frozen) \
                        and i not in inputs:
                    inputs.append(i)
                    input_types.append(out_types[i])
        sub_conf = dataclasses.replace(
            conf, network_inputs=tuple(inputs), vertices=keep,
            input_types=tuple(input_types))
        sub = ComputationGraph(sub_conf).init()
        for n in keep:
            if n in self._net.params:
                _copy_matching(self._net.params, self._net.state,
                               sub.params, sub.state, n)
        self._sub = sub
        return sub

    def fit_featurized(self, features, labels, num_epochs: int = 1):
        """Train the unfrozen tail on pre-computed boundary features, then
        fold the trained params back into the FULL graph (reference
        fitFeaturized mutates the original net's unfrozen layers)."""
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        sub = self.unfrozen_graph()
        feats = features if isinstance(features, (list, tuple)) else [features]
        labs = labels if isinstance(labels, (list, tuple)) else [labels]
        sub.fit(MultiDataSet(list(feats), list(labs)), num_epochs=num_epochs)
        for n in sub.conf.vertices:
            if n in self._net.params:
                _copy_matching(sub.params, sub.state,
                               self._net.params, self._net.state, n)
        return sub

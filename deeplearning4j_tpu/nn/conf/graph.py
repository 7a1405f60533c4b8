"""Computation-graph configuration: vertices + DAG wiring.

Parity surface: reference ``nn/conf/ComputationGraphConfiguration.java``
(GraphBuilder), graph vertex configs in ``nn/conf/graph/`` and impls in
``nn/graph/vertex/impl/`` (14 classes + rnn/): MergeVertex,
ElementWiseVertex, StackVertex, UnstackVertex, SubsetVertex, ReshapeVertex,
ScaleVertex, ShiftVertex, L2NormalizeVertex, L2Vertex, PreprocessorVertex,
LastTimeStepVertex, DuplicateToTimeSeriesVertex. Not in the reference:
``TimeShiftVertex`` (position i gets the value of position i + steps),
``StackStatesVertex`` (several states on a new leading axis, for a layer
that reads more than one) and ``LoopVertex``, a sub-graph run several times
over one set of weights (a layer to the networks: it owns parameters).

TPU-native: a vertex is a pure function of its input activations; the whole
DAG is traced in topological order into ONE XLA program (the reference's
runtime topo-order loop — ComputationGraph.java:1440-1513 — happens once at
trace time, not per batch).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (Layer, layer_from_dict,
                                               layer_to_dict, register_layer)
from deeplearning4j_tpu.optimize.updaters import Updater, Sgd

_VERTEX_REGISTRY = {}


def register_vertex(cls):
    _VERTEX_REGISTRY[cls.__name__] = cls
    return cls


def vertex_to_dict(v):
    d = dataclasses.asdict(v)
    d["@class"] = type(v).__name__
    return d


def vertex_from_dict(d):
    d = dict(d)
    cls = _VERTEX_REGISTRY[d.pop("@class")]
    names = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: (tuple(v) if isinstance(v, list) else v)
                  for k, v in d.items() if k in names})


class GraphVertex:
    """Parameterless DAG node (reference nn/graph/vertex/GraphVertex.java)."""

    def output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, *inputs):
        raise NotImplementedError

    def to_dict(self):
        return vertex_to_dict(self)


@register_vertex
@dataclasses.dataclass(frozen=True)
class MergeVertex(GraphVertex):
    """Concatenate along the feature axis (reference nn/conf/graph/MergeVertex.java)."""

    def output_type(self, *its):
        total = sum(it.flat_size() for it in its)
        base = its[0]
        if base.kind == "rnn":
            return InputType.recurrent(sum(it.size for it in its), base.timeseries_length)
        if base.kind == "cnn":
            return InputType.convolutional(base.height, base.width,
                                           sum(it.channels for it in its))
        return InputType.feed_forward(total)

    def apply(self, *inputs):
        return jnp.concatenate(inputs, axis=-1)


@register_vertex
@dataclasses.dataclass(frozen=True)
class ElementWiseVertex(GraphVertex):
    """add|subtract|product|average|max (reference ElementWiseVertex.java)."""

    op: str = "add"

    def apply(self, *inputs):
        op = self.op.lower()
        if op == "add":
            out = inputs[0]
            for x in inputs[1:]:
                out = out + x
            return out
        if op == "subtract":
            if len(inputs) != 2:
                raise ValueError("subtract requires exactly 2 inputs")
            return inputs[0] - inputs[1]
        if op in ("product", "mul"):
            out = inputs[0]
            for x in inputs[1:]:
                out = out * x
            return out
        if op in ("average", "avg"):
            return sum(inputs) / float(len(inputs))
        if op == "max":
            out = inputs[0]
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
            return out
        raise ValueError(f"Unknown elementwise op '{self.op}'")


@register_vertex
@dataclasses.dataclass(frozen=True)
class StackVertex(GraphVertex):
    """Stack along the batch axis (reference StackVertex.java)."""

    def apply(self, *inputs):
        return jnp.concatenate(inputs, axis=0)


@register_vertex
@dataclasses.dataclass(frozen=True)
class UnstackVertex(GraphVertex):
    """Take slice ``from_index`` of ``stack_size`` along batch (reference
    UnstackVertex.java)."""

    from_index: int = 0
    stack_size: int = 1

    def apply(self, *inputs):
        x = inputs[0]
        step = x.shape[0] // self.stack_size
        return x[self.from_index * step:(self.from_index + 1) * step]


@register_vertex
@dataclasses.dataclass(frozen=True)
class SubsetVertex(GraphVertex):
    """Feature range [from_index, to_index] inclusive (reference SubsetVertex.java)."""

    from_index: int = 0
    to_index: int = 0

    def output_type(self, *its):
        n = self.to_index - self.from_index + 1
        it = its[0]
        if it.kind == "rnn":
            return InputType.recurrent(n, it.timeseries_length)
        return InputType.feed_forward(n)

    def apply(self, *inputs):
        return inputs[0][..., self.from_index:self.to_index + 1]


@register_vertex
@dataclasses.dataclass(frozen=True)
class ReshapeVertex(GraphVertex):
    """Reshape to (batch, *shape) (reference ReshapeVertex.java)."""

    shape: Tuple[int, ...] = ()

    def output_type(self, *its):
        if len(self.shape) == 1:
            return InputType.feed_forward(self.shape[0])
        if len(self.shape) == 3:
            return InputType.convolutional(*self.shape)
        if len(self.shape) == 2:
            return InputType.recurrent(self.shape[1], self.shape[0])
        return its[0]

    def apply(self, *inputs):
        x = inputs[0]
        return x.reshape((x.shape[0],) + tuple(self.shape))


@register_vertex
@dataclasses.dataclass(frozen=True)
class ScaleVertex(GraphVertex):
    """x * scale (reference ScaleVertex.java)."""

    scale: float = 1.0

    def apply(self, *inputs):
        return inputs[0] * self.scale


@register_vertex
@dataclasses.dataclass(frozen=True)
class ShiftVertex(GraphVertex):
    """x + shift (reference ShiftVertex.java)."""

    shift: float = 0.0

    def apply(self, *inputs):
        return inputs[0] + self.shift


@register_vertex
@dataclasses.dataclass(frozen=True)
class TimeShiftVertex(GraphVertex):
    """Position i of (batch, time, ...) gets the value of position
    i + ``steps`` along the time axis; the last ``steps`` positions, which
    have no such value, get zeros (a reader masks them). A multi-token
    prediction module reads the NEXT token's embedding this way from the
    model's one embedding vertex, whose table then gets its gradient from
    both uses."""

    steps: int = 1

    def apply(self, *inputs):
        x = inputs[0]
        if not 0 < self.steps < x.shape[1]:
            raise ValueError(f"a shift of {self.steps} over {x.shape[1]} "
                             "steps")
        tail = jnp.zeros((x.shape[0], self.steps) + x.shape[2:], x.dtype)
        return jnp.concatenate([x[:, self.steps:], tail], axis=1)


@register_vertex
@dataclasses.dataclass(frozen=True)
class StackStatesVertex(GraphVertex):
    """Its inputs, alike in type, on a new leading axis in front of the
    batch (``InputType.passes``, as a stacked ``LoopVertex`` hands out its
    passes): what a layer reads that takes more than one state, a graph
    layer having one input."""

    def output_type(self, *its):
        if any(it != its[0] for it in its[1:]) or its[0].passes:
            raise ValueError(f"states of one type, each one state: {its}")
        return dataclasses.replace(its[0], passes=len(its))

    def apply(self, *inputs):
        return jnp.stack(inputs, axis=0)


@register_vertex
@dataclasses.dataclass(frozen=True)
class L2NormalizeVertex(GraphVertex):
    """x / ||x||_2 over feature axes (reference L2NormalizeVertex.java)."""

    eps: float = 1e-8

    def apply(self, *inputs):
        x = inputs[0]
        axes = tuple(range(1, x.ndim))
        n = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True) + self.eps)
        return x / n


@register_vertex
@dataclasses.dataclass(frozen=True)
class L2Vertex(GraphVertex):
    """Pairwise L2 distance of two inputs -> (batch, 1) (reference L2Vertex.java)."""

    eps: float = 1e-8

    def output_type(self, *its):
        return InputType.feed_forward(1)

    def apply(self, *inputs):
        a, b = inputs
        d = a.reshape(a.shape[0], -1) - b.reshape(b.shape[0], -1)
        return jnp.sqrt(jnp.sum(d * d, axis=1, keepdims=True) + self.eps)


@register_vertex
@dataclasses.dataclass(frozen=True)
class PreprocessorVertex(GraphVertex):
    """Wrap an InputPreProcessor as a vertex (reference PreprocessorVertex.java)."""

    preprocessor: Optional[object] = None

    def output_type(self, *its):
        return self.preprocessor.output_type(its[0])

    def apply(self, *inputs):
        out, _ = self.preprocessor.apply(inputs[0], None)
        return out

    def to_dict(self):
        from deeplearning4j_tpu.nn.conf.preprocessors import preprocessor_to_dict
        return {"@class": "PreprocessorVertex",
                "preprocessor": preprocessor_to_dict(self.preprocessor)}


@register_vertex
@dataclasses.dataclass(frozen=True)
class LastTimeStepVertex(GraphVertex):
    """(b, t, s) -> (b, s) last unmasked step (reference
    nn/graph/vertex/impl/rnn/LastTimeStepVertex.java). Mask handling is done
    by the graph runtime (passes the relevant input mask)."""

    mask_input: Optional[str] = None

    def output_type(self, *its):
        return InputType.feed_forward(its[0].size)

    def apply(self, *inputs, mask=None):
        x = inputs[0]
        if mask is None:
            return x[:, -1, :]
        lengths = jnp.sum(mask, axis=1).astype(jnp.int32)
        idx = jnp.maximum(lengths - 1, 0)
        return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :]


@register_vertex
@dataclasses.dataclass(frozen=True)
class DuplicateToTimeSeriesVertex(GraphVertex):
    """(b, s) -> (b, t, s) broadcast over the time length of a reference input
    (reference rnn/DuplicateToTimeSeriesVertex.java)."""

    reference_input: Optional[str] = None

    def output_type(self, *its):
        return InputType.recurrent(its[0].flat_size())

    def apply(self, *inputs, time_steps=None):
        x = inputs[0]
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], time_steps, x.shape[1]))


@dataclasses.dataclass(frozen=True)
class ComputationGraphConfiguration:
    """DAG config (reference nn/conf/ComputationGraphConfiguration.java).

    ``vertices`` maps name -> (Layer | GraphVertex, input names). Network
    inputs are named in ``network_inputs`` with types in ``input_types``.
    """

    network_inputs: Tuple[str, ...]
    vertices: Dict[str, Tuple[object, Tuple[str, ...]]]
    network_outputs: Tuple[str, ...]
    input_types: Tuple[InputType, ...] = ()
    seed: int = 12345
    dtype: str = "float32"
    updater: Updater = Sgd(learning_rate=0.1)
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20

    def __post_init__(self):
        if self.backprop_type not in ("standard", "tbptt"):
            raise ValueError(
                f"Unknown backprop_type '{self.backprop_type}' "
                "(expected 'standard' or 'tbptt')")
        if (self.backprop_type == "tbptt"
                and self.tbptt_fwd_length != self.tbptt_back_length):
            # _fit_tbptt steps and truncates by fwd_length only (same
            # constraint as MultiLayerConfiguration.__post_init__)
            raise ValueError(
                "tbptt_back_length != tbptt_fwd_length is not supported: got "
                f"fwd={self.tbptt_fwd_length}, back={self.tbptt_back_length}. "
                "Use equal lengths")

    def producer_of(self, ref: str) -> str:
        """The vertex (or network input) that makes what ``ref`` names: the
        name itself, or for a value a layer hands on beside its output
        (``"<vertex>.<value>"``: ``Layer.shared_values``) that layer's
        vertex. A name that is neither is returned as it is."""
        if ref in self.vertices or ref in self.network_inputs:
            return ref
        vertex = ref.rpartition(".")[0]
        return vertex if vertex in self.vertices else ref

    # ---- topology (reference ComputationGraph.topologicalSortOrder :1190) ----
    def topological_order(self) -> List[str]:
        indeg = {}
        children = {n: [] for n in list(self.vertices) + list(self.network_inputs)}
        for name, (_, inputs) in self.vertices.items():
            indeg[name] = len(inputs)
            for i in inputs:
                i = self.producer_of(i)
                if i not in children:
                    raise ValueError(f"Vertex '{name}' references unknown input '{i}'")
                children[i].append(name)
        order = []
        frontier = list(self.network_inputs)
        while frontier:
            cur = frontier.pop()
            if cur in self.vertices:
                order.append(cur)
            for ch in children[cur]:
                indeg[ch] -= 1
                if indeg[ch] == 0:
                    frontier.append(ch)
        if len(order) != len(self.vertices):
            cyc = set(self.vertices) - set(order)
            raise ValueError(f"Graph has a cycle or unreachable vertices: {sorted(cyc)}")
        return order

    # ---- shape inference over the DAG ----
    def _infer(self):
        """Walk the DAG once: per-vertex input types (post-preprocessor) and
        automatically inserted preprocessors for layer vertices (same
        infer_preprocessor logic the sequential config uses — the reference
        ComputationGraphConfiguration also auto-adds preprocessors)."""
        from deeplearning4j_tpu.nn.conf.preprocessors import infer_preprocessor
        if len(self.input_types) != len(self.network_inputs):
            raise ValueError("input_types must be set for all network inputs")
        known: Dict[str, InputType] = dict(zip(self.network_inputs, self.input_types))
        types = {}
        pres = {}
        for name in self.topological_order():
            obj, inputs = self.vertices[name]
            missing = [i for i in inputs if i not in known]
            if missing:
                raise ValueError(
                    f"Vertex '{name}' reads {missing}: no value of that name "
                    f"is handed on by '{self.producer_of(missing[0])}'")
            its = tuple(known[i] for i in inputs)
            if isinstance(obj, Layer):
                pre = infer_preprocessor(its[0], obj)
                if pre is not None:
                    pres[name] = pre
                    its = (pre.output_type(its[0]),) + its[1:]
                types[name] = its
                known[name] = obj.output_type(its[0])
                for key, kind in obj.shared_values(its[0]).items():
                    known[f"{name}.{key}"] = kind
            else:
                types[name] = its
                known[name] = obj.output_type(*its)
        return types, pres, known

    def vertex_input_types(self) -> Dict[str, Tuple[InputType, ...]]:
        return self._infer()[0]

    def resolved_vertex_preprocessors(self):
        return self._infer()[1]

    def vertex_output_types(self) -> Dict[str, InputType]:
        """Output InputType of every vertex (and network input) — used by
        transfer learning to type the frozen boundary."""
        return self._infer()[2]

    def wired_vertices(self) -> Dict[str, Tuple[object, Tuple[str, ...]]]:
        types = self.vertex_input_types()
        out = {}
        for name, (obj, inputs) in self.vertices.items():
            if isinstance(obj, Layer):
                obj = obj.with_n_in(types[name][0].flat_size())
            out[name] = (obj, inputs)
        return out

    # ---- static analysis (analysis/validation.py) ----
    def validate(self, *, eval_shape_check: bool = False, batch: int = 2,
                 labels_shapes=None, raise_on_error: bool = True):
        """Ahead-of-compile DAG validation: cycle / dangling-vertex /
        unknown-reference detection, merge/element-wise rank+shape
        agreement, per-layer shape inference with vertex-named messages.
        ``eval_shape_check=True`` cross-checks against ``jax.eval_shape``
        of the traced DAG. Returns the issue list; raises
        :class:`analysis.ConfigValidationError` on errors unless
        ``raise_on_error=False``."""
        from deeplearning4j_tpu.analysis.validation import (
            ConfigValidationError, validate_graph)
        issues = validate_graph(
            self, eval_shape_check=eval_shape_check, batch=batch,
            labels_shapes=labels_shapes)
        errors = [i for i in issues if i.severity == "error"]
        if errors and raise_on_error:
            raise ConfigValidationError(errors)
        return issues

    def memory_report(self, minibatch: int = 32):
        """Analytic per-vertex parameter + activation memory (no device
        allocation), plus the measured training-activation-bytes line
        (jaxpr-derived residual set of the real train step). See
        nn/memory.py::conf_memory_report."""
        from deeplearning4j_tpu.nn.memory import conf_memory_report
        return conf_memory_report(self, minibatch=minibatch)

    def fused(self) -> "ComputationGraphConfiguration":
        """Conv→BN→Act(→residual-add) fusion rewrite of this DAG
        (perf/fusion.py). Matched chains — including the residual
        bottleneck pattern — become FusedConvBNActivation vertices."""
        from deeplearning4j_tpu.perf.fusion import fuse
        return fuse(self)

    # ---- serde ----
    def to_dict(self) -> dict:
        return {
            "network_inputs": list(self.network_inputs),
            "network_outputs": list(self.network_outputs),
            "input_types": [t.to_dict() for t in self.input_types],
            "seed": self.seed,
            "dtype": self.dtype,
            "updater": self.updater.to_dict(),
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "vertices": {
                name: {"node": (layer_to_dict(obj) if isinstance(obj, Layer)
                                else obj.to_dict()),
                       "is_layer": isinstance(obj, Layer),
                       "inputs": list(inputs)}
                for name, (obj, inputs) in self.vertices.items()
            },
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration.from_dict(json.loads(s))

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        from deeplearning4j_tpu.nn.conf.preprocessors import preprocessor_from_dict
        vertices = {}
        for name, vd in d["vertices"].items():
            node = vd["node"]
            if vd["is_layer"]:
                obj = layer_from_dict(node)
            elif node["@class"] == "PreprocessorVertex":
                obj = PreprocessorVertex(preprocessor_from_dict(node["preprocessor"]))
            else:
                obj = vertex_from_dict(node)
            vertices[name] = (obj, tuple(vd["inputs"]))
        return ComputationGraphConfiguration(
            network_inputs=tuple(d["network_inputs"]),
            vertices=vertices,
            network_outputs=tuple(d["network_outputs"]),
            input_types=tuple(InputType.from_dict(t) for t in d["input_types"]),
            seed=d.get("seed", 12345),
            dtype=d.get("dtype", "float32"),
            updater=Updater.from_dict(d["updater"]),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
        )


@register_layer
@dataclasses.dataclass(frozen=True)
class LoopVertex(Layer):
    """A sub-graph run ``steps`` times over ONE set of weights: pass r
    reads what pass r - 1 wrote (``h_r = body(h_{r-1})``, ``h_0`` the
    vertex's input) and the vertex hands on every pass's output, stacked
    on a new leading axis (``steps``, batch, ...; ``InputType.passes``), or
    with ``stacked=False`` the last pass's alone. The depth-recurrent
    ("looped", "universal") transformers are this around their block of
    layers; ``ExitWeightedTokenOutputLayer`` scores the stack.

    ``body`` is a whole ``ComputationGraphConfiguration`` with one input
    and one output (build it with ``GraphBuilder`` and
    ``set_input_types``; its seed, dtype and updater are not read: the
    network's are). The body's parameters exist once, nested under the
    vertex (``params[vertex][body vertex][leaf]``), and so do their
    optimizer state, checkpoint entry and count; the gradient autodiff
    hands the optimizer is the sum over the passes. A body layer is a
    layer like any other (``apply_layer``: its ``<LayerClass>:<name>``
    scope, its ``remat`` knob) and may be a ``LoopVertex`` itself: with
    ``steps=1, stacked=False, remat="full"`` that is a sub-graph
    rematerialised as one unit. The engine updates by top-level vertex:
    the body layers that name an updater have to name the same one, which
    is then the vertex's, and l1 / l2, constraints and gradient
    normalisation inside a body are refused.

    More than one pass runs as one ``lax.scan`` over the body, so a step
    program holds the body once however many passes there are. Dropout and
    weight noise draw anew every pass. Counted at trace time
    (``bump_active``) for more than one pass: ``loop.scanned``; scope
    ``loop.body``."""

    body: Optional[ComputationGraphConfiguration] = None
    steps: int = 1
    stacked: bool = True

    def __post_init__(self):
        if isinstance(self.body, dict):      # from JSON
            object.__setattr__(
                self, "body", ComputationGraphConfiguration.from_dict(self.body))

    def input_kind(self):
        return "any"

    def _wiring(self):
        """(order, wired vertices, preprocessors, input types) of the
        body, as ``ComputationGraph`` keeps them for a whole graph."""
        types, pres, _ = self.body._infer()
        return (self.body.topological_order(), self.body.wired_vertices(),
                pres, types)

    def _body_layers(self):
        return [(n, obj) for n, (obj, _) in self.body.vertices.items()
                if isinstance(obj, Layer)]

    @property
    def updater(self):
        """The one updater the body's layers name (a builder's default
        lands on every layer), or None: the network's."""
        named = {repr(u): u for _, obj in self._body_layers()
                 if (u := getattr(obj, "updater", None)) is not None}
        if len(named) > 1:
            raise ValueError("the layers of a loop's body name different "
                             f"updaters: {sorted(named)}")
        return next(iter(named.values()), None)

    def output_type(self, it: InputType) -> InputType:
        body = self.body
        if body is None or self.steps < 1:
            raise ValueError("a LoopVertex needs a body and steps >= 1")
        if len(body.network_inputs) != 1 or len(body.network_outputs) != 1 \
                or len(body.input_types) != 1:
            raise ValueError("a loop's body has one typed input and one "
                             "output")
        inner = body.input_types[0]
        if (inner.kind, inner.flat_size()) != (it.kind, it.flat_size()):
            raise ValueError(f"the body is typed for {inner}, the vertex is "
                             f"given {it}")
        for name, obj in self._body_layers():
            if obj.is_output_layer():
                raise ValueError(f"body vertex '{name}' is an output layer")
            unbuilt = [k for k in ("l1", "l2", "l1_bias", "l2_bias",
                                   "constraints", "gradient_normalization")
                       if getattr(obj, k, None)]
            if unbuilt:
                raise ValueError(f"body vertex '{name}' sets {unbuilt}: not "
                                 "built inside a loop's body")
        self.updater                      # raises where they disagree
        out = body.vertex_output_types()[body.network_outputs[0]]
        if self.steps > 1 and (out.kind, out.flat_size()) != (
                inner.kind, inner.flat_size()):
            raise ValueError(f"a pass writes {out} where the next reads "
                             f"{inner}")
        return (dataclasses.replace(out, passes=self.steps) if self.stacked
                else out)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        order, wired, _, types = self._wiring()
        params, state = {}, {}
        for name in order:
            obj = wired[name][0]
            if isinstance(obj, Layer):
                rng, k = jax.random.split(rng)
                p, st = obj.init(k, types[name][0], dtype)
                # a body vertex without leaves has no entry
                if p:
                    params[name] = p
                if st:
                    state[name] = st
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.graph import run_vertices
        from deeplearning4j_tpu.perf.compile_watch import bump_active

        order, wired, vpre, types = self._wiring()
        source, sink = self.body.network_inputs[0], self.body.network_outputs[0]
        # the walk reads an entry a vertex; ``init`` keeps none for a body
        # vertex without leaves
        params = {name: params.get(name, {}) for name in order}

        def one_pass(h, st, r):
            acts, mask_of = {source: h}, {source: mask}
            key = None if rng is None else jax.random.fold_in(rng, r)
            _, new, _ = run_vertices(
                order, wired, vpre, types, params,
                {name: st.get(name, {}) for name in order}, acts, mask_of,
                train, key)
            return acts[sink], {name: new[name] for name in st}

        if self.steps == 1:
            h, st = one_pass(x, state, 0)
            return (h[None] if self.stacked else h), st
        with jax.named_scope("loop.body"):
            bump_active("loop.scanned")

            def step(carry, r):
                h, st = one_pass(*carry, r)
                return (h, st), (h if self.stacked else None)

            (h, st), outs = jax.lax.scan(step, (x, state),
                                         jnp.arange(self.steps))
            return (outs if self.stacked else h), st


class GraphBuilder:
    """Fluent DAG builder (reference ComputationGraphConfiguration.GraphBuilder,
    used by every zoo model — e.g. ResNet50.java:173 graphBuilder)."""

    def __init__(self, parent=None):
        self._parent = parent  # NeuralNetConfiguration Builder for defaults
        self._inputs: List[str] = []
        self._outputs: List[str] = []
        self._vertices: Dict[str, Tuple[object, Tuple[str, ...]]] = {}
        self._input_types: List[InputType] = []
        self._backprop_type = "standard"
        self._tbptt_length = 20

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        from deeplearning4j_tpu.nn.conf.network import _apply_layer_defaults
        if self._parent is not None:
            layer = _apply_layer_defaults(layer, self._parent._defaults)
        self._vertices[name] = (layer, tuple(inputs))
        return self

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str) -> "GraphBuilder":
        self._vertices[name] = (vertex, tuple(inputs))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def set_input_types(self, *types: InputType) -> "GraphBuilder":
        self._input_types = list(types)
        return self

    def backprop_type(self, kind: str, fwd_length: int = 20,
                      back_length: Optional[int] = None) -> "GraphBuilder":
        """reference GraphBuilder.backpropType(...).tBPTTForwardLength(...);
        back_length must equal fwd_length (windows step by fwd_length)."""
        if kind not in ("standard", "tbptt"):
            raise ValueError(f"Unknown backprop_type '{kind}' "
                             "(expected 'standard' or 'tbptt')")
        if back_length is not None and back_length != fwd_length:
            raise ValueError(
                "tbptt back_length != fwd_length is not supported: got "
                f"fwd={fwd_length}, back={back_length}")
        self._backprop_type = kind
        self._tbptt_length = fwd_length
        return self

    def build(self) -> ComputationGraphConfiguration:
        seed = self._parent._seed if self._parent else 12345
        dtype = self._parent._dtype if self._parent else "float32"
        updater = self._parent._updater if self._parent else Sgd(learning_rate=0.1)
        conf = ComputationGraphConfiguration(
            network_inputs=tuple(self._inputs),
            vertices=dict(self._vertices),
            network_outputs=tuple(self._outputs),
            input_types=tuple(self._input_types),
            seed=seed, dtype=dtype, updater=updater,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_length,
            tbptt_back_length=self._tbptt_length,
        )
        conf.topological_order()  # validate DAG early
        return conf

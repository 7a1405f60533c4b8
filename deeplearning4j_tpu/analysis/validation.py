"""Config validation: shape/dtype inference before any XLA trace.

In the reference, every ``INDArray`` op crossed into ND4J where a shape
error surfaced at runtime deep in C++. On the JAX substrate a config
mistake is worse: it costs a multi-second trace/compile before it errors,
and the error points at an einsum inside a traced function, not at the
layer that caused it. This pass walks the SAME ``InputType`` inference the
configs already use for wiring (``output_type`` per layer/vertex), but
captures every failure as a :class:`ValidationIssue` that names the
offending layer and both shapes — and adds the checks shape inference alone
does not make (unknown activations/losses, n_in disagreement, arity and
rank agreement on merge vertices, time-axis consistency, dangling DAG
nodes).

The inference is cross-checkable against real tracing:
``eval_shape_check=True`` runs the network's actual forward under
``jax.eval_shape`` (zero FLOPs, no compile) and compares every layer's
traced activation shape against the pure-Python prediction, so the two can
never silently drift apart.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "ValidationIssue", "ConfigValidationError",
    "validate_multilayer", "validate_graph",
]


@dataclasses.dataclass(frozen=True)
class ValidationIssue:
    """One finding. ``severity`` is 'error' (would fail or mis-train at
    runtime) or 'warning' (suspicious but runnable)."""

    rule: str
    layer: str          # display name of the offending layer/vertex
    message: str
    severity: str = "error"

    def __str__(self):
        return f"[{self.severity}] {self.rule} @ {self.layer}: {self.message}"


class ConfigValidationError(ValueError):
    """Raised by ``conf.validate()`` when error-severity issues exist."""

    def __init__(self, issues: Sequence[ValidationIssue]):
        self.issues = list(issues)
        super().__init__(
            "Invalid network configuration "
            f"({len(self.issues)} error{'s' if len(self.issues) != 1 else ''}):\n"
            + "\n".join(f"  - {i}" for i in self.issues))


def describe_type(it) -> str:
    """Human-readable InputType, used in every both-shapes message."""
    if it is None:
        return "<unknown>"
    if it.passes:
        one = describe_type(dataclasses.replace(it, passes=None))
        return f"{it.passes} passes of {one}"
    if it.kind == "cnn":
        return f"cnn(h={it.height}, w={it.width}, c={it.channels})"
    if it.kind == "cnn_flat":
        return (f"cnn_flat(h={it.height}, w={it.width}, c={it.channels} -> "
                f"{it.flat_size()})")
    if it.kind in ("rnn", "cnn1d"):
        t = "?" if it.timeseries_length is None else it.timeseries_length
        return f"{it.kind}(t={t}, size={it.size})"
    return f"ff(size={it.size})"


def _layer_name(i: Optional[int], layer) -> str:
    cls = type(layer).__name__
    name = getattr(layer, "name", None)
    if name:
        return f"'{name}' ({cls})"
    if i is None:
        return cls
    return f"layer[{i}] ({cls})"


# layers where n_out == 0 is legal (width inferred from the input)
_N_OUT_OPTIONAL = ("TransformerEncoderBlock", "KimiDeltaAttention",
                   "GatedDeltaNet", "MultiHeadLatentAttention",
                   "GatedAttention", "RotaryAttention", "GatedFeedForward",
                   "RoutedExperts", "MultiTokenCombine", "GatedShortConv",
                   "Mamba2Mixer", "Mamba1Mixer", "GatedMemoryUnit",
                   "DifferentialAttention")


def _check_layer(layer, cur, name: str) -> List[ValidationIssue]:
    """Static per-layer checks that do not need output_type to succeed.
    ``cur`` is the InputType the layer will see (post-preprocessor)."""
    from deeplearning4j_tpu.nn.activations import ACTIVATIONS
    from deeplearning4j_tpu.nn.lossfunctions import LOSSES

    issues: List[ValidationIssue] = []

    # unknown activation (catches typos before a trace ever starts)
    for attr in ("activation", "ff_activation"):
        act = getattr(layer, attr, None)
        if act is not None and not callable(act) \
                and str(act).lower() not in ACTIVATIONS:
            issues.append(ValidationIssue(
                "unknown-activation", name,
                f"activation '{act}' is not a known activation "
                f"(known: {sorted(ACTIVATIONS)[:8]}...)"))

    # unknown loss on loss-bearing layers
    if layer.is_output_layer():
        loss = getattr(layer, "loss", None)
        if loss is not None and not callable(loss) \
                and str(loss).lower() not in LOSSES:
            issues.append(ValidationIssue(
                "unknown-loss", name,
                f"loss '{loss}' is not a known loss function "
                f"(known: {sorted(LOSSES)})"))

    # dropout is a retain probability (DL4J 0.9 semantics): [0, 1]
    dropout = getattr(layer, "dropout", None)
    if dropout is not None and not hasattr(dropout, "apply"):
        try:
            d = float(dropout)
        except (TypeError, ValueError):
            d = None
        if d is not None and not (0.0 <= d <= 1.0):
            issues.append(ValidationIssue(
                "dropout-range", name,
                f"dropout (retain probability) must be in [0, 1], got {d}"))

    # n_out required where the layer cannot infer its own width
    if hasattr(layer, "n_out") \
            and type(layer).__name__ not in _N_OUT_OPTIONAL:
        n_out = getattr(layer, "n_out")
        if not n_out or n_out < 0:
            issues.append(ValidationIssue(
                "n-out-missing", name,
                f"n_out must be a positive integer, got {n_out!r}"))

    # explicit n_in that disagrees with the inferred input size (stale
    # hand-wiring, e.g. after editing an upstream layer's width)
    target = layer
    for _ in range(3):  # unwrap Bidirectional/LastTimeStep-style wrappers
        n_in = getattr(target, "n_in", None)
        if n_in and cur is not None:
            kind = target.input_kind() if hasattr(target, "input_kind") else "any"
            if kind == "cnn" and cur.kind == "cnn":
                expected = cur.channels
                what = f"input channels ({describe_type(cur)})"
            else:
                expected = cur.flat_size()
                what = f"input size ({describe_type(cur)})"
            if int(n_in) != int(expected):
                issues.append(ValidationIssue(
                    "n-in-mismatch", name,
                    f"explicit n_in={n_in} disagrees with the {what} "
                    f"= {expected}"))
        inner = getattr(target, "layer", None)
        if inner is None:
            break
        target = inner

    # unknown remat policy (the knob lowers to jax.checkpoint at trace
    # time; a typo would otherwise surface mid-trace)
    remat = getattr(layer, "remat", None)
    if remat is not None:
        from deeplearning4j_tpu.perf.fusion import REMAT_POLICIES
        if str(remat) not in REMAT_POLICIES:
            issues.append(ValidationIssue(
                "unknown-remat", name,
                f"remat='{remat}' is not a known rematerialization policy "
                f"(known: {sorted(REMAT_POLICIES)})"))

    # sequence layers need a time axis to operate on
    if hasattr(layer, "input_kind") and layer.input_kind() == "rnn" \
            and cur is not None and cur.kind not in ("rnn", "cnn1d"):
        issues.append(ValidationIssue(
            "time-axis", name,
            f"sequence layer fed non-sequence input {describe_type(cur)}; "
            "use InputType.recurrent(...) or insert a "
            "FeedForwardToRnnPreProcessor"))

    # known-incoherent loss/activation pairings (mis-trains silently)
    if layer.is_output_layer():
        loss = str(getattr(layer, "loss", "") or "").lower()
        act = str(getattr(layer, "activation", "") or "").lower()
        if loss == "mcxent" and act in ("identity", "relu", "sigmoid"):
            issues.append(ValidationIssue(
                "loss-activation", name,
                f"loss 'mcxent' expects a softmax output, got activation "
                f"'{act}' (multi-class cross-entropy over non-normalized "
                "outputs trains incorrectly)", severity="warning"))
        if loss == "xent" and act == "softmax":
            issues.append(ValidationIssue(
                "loss-activation", name,
                "loss 'xent' (binary cross-entropy) with softmax activation "
                "— use 'mcxent' for multi-class softmax outputs",
                severity="warning"))

    return issues


def _labels_shape_issue(out_layer, final_type, labels_shape,
                        name: str) -> Optional[ValidationIssue]:
    """Loss-vs-label shape compatibility for a concrete labels shape."""
    n_out = getattr(out_layer, "n_out", None) or final_type.flat_size()
    ls = tuple(int(d) for d in labels_shape)
    if getattr(out_layer, "sparse_labels", False):
        # integer class ids: the output's shape without its class axis
        rank = 2 if final_type.kind in ("rnn", "cnn1d") else 1
        ok = len(ls) == rank
        expected = "(batch, time) ids" if rank == 2 else "(batch,) ids"
    elif final_type.kind in ("rnn", "cnn1d"):
        ok = len(ls) == 3 and ls[-1] == n_out
        expected = f"(batch, time, {n_out})"
    else:
        ok = len(ls) == 2 and ls[-1] == n_out
        expected = f"(batch, {n_out})"
    if ok:
        return None
    return ValidationIssue(
        "labels-shape", name,
        f"labels shape {ls} is incompatible with the output layer "
        f"(n_out={n_out}, output {describe_type(final_type)}): "
        f"expected {expected}")


# --------------------------------------------------------------- multilayer
def validate_multilayer(conf, *, eval_shape_check: bool = False,
                        batch: int = 2,
                        labels_shape=None) -> List[ValidationIssue]:
    """Validate a MultiLayerConfiguration. Returns ALL issues found (empty
    list = clean); raising on errors is the caller's choice
    (``conf.validate()`` raises :class:`ConfigValidationError`)."""
    from deeplearning4j_tpu.nn.conf.preprocessors import infer_preprocessor

    issues: List[ValidationIssue] = []
    if not conf.layers:
        return [ValidationIssue("empty-network", "<network>",
                                "configuration has no layers")]
    if conf.input_type is None:
        return [ValidationIssue(
            "missing-input-type", "<network>",
            "input_type is required for shape inference "
            "(.set_input_type(InputType...) on the builder)")]

    cur = conf.input_type
    types = []          # InputType seen by each layer, post-preprocessor
    inference_ok = True
    for i, layer in enumerate(conf.layers):
        name = _layer_name(i, layer)
        pre = (conf.input_preprocessors or {}).get(i)
        try:
            if pre is None:
                pre = infer_preprocessor(cur, layer)
        except ValueError as e:
            issues.append(ValidationIssue(
                "preprocessor", name,
                f"{e} (input {describe_type(cur)})"))
            inference_ok = False
            break
        if pre is not None:
            cur = pre.output_type(cur)
        types.append(cur)
        issues.extend(_check_layer(layer, cur, name))
        if getattr(layer, "tied_to", ""):
            issues.append(ValidationIssue(
                "tied-head", name,
                f"tied_to '{layer.tied_to}' names a vertex: a "
                "ComputationGraph's field, a stack has none"))
        if layer.is_output_layer() and i != len(conf.layers) - 1:
            issues.append(ValidationIssue(
                "output-layer-position", name,
                f"output/loss layer at position {i} of "
                f"{len(conf.layers)}; only the last layer may carry a loss"))
        try:
            cur = layer.output_type(cur)
        except ValueError as e:
            issues.append(ValidationIssue(
                "geometry", name,
                f"{e} (input {describe_type(types[-1])})"))
            inference_ok = False
            break

    last = conf.layers[-1]
    if not last.is_output_layer():
        issues.append(ValidationIssue(
            "no-output-layer", _layer_name(len(conf.layers) - 1, last),
            "last layer is not an output/loss layer: fit() will refuse this "
            "network (inference-only use is fine)", severity="warning"))

    if conf.backprop_type == "tbptt" \
            and not any(l.is_recurrent() for l in conf.layers):
        issues.append(ValidationIssue(
            "tbptt-without-rnn", "<network>",
            "backprop_type='tbptt' but no layer is recurrent; truncated "
            "BPTT windows will never apply", severity="warning"))

    if inference_ok and labels_shape is not None and last.is_output_layer():
        li = _labels_shape_issue(last, cur, labels_shape,
                                 _layer_name(len(conf.layers) - 1, last))
        if li is not None:
            issues.append(li)

    if inference_ok and eval_shape_check \
            and not any(i.severity == "error" for i in issues):
        issues.extend(_eval_shape_check_multilayer(conf, batch))
    return issues


# -------------------------------------------------------------------- graph
def _vertex_arity_issue(obj, in_names, name) -> Optional[ValidationIssue]:
    from deeplearning4j_tpu.nn.conf.graph import (
        ElementWiseVertex, L2Vertex,
    )
    if isinstance(obj, L2Vertex) and len(in_names) != 2:
        return ValidationIssue(
            "vertex-arity", name,
            f"L2Vertex requires exactly 2 inputs, got {len(in_names)}")
    if isinstance(obj, ElementWiseVertex):
        if obj.op.lower() == "subtract" and len(in_names) != 2:
            return ValidationIssue(
                "vertex-arity", name,
                f"ElementWiseVertex(op='subtract') requires exactly 2 "
                f"inputs, got {len(in_names)}")
        if len(in_names) < 2:
            return ValidationIssue(
                "vertex-arity", name,
                f"ElementWiseVertex needs >= 2 inputs, got {len(in_names)}")
    return None


def _merge_agreement_issues(obj, its, in_names, name) -> List[ValidationIssue]:
    """Rank + shape agreement for multi-input combiner vertices, with both
    shapes in the message."""
    from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex, MergeVertex
    issues: List[ValidationIssue] = []
    if len(its) < 2:
        return issues
    shapes = ", ".join(f"{n}={describe_type(t)}"
                       for n, t in zip(in_names, its))
    if isinstance(obj, (MergeVertex, ElementWiseVertex)):
        kinds = {t.kind for t in its}
        if len(kinds) > 1:
            issues.append(ValidationIssue(
                "merge-rank-mismatch", name,
                f"inputs have different ranks/families {sorted(kinds)}: "
                f"{shapes}"))
            return issues
        base = its[0]
        if isinstance(obj, ElementWiseVertex):
            # element-wise needs every dim equal (feature axis included)
            if any(t != base for t in its[1:]):
                issues.append(ValidationIssue(
                    "elementwise-mismatch", name,
                    f"element-wise '{obj.op}' needs identical input shapes: "
                    f"{shapes}"))
        else:  # MergeVertex concatenates features: non-feature dims agree
            if base.kind == "cnn" and any(
                    (t.height, t.width) != (base.height, base.width)
                    for t in its[1:]):
                issues.append(ValidationIssue(
                    "merge-mismatch", name,
                    f"merge needs equal spatial dims: {shapes}"))
            if base.kind in ("rnn", "cnn1d"):
                ts = {t.timeseries_length for t in its
                      if t.timeseries_length is not None}
                if len(ts) > 1:
                    issues.append(ValidationIssue(
                        "merge-mismatch", name,
                        f"merge needs equal sequence lengths: {shapes}"))
    return issues


def validate_graph(conf, *, eval_shape_check: bool = False,
                   batch: int = 2,
                   labels_shapes=None) -> List[ValidationIssue]:
    """Validate a ComputationGraphConfiguration DAG."""
    from deeplearning4j_tpu.nn.conf.graph import (
        DuplicateToTimeSeriesVertex, LastTimeStepVertex,
    )
    from deeplearning4j_tpu.nn.conf.layers import Layer
    from deeplearning4j_tpu.nn.conf.preprocessors import infer_preprocessor

    issues: List[ValidationIssue] = []
    known_names = set(conf.network_inputs) | set(conf.vertices)

    if len(conf.input_types) != len(conf.network_inputs):
        issues.append(ValidationIssue(
            "missing-input-type", "<network>",
            f"{len(conf.network_inputs)} network inputs but "
            f"{len(conf.input_types)} input_types; every input needs a "
            "declared InputType"))
        return issues

    for ni in conf.network_inputs:
        if ni in conf.vertices:
            issues.append(ValidationIssue(
                "name-collision", f"'{ni}'",
                "name is both a network input and a vertex"))

    # unknown input references (named per vertex)
    structurally_ok = True
    for name, (obj, in_names) in conf.vertices.items():
        if not in_names:
            issues.append(ValidationIssue(
                "vertex-no-inputs", f"'{name}'",
                f"vertex '{name}' has no inputs"))
            structurally_ok = False
        for i in in_names:
            # ``<vertex>.<value>``: a value a layer hands on beside its
            # output (whether it hands on THAT value: shape inference below)
            if conf.producer_of(i) not in known_names:
                issues.append(ValidationIssue(
                    "unknown-input", f"'{name}'",
                    f"vertex '{name}' references unknown input '{i}' "
                    f"(known: network inputs {list(conf.network_inputs)}, "
                    f"vertices {sorted(conf.vertices)})"))
                structurally_ok = False
        ai = _vertex_arity_issue(obj, in_names, f"'{name}'")
        if ai is not None:
            issues.append(ai)
        wanted = getattr(obj, "extra_inputs", ())
        if isinstance(obj, Layer) and len(in_names) < 1 + len(wanted):
            issues.append(ValidationIssue(
                "layer-inputs", f"'{name}'",
                f"{type(obj).__name__} reads {1 + len(wanted)} inputs (its "
                f"own and {list(wanted)}), the vertex is wired to "
                f"{len(in_names)}"))
            structurally_ok = False

    for out in conf.network_outputs:
        if out not in conf.vertices:
            issues.append(ValidationIssue(
                "unknown-output", f"'{out}'",
                f"network output '{out}' is not a vertex"))
            structurally_ok = False
        else:
            obj = conf.vertices[out][0]
            if not (isinstance(obj, Layer) and obj.is_output_layer()):
                issues.append(ValidationIssue(
                    "output-not-loss", f"'{out}'",
                    f"network output '{out}' ({type(obj).__name__}) is not "
                    "an output/loss layer"))

    if not structurally_ok:
        return issues  # topology below would mis-report on broken references

    # cycle / unreachable detection (Kahn's algorithm, mirrored from
    # topological_order but capturing the leftover set instead of raising)
    indeg = {n: len(ins) for n, (_, ins) in conf.vertices.items()}
    children: Dict[str, List[str]] = {n: [] for n in known_names}
    for name, (_, in_names) in conf.vertices.items():
        for i in in_names:
            children[conf.producer_of(i)].append(name)
    order: List[str] = []
    frontier = list(conf.network_inputs)
    while frontier:
        cur = frontier.pop()
        if cur in conf.vertices:
            order.append(cur)
        for ch in children[cur]:
            indeg[ch] -= 1
            if indeg[ch] == 0:
                frontier.append(ch)
    leftover = set(conf.vertices) - set(order)
    if leftover:
        # every leftover vertex is on a cycle or downstream of one (a
        # no-input or dangling-reference island was already rejected
        # above). Peel vertices with no successor inside the leftover set
        # until fixpoint: what remains is the cycle core, the peeled rest
        # merely depends on it.
        core = set(leftover)
        while True:
            downstream_free = {
                n for n in core
                if not any(n in conf.vertices[ch][1]  # ch==n: self-loop
                           for ch in core)}
            if not downstream_free:
                break
            core -= downstream_free
        cyclic = sorted(core) if core else sorted(leftover)
        issues.append(ValidationIssue(
            "cycle", f"'{cyclic[0]}'",
            f"graph has a cycle through vertices {cyclic}"))
        downstream = sorted(leftover - core)
        if core and downstream:
            issues.append(ValidationIssue(
                "cycle-downstream", f"'{downstream[0]}'",
                f"vertices {downstream} can never evaluate: they depend "
                f"on the cycle through {cyclic}"))
        return issues

    # dangling vertices: output feeds nothing and is not a network output
    consumed = {conf.producer_of(i)
                for _, (_, ins) in conf.vertices.items() for i in ins}
    for name in conf.vertices:
        if name not in consumed and name not in conf.network_outputs:
            issues.append(ValidationIssue(
                "dangling-vertex", f"'{name}'",
                f"vertex '{name}' is consumed by nothing and is not a "
                "network output (dead subgraph)", severity="warning"))

    # shape inference over the DAG, capturing per-vertex failures
    known: Dict[str, object] = dict(zip(conf.network_inputs,
                                        conf.input_types))
    inference_ok = True
    final_types: Dict[str, object] = {}
    for name in order:
        obj, in_names = conf.vertices[name]
        disp = f"'{name}'"
        missing = [i for i in in_names if i not in known]
        if missing:
            maker = conf.producer_of(missing[0])
            issues.append(ValidationIssue(
                "unknown-value", disp,
                f"vertex '{name}' reads '{missing[0]}', which '{maker}' "
                f"({type(conf.vertices[maker][0]).__name__}) does not hand "
                "on (Layer.shared_values)"))
            inference_ok = False
            break
        its = tuple(known[i] for i in in_names)
        if isinstance(obj, Layer):
            cur = its[0]
            try:
                pre = infer_preprocessor(cur, obj)
            except ValueError as e:
                issues.append(ValidationIssue(
                    "preprocessor", disp,
                    f"{e} (input {describe_type(cur)})"))
                inference_ok = False
                break
            if pre is not None:
                cur = pre.output_type(cur)
            issues.extend(_check_layer(obj, cur, disp))
            ti = _tied_issue(conf, obj, cur, disp)
            if ti is not None:
                issues.append(ti)
            try:
                known[name] = obj.output_type(cur)
            except ValueError as e:
                issues.append(ValidationIssue(
                    "geometry", disp,
                    f"{e} (input {describe_type(cur)})"))
                inference_ok = False
                break
            for key, kind in obj.shared_values(cur).items():
                known[f"{name}.{key}"] = kind
            issues.extend(_extra_input_issues(obj, its, in_names, disp))
        else:
            issues.extend(_merge_agreement_issues(obj, its, in_names, disp))
            if isinstance(obj, LastTimeStepVertex) \
                    and its[0].kind not in ("rnn", "cnn1d"):
                issues.append(ValidationIssue(
                    "time-axis", disp,
                    f"LastTimeStepVertex needs sequence input, got "
                    f"{describe_type(its[0])}"))
            if isinstance(obj, DuplicateToTimeSeriesVertex) \
                    and obj.reference_input is not None \
                    and obj.reference_input not in known_names:
                issues.append(ValidationIssue(
                    "unknown-input", disp,
                    f"reference_input '{obj.reference_input}' is not a "
                    "known vertex or network input"))
            if any(i.severity == "error" and i.layer == disp
                   for i in issues):
                inference_ok = False
                break
            try:
                known[name] = obj.output_type(*its)
            except (ValueError, IndexError, AttributeError) as e:
                issues.append(ValidationIssue(
                    "shape-inference", disp,
                    f"{type(obj).__name__}.output_type failed: {e} "
                    f"(inputs {[describe_type(t) for t in its]})"))
                inference_ok = False
                break
        final_types[name] = known[name]

    if inference_ok and labels_shapes is not None:
        for out, ls in zip(conf.network_outputs, labels_shapes):
            obj = conf.vertices[out][0]
            li = _labels_shape_issue(obj, final_types[out], ls, f"'{out}'")
            if li is not None:
                issues.append(li)

    if inference_ok and eval_shape_check \
            and not any(i.severity == "error" for i in issues):
        issues.extend(_eval_shape_check_graph(conf, batch))
    return issues


def _extra_input_issues(layer, its, in_names, name: str):
    """What a layer reads beside its own input has the width the layer's
    type says (``extra_input_sizes``, where the type has one)."""
    sizes = getattr(layer, "extra_input_sizes", None)
    if sizes is None:
        return []
    out = []
    # a layer whose fields NAME the value it reads is wired to that value
    for ref, want in zip(in_names[1:],
                         getattr(layer, "extra_input_refs", ())):
        if ref != want:
            out.append(ValidationIssue(
                "extra-input", name,
                f"{type(layer).__name__} names '{want}' in its fields, the "
                f"vertex is wired to '{ref}'"))
    for ref, kind, (key, want) in zip(in_names[1:], its[1:],
                                      sizes(its[0]).items()):
        if kind.flat_size() != want:
            out.append(ValidationIssue(
                "extra-input", name,
                f"{type(layer).__name__} reads '{ref}' as its {key} at a "
                f"width of {want}, the value is {describe_type(kind)}"))
    return out


def _tied_issue(conf, layer, cur, name: str) -> Optional[ValidationIssue]:
    """A layer whose ``tied_to`` names a vertex reads that vertex's matrix
    ``W`` transposed: the vertex has to be a layer whose ``W`` is this
    layer's (n_out, n_in)."""
    tied = getattr(layer, "tied_to", "")
    if not tied:
        return None
    other = conf.vertices.get(tied, (None,))[0]
    if not (hasattr(other, "n_in") and hasattr(other, "n_out")):
        return ValidationIssue(
            "tied-head", name,
            f"tied_to '{tied}' is no layer with a matrix to read "
            f"(vertices {sorted(conf.vertices)})")
    want = (layer.n_out, layer.n_in or cur.size)
    if (other.n_in, other.n_out) != want:
        return ValidationIssue(
            "tied-head", name,
            f"tied_to '{tied}' holds a matrix of ({other.n_in}, "
            f"{other.n_out}), this layer reads one of {want} transposed")
    return None


# ------------------------------------------------- jax.eval_shape cross-check
_DEFAULT_T = 16  # time length used when the config leaves it unknown


def _input_struct(it, batch: int, index_input: bool):
    """ShapeDtypeStruct for one network input of the given InputType."""
    import jax
    import jax.numpy as jnp
    if index_input:
        t = (it.timeseries_length or _DEFAULT_T) if it.kind in ("rnn", "cnn1d") else 1
        return jax.ShapeDtypeStruct((batch, t), jnp.int32)
    if it.kind in ("rnn", "cnn1d"):
        t = it.timeseries_length or _DEFAULT_T
        return jax.ShapeDtypeStruct((batch, t, it.size), jnp.float32)
    if it.kind == "cnn":
        return jax.ShapeDtypeStruct(
            (batch, it.height, it.width, it.channels), jnp.float32)
    return jax.ShapeDtypeStruct((batch, it.flat_size()), jnp.float32)


def _shape_agrees(predicted, actual: Tuple[int, ...]) -> bool:
    """Does a traced activation shape match the InputType prediction?
    Batch dims are never compared (preprocessors legally fold time into
    batch); unknown sequence lengths (None) match anything."""
    if predicted.passes:     # a LoopVertex's stack: passes, then one pass
        return (len(actual) > 1 and actual[0] == predicted.passes
                and _shape_agrees(dataclasses.replace(predicted, passes=None),
                                  actual[1:]))
    if predicted.kind in ("ff", "cnn_flat"):
        return len(actual) == 2 and actual[-1] == predicted.flat_size()
    if predicted.kind in ("rnn", "cnn1d"):
        if len(actual) != 3 or actual[-1] != predicted.size:
            return False
        t = predicted.timeseries_length
        return t is None or actual[1] == t
    if predicted.kind == "cnn":
        return (len(actual) == 4 and tuple(actual[1:]) ==
                (predicted.height, predicted.width, predicted.channels))
    return True


def _abstract_init(layer, it, key):
    """Parameter/state SHAPES of layer.init without allocating anything."""
    import jax
    import jax.numpy as jnp
    return jax.eval_shape(lambda k: layer.init(k, it, jnp.float32), key)


def _is_index_layer(layer) -> bool:
    from deeplearning4j_tpu.nn.conf.recurrent import EmbeddingLayer
    return (getattr(layer, "takes_index_sequence", False)
            or isinstance(layer, EmbeddingLayer))


def _eval_shape_check_multilayer(conf, batch: int) -> List[ValidationIssue]:
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    issues: List[ValidationIssue] = []
    net = MultiLayerNetwork(conf)
    types = conf.layer_input_types()
    key = jax.random.key(0)
    params, state = [], []
    for layer, it in zip(net.layers, types):
        p, s = _abstract_init(layer, it, key)
        params.append(p)
        state.append(s)
    first = net.layers[0]
    if _is_index_layer(first) and not getattr(first, "takes_index_sequence",
                                              False):
        x = jax.ShapeDtypeStruct((batch, 1), jnp.int32)  # EmbeddingLayer ids
    else:
        x = _input_struct(conf.input_type, batch, _is_index_layer(first))
    try:
        acts = jax.eval_shape(
            lambda p, s, xx: net._forward(p, s, xx, False, None, None)[0],
            params, state, x)
    except Exception as e:  # inference said OK but tracing disagrees
        return [ValidationIssue(
            "eval-shape-trace", "<network>",
            f"jax.eval_shape of the forward pass failed although shape "
            f"inference passed: {type(e).__name__}: {e}")]
    for i, (layer, it) in enumerate(zip(net.layers, types)):
        predicted = layer.output_type(it)
        actual = tuple(acts[i].shape)
        if not _shape_agrees(predicted, actual):
            issues.append(ValidationIssue(
                "eval-shape-drift", _layer_name(i, layer),
                f"shape inference predicts {describe_type(predicted)} but "
                f"jax.eval_shape traces activation shape {actual}"))
    return issues


def _eval_shape_check_graph(conf, batch: int) -> List[ValidationIssue]:
    import jax
    from deeplearning4j_tpu.nn.conf.layers import Layer
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    issues: List[ValidationIssue] = []
    try:
        net = ComputationGraph(conf)
    except ValueError as e:
        return [ValidationIssue("graph-construction", "<network>", str(e))]
    key = jax.random.key(0)
    params, state = {}, {}
    for name in net.order:
        obj, _ = net.vertices[name]
        if isinstance(obj, Layer):
            p, s = _abstract_init(obj, net.vertex_input_types[name][0], key)
        else:
            p, s = {}, {}
        params[name] = p
        state[name] = s
    # an input is an index sequence when any direct consumer embeds ids
    inputs = []
    for ni, it in zip(conf.network_inputs, conf.input_types):
        consumers = [conf.vertices[n][0] for n, (_, ins) in
                     conf.vertices.items() if ni in ins]
        idx = any(isinstance(c, Layer) and _is_index_layer(c)
                  for c in consumers)
        inputs.append(_input_struct(it, batch, idx))
    try:
        acts = jax.eval_shape(
            lambda p, s, xs: net._forward(p, s, xs, False, None, None)[0],
            params, state, inputs)
    except Exception as e:
        return [ValidationIssue(
            "eval-shape-trace", "<network>",
            f"jax.eval_shape of the graph forward failed although shape "
            f"inference passed: {type(e).__name__}: {e}")]
    predicted_types = conf.vertex_output_types()
    for name in net.order:
        predicted = predicted_types[name]
        actual = tuple(acts[name].shape)
        if not _shape_agrees(predicted, actual):
            issues.append(ValidationIssue(
                "eval-shape-drift", f"'{name}'",
                f"shape inference predicts {describe_type(predicted)} but "
                f"jax.eval_shape traces activation shape {actual}"))
    return issues

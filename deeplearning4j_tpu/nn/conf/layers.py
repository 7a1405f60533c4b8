"""Layer configurations + implementations (feed-forward core).

Parity surface: reference ``nn/conf/layers/*`` (declarative configs) together
with ``nn/layers/*`` (imperative impls). In the TPU rebuild the conf/impl split
collapses: each config dataclass carries pure ``init``/``apply`` functions that
JAX traces into one XLA program — the per-layer interpretive loop of
``MultiLayerNetwork.feedForwardToLayer`` disappears at compile time.

Contract (every layer):
- ``output_type(input_type) -> InputType``      shape inference
  (reference: ``Layer.getOutputType`` in nn/conf/layers/Layer.java)
- ``init(rng, input_type, dtype) -> (params, state)``   params is a dict of
  arrays; state is a dict for non-trainable buffers (batchnorm running stats)
  (reference: the ``nn/params/*ParamInitializer`` classes)
- ``apply(params, state, x, *, train, rng, mask) -> (out, new_state)``
  (reference: ``Layer.activate`` — nn/api/Layer.java:114-166; backprop is jax
  autodiff instead of ``Layer.backpropGradient``)

Dropout field semantics follow DL4J 0.9: ``dropout`` is the *retain*
probability applied to the layer's input when training (0 disables).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.initializers import Distribution, init_weights
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn import lossfunctions
from deeplearning4j_tpu.obs.owners import layer_marker
from deeplearning4j_tpu.optimize.updaters import Updater

LAYER_REGISTRY = {}


def register_layer(cls):
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_to_dict(conf) -> dict:
    d = {"@class": type(conf).__name__}
    for f in dataclasses.fields(conf):
        v = getattr(conf, f.name)
        if v is None:
            continue
        if isinstance(v, (Updater,)):
            v = v.to_dict()
        elif isinstance(v, Distribution):
            v = v.to_dict()
        elif isinstance(v, InputType):
            v = v.to_dict()
        elif dataclasses.is_dataclass(v) and hasattr(v, "to_dict"):
            v = v.to_dict()
        elif isinstance(v, (tuple, list)):
            v = [e.to_dict() if dataclasses.is_dataclass(e)
                 and hasattr(e, "to_dict") else e for e in v]
        d[f.name] = v
    return d


def layer_from_dict(d: dict):
    d = dict(d)
    cls = LAYER_REGISTRY[d.pop("@class")]
    names = {f.name for f in dataclasses.fields(cls)}
    kwargs = {}
    for k, v in d.items():
        if k not in names:
            continue
        if k == "updater" and isinstance(v, dict):
            v = Updater.from_dict(v)
        elif k == "dist" and isinstance(v, dict):
            v = Distribution.from_dict(v)
        elif isinstance(v, dict) and "@class" in v:  # nested layer (e.g. Bidirectional)
            v = layer_from_dict(v)
        elif isinstance(v, list):  # JSON has no tuples
            v = tuple(layer_from_dict(e)
                      if isinstance(e, dict) and "@class" in e else e
                      for e in v)
        kwargs[k] = v
    return cls(**kwargs)


def resolve_param_path(params: dict, key: str):
    """Resolve a possibly-nested '/'-separated param key (wrapper layers like
    Bidirectional expose 'fwd/W'-style paths). Returns the array or None."""
    node = params
    for part in key.split("/"):
        if isinstance(node, dict) and part in node:
            node = node[part]
        else:
            return None
    return node


def regularization_coefficients(layer):
    """(l1, l2, l1_bias, l2_bias) for a layer; wrapper layers (those with a
    nested ``layer`` field) fall back to the inner layer's coefficients when
    their own are all zero — matching the reference, where the wrapped layer's
    conf carries the regularization."""
    vals = (getattr(layer, "l1", 0.0) or 0.0, getattr(layer, "l2", 0.0) or 0.0,
            getattr(layer, "l1_bias", 0.0) or 0.0,
            getattr(layer, "l2_bias", 0.0) or 0.0)
    inner = getattr(layer, "layer", None)
    if inner is not None and not any(vals):
        return regularization_coefficients(inner)
    return vals


def dropout_input(x, dropout, train: bool, rng):
    """Inverted dropout on layer input (reference: Dropout.applyDropout via
    BaseLayer.applyDropOutIfNecessary; retain-prob semantics of DL4J 0.9).
    ``dropout`` may be a plain retain probability or an IDropout object
    (AlphaDropout/GaussianDropout/GaussianNoise — nn/conf/regularization)."""
    if not dropout:  # None / 0.0: disabled
        return x
    if not hasattr(dropout, "apply"):
        from deeplearning4j_tpu.nn.conf.regularization import Dropout
        dropout = Dropout(float(dropout))  # single implementation of the math
    return dropout.apply(x, rng, train)


def _set_param_path(params: dict, key: str, value):
    """Set a possibly-nested '/'-separated param key in place."""
    node = params
    parts = key.split("/")
    for part in parts[:-1]:
        node = node[part]
    node[parts[-1]] = value


def reg_object(layer, attr: str):
    """Resolve ``constraints``/``weight_noise`` on a layer, falling back to
    the wrapped layer for wrapper configs (Bidirectional etc.) — same
    fallthrough as regularization_coefficients."""
    v = getattr(layer, attr, None)
    if v is None:
        inner = getattr(layer, "layer", None)
        if inner is not None:
            return reg_object(inner, attr)
    return v


def _bias_keys(layer, params: dict) -> list:
    """Bias param paths: top-level 'b' plus the sibling of every nested
    weight path (e.g. 'fwd/W' -> 'fwd/b' for wrapper layers)."""
    keys = []
    if resolve_param_path(params, "b") is not None:
        keys.append("b")
    for wk in layer.regularizable():
        if "/" in wk:
            bk = wk.rsplit("/", 1)[0] + "/b"
            if bk not in keys and resolve_param_path(params, bk) is not None:
                keys.append(bk)
    return keys


def _constraint_keys(layer, params: dict, c) -> list:
    keys = []
    if getattr(c, "apply_to_weights", True):
        keys.extend(k for k in layer.regularizable()
                    if resolve_param_path(params, k) is not None)
    if getattr(c, "apply_to_biases", False):
        keys.extend(_bias_keys(layer, params))
    return keys


def apply_constraints(layer, params):
    """Apply the layer's parameter constraints after an update (reference
    BaseConstraint.applyConstraint, called from BaseMultiLayerUpdater).
    ``params`` must be a freshly-built dict (it is mutated in place inside
    the traced step)."""
    cons = reg_object(layer, "constraints")
    if not cons:
        return params
    for c in cons:
        for key in _constraint_keys(layer, params, c):
            _set_param_path(params, key,
                            c.apply(resolve_param_path(params, key)))
    return params


def apply_layer(layer, params, state, x, *, train, rng, mask, name,
                extra=None):
    """The networks' single entry into ``layer.apply``: lowers the layer
    through ``jax.checkpoint`` when its ``remat=`` knob is set (policy names
    in perf/fusion.py), so the backward pass recomputes instead of saving
    what the policy excludes. What the layer's TYPE names as dearer to
    recompute than to keep (``remat_keeps``) is kept under every policy but
    ``"nothing_saveable"``: ``"full"`` recomputes everything else, and
    everything for a type that names nothing (``policy=None``, as ever).
    Counted at trace time, one a name that a layer application's policy
    holds: ``remat.kept_values``. ``extra`` carries optional additional
    traced inputs (the fused residual-add input in ComputationGraph).
    ``name`` is the layer's name in its network (a graph's vertex name, an
    MLN's index): every operation of the layer, forward and backward,
    carries ``<LayerClass>:<name>`` in its ``op_name``, so a device trace
    can be read by layer kind whatever the compiler calls its fusions."""
    extra = extra or {}
    with jax.named_scope(layer_marker(layer, name)):
        if getattr(layer, "remat", None):
            from deeplearning4j_tpu.perf.compile_watch import bump_active
            from deeplearning4j_tpu.perf.fusion import (
                kept_names, remat_policy)
            keeps = kept_names(layer)
            policy = remat_policy(layer.remat, keeps)
            if keeps:
                bump_active("remat.kept_values", len(keeps))

            def run(p, s, xx, kk, mm, ee):
                return layer.apply(p, s, xx, train=train, rng=kk, mask=mm,
                                   **ee)

            return jax.checkpoint(run, policy=policy)(params, state, x, rng,
                                                      mask, extra)
        return layer.apply(params, state, x, train=train, rng=rng, mask=mask,
                           **extra)


def noisy_params(layer, params, rng, train: bool):
    """Apply the layer's weight noise for a training forward pass (reference
    BaseLayer.getParamWithNoise via IWeightNoise). Uses a stream folded off
    the layer's dropout key so the two draws are independent."""
    wn = reg_object(layer, "weight_noise")
    if wn is None or not train or rng is None:
        return params
    out = dict(params)
    keys = [k for k in layer.regularizable()
            if resolve_param_path(params, k) is not None]
    if wn.apply_to_bias:
        keys.extend(_bias_keys(layer, params))
    for i, key in enumerate(keys):
        sub = jax.random.fold_in(rng, 7919 + i)
        if "/" in key:  # nested (wrapper layers): rebuild the nested dicts
            top, restk = key.split("/", 1)
            inner = dict(out[top])
            inner[restk] = wn.apply_to_param(inner[restk], sub)
            out[top] = inner
        else:
            out[key] = wn.apply_to_param(out[key], sub)
    return out


@dataclasses.dataclass(frozen=True)
class Layer:
    """Base of all layer configs (reference nn/conf/layers/Layer.java)."""

    name: Optional[str] = None
    dropout: float = 0.0
    # per-layer rematerialization: lower this layer's apply through
    # jax.checkpoint with the named policy (perf/fusion.py REMAT_POLICIES:
    # 'full' recomputes everything in the backward but what the layer's
    # type names (remat_keeps, below); 'dots_saveable' keeps matmul/conv
    # outputs; 'nothing_saveable' keeps nothing at all; ...). None = normal
    # autodiff saving. Validated by analysis/validation.py; visible in
    # conf.memory_report().
    remat: Optional[str] = None

    # what a rematerialised layer of this TYPE keeps all the same, as dearer
    # to recompute than to hold: the ``checkpoint_name``s its computation
    # gives them (``apply_layer`` joins them to the policy; ``"full"`` then
    # keeps these alone, ``"nothing_saveable"`` nothing). Not a field: the
    # type declares it, and ``remat_kept_bytes`` says what it costs (the
    # delta-rule layers: their scan's results and their wide projections'
    # outputs; the latent attention: its attention's output and log-sum-exp
    # and q, k, v as it hands them to the attention).
    remat_keeps = ()

    def remat_kept_bytes(self, input_type: InputType,
                         dtype=jnp.float32) -> int:
        """Bytes of ``remat_keeps`` for ONE example of ``input_type`` in a
        network that computes in ``dtype``."""
        return 0

    # a layer in a ``ComputationGraph`` may read more than one vertex and
    # hand on more than one value. ``extra_inputs`` names the keyword by
    # which ``apply`` takes each vertex input after the first (the fused
    # conv block's residual-add operand ``res``, a gated memory unit's
    # ``memory``, a cross-attention's ``kv``). ``shared_values`` names what
    # ``apply`` hands on BESIDE its output, and each value's type: with any,
    # ``apply`` returns ``((out, {name: value}), state)`` and a later vertex
    # reads ``<vertex>.<name>`` as one of its inputs (``nn/graph.py``
    # ``run_vertices``). Not fields: the type declares both from its fields.
    extra_inputs = ()

    def shared_values(self, input_type: InputType) -> dict:
        return {}

    # ---- shape inference ----
    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    # ---- params ----
    def init(self, rng, input_type: InputType, dtype=jnp.float32):
        return {}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        raise NotImplementedError

    # which param keys get l1/l2 (weights only, like DL4J's regularization-by-param-type)
    def regularizable(self) -> Tuple[str, ...]:
        return ()

    def is_output_layer(self) -> bool:
        return False

    def is_recurrent(self) -> bool:
        return False

    def input_kind(self) -> str:
        """Preferred input family for automatic preprocessor insertion:
        'ff' | 'cnn' | 'rnn' | 'any' (reference: each layer conf's
        getPreProcessorForInputType)."""
        return "any"

    def with_n_in(self, n_in: int):
        """Fill in n_in during config wiring (reference
        MultiLayerConfiguration's preProcess/setNIn pass)."""
        if hasattr(self, "n_in") and getattr(self, "n_in") in (None, 0):
            return dataclasses.replace(self, n_in=n_in)
        return self

    def to_dict(self):
        return layer_to_dict(self)


@dataclasses.dataclass(frozen=True)
class BaseLayer(Layer):
    """Layers with weights (reference nn/conf/layers/BaseLayer.java): carry
    activation, weight init, regularization and per-layer updater override."""

    activation: str = "identity"
    weight_init: str = "xavier"
    dist: Optional[Distribution] = None
    bias_init: float = 0.0
    l1: float = 0.0
    l2: float = 0.0
    l1_bias: float = 0.0
    l2_bias: float = 0.0
    updater: Optional[Updater] = None
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    # post-update parameter constraints (nn/conf/regularization.py;
    # reference nn/conf/constraint/)
    constraints: Optional[tuple] = None
    # training-forward weight noise (reference nn/conf/weightnoise/)
    weight_noise: Optional[object] = None

    def regularizable(self):
        return ("W",)


@register_layer
@dataclasses.dataclass(frozen=True)
class DenseLayer(BaseLayer):
    """Fully connected layer (reference nn/conf/layers/DenseLayer.java +
    nn/layers/feedforward/dense/DenseLayer.java). y = act(x @ W + b).

    The matmul is MXU-shaped: (batch, n_in) @ (n_in, n_out)."""

    n_in: Optional[int] = None
    n_out: int = 0
    has_bias: bool = True

    def input_kind(self):
        return "ff"

    def output_type(self, input_type):
        if input_type.kind == "rnn":  # dense broadcasts over time natively
            return InputType.recurrent(self.n_out, input_type.timeseries_length)
        return InputType.feed_forward(self.n_out)

    def init(self, rng, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.flat_size()
        k_w, _ = jax.random.split(rng)
        params = {
            "W": init_weights(k_w, (n_in, self.n_out), n_in, self.n_out,
                              self.weight_init, self.dist, dtype)
        }
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return get_activation(self.activation)(z), state


@register_layer
@dataclasses.dataclass(frozen=True)
class ActivationLayer(Layer):
    """Pure activation (reference nn/conf/layers/ActivationLayer.java).
    ``activation_param`` feeds parameterized activations (LeakyReLU alpha,
    ELU alpha, ThresholdedReLU theta — the Keras advanced-activation layer
    classes lower to this)."""

    activation: str = "relu"
    activation_param: Optional[float] = None

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        fn = get_activation(self.activation)
        if self.activation_param is not None:
            return fn(x, self.activation_param), state
        return fn(x), state


@register_layer
@dataclasses.dataclass(frozen=True)
class PReLULayer(BaseLayer):
    """Parametric ReLU with learnable negative slope (reference
    nn/conf/layers/PReLULayer — Keras advanced_activations.PReLU).
    ``shared_axes`` lists 1-based input axes sharing one alpha (Keras
    convention: shared_axes=[1, 2] gives per-channel alpha on NHWC)."""

    shared_axes: Optional[Tuple[int, ...]] = None

    def input_kind(self):
        return "any"

    def output_type(self, input_type):
        return input_type

    def _alpha_shape(self, input_type):
        if input_type.kind == "cnn":
            shape = [input_type.height, input_type.width, input_type.channels]
        elif input_type.kind in ("rnn", "cnn1d"):
            shape = [input_type.timeseries_length or 1, input_type.size]
        else:
            shape = [input_type.flat_size()]
        for ax in self.shared_axes or ():
            shape[ax - 1] = 1
        return tuple(shape)

    def init(self, rng, input_type, dtype=jnp.float32):
        return {"alpha": jnp.zeros(self._alpha_shape(input_type), dtype)}, {}

    def regularizable(self):
        return ()

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        alpha = params["alpha"]
        return jnp.where(x >= 0, x, alpha * x), state


@register_layer
@dataclasses.dataclass(frozen=True)
class DropoutLayer(Layer):
    """Standalone dropout (reference nn/conf/layers/DropoutLayer.java).
    ``dropout`` = retain probability."""

    dropout: float = 0.5

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return dropout_input(x, self.dropout, train, rng), state


@dataclasses.dataclass(frozen=True)
class BaseOutputLayer(BaseLayer):
    """Common machinery for loss-bearing layers (reference
    nn/conf/layers/BaseOutputLayer.java + nn/layers/BaseOutputLayer.java).

    ``apply`` returns post-activation predictions; ``pre_output`` returns the
    pre-activation z used for the numerically-stable fused loss; ``score``
    computes the mask-aware mean loss."""

    loss: str = "mcxent"
    loss_weights: Optional[Tuple[float, ...]] = None

    def is_output_layer(self):
        return True

    def pre_output(self, params, x):
        """May return a pytree for layers whose score needs more than the
        logits (CenterLoss carries features+centers; YOLO the raw grid)."""
        z = x @ params["W"]
        if "b" in params:
            z = z + params["b"]
        return z

    def output_activations(self, preout):
        """preout -> network predictions (the networks call this instead of
        applying ``activation`` directly, so structured preouts work)."""
        return get_activation(self.activation)(preout)

    def compute_score(self, labels, preout, mask=None):
        return lossfunctions.score(self.loss, labels, preout, self.activation,
                                   mask, self.loss_weights)

    def compute_score_array(self, labels, preout, mask=None):
        return lossfunctions.score_array(self.loss, labels, preout,
                                         self.activation, mask, self.loss_weights)


@register_layer
@dataclasses.dataclass(frozen=True)
class OutputLayer(BaseOutputLayer):
    """Dense + loss (reference nn/conf/layers/OutputLayer.java)."""

    n_in: Optional[int] = None
    n_out: int = 0
    has_bias: bool = True
    activation: str = "softmax"

    def input_kind(self):
        return "ff"

    def output_type(self, input_type):
        return InputType.feed_forward(self.n_out)

    def init(self, rng, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.flat_size()
        k_w, _ = jax.random.split(rng)
        params = {
            "W": init_weights(k_w, (n_in, self.n_out), n_in, self.n_out,
                              self.weight_init, self.dist, dtype)
        }
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        return get_activation(self.activation)(self.pre_output(params, x)), state


@register_layer
@dataclasses.dataclass(frozen=True)
class CenterLossOutputLayer(OutputLayer):
    """Softmax output + center loss (reference
    nn/conf/layers/CenterLossOutputLayer.java: alpha=0.05, lambda=2e-4;
    nn/layers/training/CenterLossOutputLayer.java:35).

    Loss = interclass(labels, softmax) + lambda/2 * mean ||f - c_y||^2 where
    f is the layer input (the embedding) and c_y the per-class center.

    Center updates mirror the reference's hand-crafted rule (centers move
    toward the class mean of the features with rate alpha, normalized by
    class count + 1 — CenterLossOutputLayer.java:209-224): that direction is
    injected as the autodiff gradient of a value-neutral pseudo-term, so any
    updater works on the other params while centers follow the reference
    dynamics."""

    alpha: float = 0.05
    lamda: float = 2e-4   # "lambda" is a Python keyword; JSON key is "lamda"
    # reference's gradientCheck flag (CenterLossOutputLayer.java:218): centers
    # take the TRUE loss gradient instead of the alpha EMA direction, so
    # finite-difference checks pass
    gradient_check: bool = False

    def init(self, rng, input_type, dtype=jnp.float32):
        params, state = super().init(rng, input_type, dtype)
        n_in = self.n_in or input_type.flat_size()
        # centers start at zero (reference CenterLossParamInitializer)
        params["cL"] = jnp.zeros((self.n_out, n_in), dtype)
        return params, state

    def pre_output(self, params, x):
        z = x @ params["W"]
        if "b" in params:
            z = z + params["b"]
        # score needs the features and centers too: carry them as a pytree
        return {"z": z, "f": x, "cL": params["cL"]}

    def output_activations(self, preout):
        return get_activation(self.activation)(preout["z"])

    def compute_score(self, labels, preout, mask=None):
        inter = lossfunctions.score(self.loss, labels, preout["z"],
                                    self.activation, mask, self.loss_weights)
        if self.gradient_check:
            centers_y = labels @ preout["cL"]             # true gradient mode
            diff = preout["f"] - centers_y
            return inter + 0.5 * self.lamda * jnp.mean(jnp.sum(diff * diff, -1))
        centers_y = labels @ jax.lax.stop_gradient(preout["cL"])  # (B, n_in)
        diff = preout["f"] - centers_y
        intra = 0.5 * self.lamda * jnp.mean(jnp.sum(diff * diff, -1))
        # value-neutral term whose gradient w.r.t. centers reproduces the
        # reference's alpha * sum(c_y - f) / (count_y + 1) update direction
        counts = jnp.sum(labels, 0)                       # (n_out,)
        w_per_ex = labels @ (1.0 / (counts + 1.0))        # (B,)
        cdiff = labels @ preout["cL"] - jax.lax.stop_gradient(preout["f"])
        pseudo = 0.5 * self.alpha * jnp.sum(
            w_per_ex[:, None] * cdiff * cdiff)
        pseudo = pseudo - jax.lax.stop_gradient(pseudo)   # grad only, no value
        return inter + intra + pseudo

    def compute_score_array(self, labels, preout, mask=None):
        inter = lossfunctions.score_array(self.loss, labels, preout["z"],
                                          self.activation, mask,
                                          self.loss_weights)
        centers_y = labels @ preout["cL"]
        intra = 0.5 * self.lamda * jnp.sum((preout["f"] - centers_y) ** 2, -1)
        return inter + intra


@register_layer
@dataclasses.dataclass(frozen=True)
class LossLayer(BaseOutputLayer):
    """Loss without weights (reference nn/conf/layers/LossLayer.java)."""

    activation: str = "identity"

    def regularizable(self):
        return ()

    def pre_output(self, params, x):
        return x

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return get_activation(self.activation)(x), state

"""Recurrent layer family: LSTM, GravesLSTM (peepholes), bidirectional
wrappers, RnnOutputLayer, embeddings.

Parity surface: reference ``nn/conf/layers/{LSTM,GravesLSTM,
GravesBidirectionalLSTM,RnnOutputLayer,EmbeddingLayer}.java`` and the shared
imperative math in ``nn/layers/recurrent/LSTMHelpers.java`` (785 LoC fwd/bwd
for all LSTM variants; cuDNN path CudnnLSTMHelper.java).

TPU-native design:
- activations are (batch, time, size) — time-major is used only inside the
  scan; the input-to-hidden projection for ALL timesteps is hoisted out of the
  recurrence as one large MXU matmul ``(b*t, n_in) @ (n_in, 4n)``, so the
  scan body is just the small recurrent matmul + gate math.
- the backward pass is jax autodiff through ``lax.scan`` (replacing the
  hand-written backpropGradientHelper of LSTMHelpers.java:462).
- per-timestep masking holds cell/hidden state through masked steps and zeroes
  the output, matching the reference's variable-length masking semantics.
- stateful inference (``rnnTimeStep`` — MultiLayerNetwork.java:2615) and
  truncated BPTT carry an explicit (h, c) pytree; layers expose
  ``init_carry``/``apply_seq`` so the network can thread carries through jit.

Gate ordering is (i, f, g, o); forget-gate bias init defaults to 1.0 like the
reference's ``forgetGateBiasInit``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.initializers import init_weights
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BaseLayer, BaseOutputLayer, register_layer, dropout_input, layer_from_dict,
)


@dataclasses.dataclass(frozen=True)
class BaseRecurrentLayer(BaseLayer):
    """Common recurrent contract: carries + sequence application."""

    def is_recurrent(self):
        return True

    def input_kind(self):
        return "rnn"

    def init_carry(self, batch: int, dtype=jnp.float32):
        raise NotImplementedError

    def apply_seq(self, params, carry, x, *, train=False, rng=None, mask=None):
        """(out, new_carry); x is (batch, time, n_in)."""
        raise NotImplementedError

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        out, _ = self.apply_seq(params, self.init_carry(x.shape[0], x.dtype),
                                x, train=train, rng=rng, mask=mask)
        return out, state


@register_layer
@dataclasses.dataclass(frozen=True)
class LSTM(BaseRecurrentLayer):
    """Standard LSTM (reference nn/conf/layers/LSTM.java — no peepholes;
    matches CudnnLSTMHelper-supported config: sigmoid gates + tanh)."""

    n_in: Optional[int] = None
    n_out: int = 0
    activation: str = "tanh"
    gate_activation: str = "sigmoid"
    forget_gate_bias_init: float = 1.0

    def regularizable(self):
        return ("W", "U")

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.size
        n = self.n_out
        k1, k2 = jax.random.split(rng)
        # fused gate weights: order (i, f, g, o)
        W = init_weights(k1, (n_in, 4 * n), n_in, n, self.weight_init, self.dist, dtype)
        U = init_weights(k2, (n, 4 * n), n, n, self.weight_init, self.dist, dtype)
        b = jnp.zeros((4 * n,), dtype)
        b = b.at[n:2 * n].set(self.forget_gate_bias_init)
        return {"W": W, "U": U, "b": b}, {}

    def init_carry(self, batch, dtype=jnp.float32):
        n = self.n_out
        return {"h": jnp.zeros((batch, n), dtype), "c": jnp.zeros((batch, n), dtype)}

    def _gates(self, z, c_prev, params):
        n = self.n_out
        act = get_activation(self.activation)
        gate = get_activation(self.gate_activation)
        i = gate(z[:, 0 * n:1 * n])
        f = gate(z[:, 1 * n:2 * n])
        g = act(z[:, 2 * n:3 * n])
        o = gate(z[:, 3 * n:4 * n])
        c = f * c_prev + i * g
        h = o * act(c)
        return h, c

    def apply_seq(self, params, carry, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        b, t, _ = x.shape
        # hoisted input projection: one big MXU matmul over all timesteps
        xw = (x.reshape(b * t, -1) @ params["W"] + params["b"]).reshape(b, t, -1)
        xw_t = jnp.swapaxes(xw, 0, 1)                      # (t, b, 4n)
        m_t = None if mask is None else jnp.swapaxes(mask, 0, 1)  # (t, b)

        U = params["U"]

        def step(c, inp):
            if m_t is None:
                xw_i = inp
            else:
                xw_i, m_i = inp
            h_prev, c_prev = c["h"], c["c"]
            z = xw_i + h_prev @ U
            h, cc = self._gates(z, c_prev, params)
            if m_t is not None:
                keep = m_i[:, None]
                h = keep * h + (1.0 - keep) * h_prev
                cc = keep * cc + (1.0 - keep) * c_prev
                out = keep * h
            else:
                out = h
            return {"h": h, "c": cc}, out

        xs = xw_t if m_t is None else (xw_t, m_t)
        new_carry, outs = lax.scan(step, carry, xs)
        return jnp.swapaxes(outs, 0, 1), new_carry


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesLSTM(LSTM):
    """LSTM with peephole connections (reference nn/conf/layers/GravesLSTM.java;
    math per LSTMHelpers.java with hasPeepholeConnections=true): diagonal
    peepholes c_{t-1} -> i,f gates and c_t -> o gate."""

    def init(self, rng, it: InputType, dtype=jnp.float32):
        params, state = super().init(rng, it, dtype)
        n = self.n_out
        k = jax.random.fold_in(rng, 7)
        k1, k2, k3 = jax.random.split(k, 3)
        params["p_i"] = init_weights(k1, (n,), n, n, "uniform", None, dtype)
        params["p_f"] = init_weights(k2, (n,), n, n, "uniform", None, dtype)
        params["p_o"] = init_weights(k3, (n,), n, n, "uniform", None, dtype)
        return params, state

    def _gates(self, z, c_prev, params):
        n = self.n_out
        act = get_activation(self.activation)
        gate = get_activation(self.gate_activation)
        i = gate(z[:, 0 * n:1 * n] + c_prev * params["p_i"])
        f = gate(z[:, 1 * n:2 * n] + c_prev * params["p_f"])
        g = act(z[:, 2 * n:3 * n])
        c = f * c_prev + i * g
        o = gate(z[:, 3 * n:4 * n] + c * params["p_o"])
        h = o * act(c)
        return h, c


def _flip_time(x, mask):
    """Reverse the time axis; with a mask, reverse only the valid prefix of
    each sequence (matches the reference's bidirectional reversal semantics)."""
    if mask is None:
        return jnp.flip(x, axis=1)
    t = x.shape[1]
    lengths = jnp.sum(mask, axis=1).astype(jnp.int32)          # (b,)
    idx = jnp.arange(t)[None, :]                               # (1, t)
    src = lengths[:, None] - 1 - idx                           # reversed valid prefix
    src = jnp.where(src >= 0, src, idx)                        # padding stays in place
    return jnp.take_along_axis(x, src[..., None].astype(jnp.int32), axis=1)


@register_layer
@dataclasses.dataclass(frozen=True)
class Bidirectional(BaseRecurrentLayer):
    """Generic bidirectional wrapper (reference
    nn/conf/layers/GravesBidirectionalLSTM.java generalized; mode semantics
    from the later Bidirectional wrapper): runs the wrapped recurrent layer
    forward and time-reversed, combining with mode add|mul|average|concat."""

    layer: Optional[LSTM] = None
    mode: str = "concat"

    # Carrying state across windows/steps is temporally invalid for the
    # backward direction (the reference's GravesBidirectionalLSTM.rnnTimeStep
    # throws UnsupportedOperationException); under tBPTT each window is
    # processed statelessly.
    supports_stateful = False

    def regularizable(self):
        # Regularize both directions' wrapped weights (the reference applies
        # l1/l2 to fwd and bwd input+recurrent weights alike); "/"-paths are
        # resolved into the nested param tree by the network's _regularization.
        inner = self.layer.regularizable() if self.layer is not None else ()
        return tuple(f"{d}/{k}" for d in ("fwd", "bwd") for k in inner)

    def output_type(self, it: InputType) -> InputType:
        inner = self.layer.output_type(it)
        n = inner.size * 2 if self.mode == "concat" else inner.size
        return InputType.recurrent(n, it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        k1, k2 = jax.random.split(rng)
        fwd, _ = self.layer.init(k1, it, dtype)
        bwd, _ = self.layer.init(k2, it, dtype)
        return {"fwd": fwd, "bwd": bwd}, {}

    def init_carry(self, batch, dtype=jnp.float32):
        return {"fwd": self.layer.init_carry(batch, dtype),
                "bwd": self.layer.init_carry(batch, dtype)}

    def apply_seq(self, params, carry, x, *, train=False, rng=None, mask=None):
        k1 = k2 = None
        if rng is not None:
            k1, k2 = jax.random.split(rng)
        out_f, c_f = self.layer.apply_seq(params["fwd"], carry["fwd"], x,
                                          train=train, rng=k1, mask=mask)
        x_rev = _flip_time(x, mask)
        out_b, c_b = self.layer.apply_seq(params["bwd"], carry["bwd"], x_rev,
                                          train=train, rng=k2, mask=mask)
        out_b = _flip_time(out_b, mask)
        m = self.mode
        if m == "concat":
            out = jnp.concatenate([out_f, out_b], axis=-1)
        elif m == "add":
            out = out_f + out_b
        elif m == "mul":
            out = out_f * out_b
        elif m == "average":
            out = 0.5 * (out_f + out_b)
        else:
            raise ValueError(f"Unknown bidirectional mode '{self.mode}'")
        return out, {"fwd": c_f, "bwd": c_b}

    def with_n_in(self, n_in):
        if self.layer is not None and getattr(self.layer, "n_in", 0) in (None, 0):
            return dataclasses.replace(self, layer=self.layer.with_n_in(n_in))
        return self


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesBidirectionalLSTM(Bidirectional):
    """reference nn/conf/layers/GravesBidirectionalLSTM.java — bidirectional
    GravesLSTM with summed outputs."""

    mode: str = "add"

    def __post_init__(self):
        if self.layer is None:
            raise ValueError("GravesBidirectionalLSTM requires layer=GravesLSTM(...)")


@register_layer
@dataclasses.dataclass(frozen=True)
class RnnOutputLayer(BaseOutputLayer):
    """Per-timestep output + loss (reference nn/conf/layers/RnnOutputLayer.java).
    Dense over the feature axis of (batch, time, n_in); the loss averages over
    unmasked timesteps."""

    n_in: Optional[int] = None
    n_out: int = 0
    has_bias: bool = True
    activation: str = "softmax"

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.size
        params = {"W": init_weights(rng, (n_in, self.n_out), n_in, self.n_out,
                                    self.weight_init, self.dist, dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def pre_output(self, params, x):
        z = x @ params["W"]
        if "b" in params:
            z = z + params["b"]
        return z

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        return get_activation(self.activation)(self.pre_output(params, x)), state


@register_layer
@dataclasses.dataclass(frozen=True)
class TokenOutputLayer(RnnOutputLayer):
    """Per-timestep softmax over a vocabulary with INTEGER labels
    (``sparse_mcxent``: labels are (batch, time) class ids, no one-hot
    array ever exists) and a loss that never holds the sequence's logits:
    ``compute_score`` runs the time axis in blocks of ``time_block`` steps
    (``lossfunctions.blocked_sparse_mcxent``), so that at 8192 steps over
    20,480 classes the float32 logits, their softmax and gradient are one
    block's. In a training step the loss and its gradients come out of ONE
    loop over the blocks (a rule of its own, ``jax.custom_vjp``: three
    products a block); what it keeps for the backward pass are the
    gradients of the features, ``W`` and ``b`` and a cross-entropy a token,
    which the backward pass scales. ``score`` runs one product a block.
    The loss has no forward-mode rule (``jax.jvp`` / ``jacfwd`` /
    ``hessian`` of it raise). ``apply`` / ``output`` still return the whole
    softmax.

    A head TIED to the embedding names, in ``tied_to``, the vertex whose
    matrix ``W`` (n_out, n_in), an ``EmbeddingSequenceLayer``'s table, it
    reads transposed. It owns no ``W`` then (``init`` draws none), and the
    graph hands it that vertex's leaf (``nn/graph.py::run_vertices`` through
    ``tied_params``): ONE leaf that the embedding gathers from and the
    blocked loss multiplies by, its gradient the sum of both uses, one
    optimizer state, one cast to the compute type. Counted at trace time:
    ``head.tied``. Empty (the default), the layer is what it was. A
    ``ComputationGraph``'s field: a stack has no vertex to name."""

    has_bias: bool = False
    loss: str = "sparse_mcxent"
    time_block: int = 1024
    tied_to: str = ""          # the vertex whose W this head reads; "": its own

    sparse_labels = True       # labels are ids: (batch, time)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        if not self.tied_to:
            return super().init(rng, it, dtype)
        return ({"b": jnp.full((self.n_out,), self.bias_init, dtype)}
                if self.has_bias else {}), {}

    def tied_params(self, params, other):
        """This layer's parameters with the matrix of the vertex it is tied
        to in ``W``'s place: ``other["W"]`` (n_out, n_in) transposed, inside
        the step (XLA folds it into the loss's products; no transposed copy
        outlives a step)."""
        from deeplearning4j_tpu.perf.compile_watch import bump_active

        bump_active("head.tied")
        return {**params, "W": other["W"].T}

    def pre_output(self, params, x):
        # the score needs the features and the weights, not the logits
        out = {"x": x, "W": params["W"]}
        if "b" in params:
            out["b"] = params["b"]
        return out

    def _logits(self, preout):
        z = preout["x"] @ preout["W"]
        return z + preout["b"] if "b" in preout else z

    def output_activations(self, preout):
        return get_activation(self.activation)(self._logits(preout))

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        return self.output_activations(self.pre_output(params, x)), state

    def _blocked(self):
        return (str(self.loss).lower() == "sparse_mcxent"
                and str(self.activation).lower() == "softmax"
                and self.loss_weights is None)

    def compute_score(self, labels, preout, mask=None):
        if not self._blocked():
            return super().compute_score(labels, self._logits(preout), mask)
        from deeplearning4j_tpu.nn.lossfunctions import blocked_sparse_mcxent
        return blocked_sparse_mcxent(preout["x"], preout["W"],
                                     preout.get("b"), labels, mask,
                                     self.time_block)

    def compute_score_array(self, labels, preout, mask=None):
        return super().compute_score_array(labels, self._logits(preout), mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class ExitWeightedTokenOutputLayer(TokenOutputLayer):
    """The output layer of a looped model that may leave after any pass:
    its input is a ``LoopVertex``'s stacked output (passes, batch, time,
    n_in), every pass is scored by the one head ``W`` and gated by
    ``sigmoid(x_r Wg + bg)``, and the training loss is the expectation of
    the passes' cross-entropies under the gates' exit distribution less
    ``entropy_weight`` times that distribution's entropy
    (``lossfunctions.blocked_exit_weighted_mcxent``: one pass's block of
    ``time_block`` steps of logits and their gradient at a time, over
    ``TokenOutputLayer``'s block loop: in a training step one loop over the
    (pass, block) pairs yields the loss and the gradients of the passes'
    states and the head, and keeps them and the passes' cross-entropy a
    token, through which the gates learn, for the backward pass; no
    forward-mode rule). INTEGER labels (batch, time), as
    ``TokenOutputLayer``. ``apply`` / ``output`` give the LAST pass's
    softmax: leaving early at inference is not built."""

    entropy_weight: float = 0.0

    def output_type(self, it: InputType) -> InputType:
        if not it.passes:
            raise ValueError("expects a LoopVertex's stacked passes, the "
                             "input brings one state")
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        k_head, k_gate = jax.random.split(rng)
        params, state = super().init(k_head, it, dtype)
        n_in = self.n_in or it.size
        params["Wg"] = init_weights(k_gate, (n_in, 1), n_in, 1,
                                    self.weight_init, self.dist, dtype)
        params["bg"] = jnp.zeros((1,), dtype)
        return params, state

    def pre_output(self, params, x):
        return {**super().pre_output(params, x), "Wg": params["Wg"],
                "bg": params["bg"]}

    def _logits(self, preout):
        return super()._logits({**preout, "x": preout["x"][-1]})

    def compute_score(self, labels, preout, mask=None):
        if not self._blocked():
            raise ValueError("the exit-weighted loss is sparse_mcxent over a "
                             "softmax without class weights")
        from deeplearning4j_tpu.nn.lossfunctions import (
            blocked_exit_weighted_mcxent)
        return blocked_exit_weighted_mcxent(
            preout["x"], preout["W"], preout.get("b"), preout["Wg"],
            preout["bg"], labels, mask, self.time_block, self.entropy_weight)


@register_layer
@dataclasses.dataclass(frozen=True)
class MultiTokenOutputLayer(TokenOutputLayer):
    """The output layer of a model with multi-token prediction modules
    (DeepSeek-V3, arXiv:2412.19437 section 2.2): its input is a
    ``StackStatesVertex``'s (1 + D, batch, time, n_in), the trunk's final
    state first and then the D modules', every state is scored by the ONE
    head ``W``, module k against the labels k steps further on with the
    positions that have none masked, and the training loss is the trunk's
    plus ``module_weight`` / D times the modules'
    (``lossfunctions.blocked_multi_token_mcxent``: ``TokenOutputLayer``'s
    block loop over the (state, block) pairs, one loop, the head's gradient
    summed in one carry). ``score`` reports the same sum. INTEGER labels
    (batch, time), as ``TokenOutputLayer``. ``apply`` / ``output`` give the
    trunk's softmax: drafting with the modules at inference is not built."""

    module_weight: float = 0.3

    def output_type(self, it: InputType) -> InputType:
        if not it.passes or it.passes < 2:
            raise ValueError("expects a StackStatesVertex's trunk and module "
                             "states, the input brings one state")
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def _logits(self, preout):
        return super()._logits({**preout, "x": preout["x"][0]})

    def compute_score(self, labels, preout, mask=None):
        if not self._blocked():
            raise ValueError("the multi-token loss is sparse_mcxent over a "
                             "softmax without class weights")
        from deeplearning4j_tpu.nn.lossfunctions import (
            blocked_multi_token_mcxent)
        return blocked_multi_token_mcxent(
            preout["x"], preout["W"], preout.get("b"), labels, mask,
            self.time_block, self.module_weight)


@register_layer
@dataclasses.dataclass(frozen=True)
class MultiTokenCombine(BaseLayer):
    """The input of a multi-token prediction module (DeepSeek-V3,
    arXiv:2412.19437 eq. 21): from a ``StackStatesVertex``'s (2, batch,
    time, d), the state h of the depth before and the embedding e of the
    token one step further on (a ``TimeShiftVertex`` of the model's
    embedding), ``[RMSNorm_h(h) ; RMSNorm_e(e)] W`` with ``W`` (2 d, d) and
    no bias (leaves ``h_norm``, ``e_norm``, ``W``; scope ``mtp.combine``).
    Counted at trace time, once a module: ``mtp.modules``."""

    n_in: Optional[int] = None
    n_out: int = 0              # model width; inferred from the input when 0
    eps: float = 1e-6
    weight_init: str = "xavier_fan_in"

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return ("W",)

    def output_type(self, it: InputType) -> InputType:
        if it.passes != 2:
            raise ValueError("expects a StackStatesVertex's two states (the "
                             "state before, the next token's embedding)")
        return InputType.recurrent(self.n_out or self.n_in or it.size,
                                   it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        n_out = self.n_out or d
        return {"h_norm": jnp.ones((d,), dtype),
                "e_norm": jnp.ones((d,), dtype),
                "W": init_weights(rng, (2 * d, n_out), 2 * d, n_out,
                                  self.weight_init, self.dist, dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.conf.normalization import rms_norm
        from deeplearning4j_tpu.perf.compile_watch import bump_active

        bump_active("mtp.modules")
        x = dropout_input(x, self.dropout, train, rng)
        with jax.named_scope("mtp.combine"):
            out = jnp.concatenate(
                [rms_norm(x[0], params["h_norm"], self.eps),
                 rms_norm(x[1], params["e_norm"], self.eps)], -1) \
                @ params["W"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        return out, state


@register_layer
@dataclasses.dataclass(frozen=True)
class EmbeddingLayer(BaseLayer):
    """Index -> vector lookup (reference nn/conf/layers/EmbeddingLayer.java +
    nn/layers/feedforward/embedding/EmbeddingLayer.java): input is a column of
    integer indices (batch,) or (batch, 1). On TPU this is a gather — a single
    HLO — rather than the reference's row-view copy."""

    n_in: Optional[int] = None  # vocab size
    n_out: int = 0
    has_bias: bool = True
    activation: str = "identity"

    def input_kind(self):
        return "ff"

    def output_type(self, it: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.flat_size()
        params = {"W": init_weights(rng, (n_in, self.n_out), n_in, self.n_out,
                                    self.weight_init, self.dist, dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        z = params["W"][idx]
        if "b" in params:
            z = z + params["b"]
        return get_activation(self.activation)(z), state


@register_layer
@dataclasses.dataclass(frozen=True)
class EmbeddingSequenceLayer(BaseLayer):
    """Sequence of indices (batch, time) -> (batch, time, n_out). Not in the
    0.9.x reference (added upstream later as EmbeddingSequenceLayer); included
    because char-RNN/NLP models on TPU want gathers, not one-hot matmuls."""

    n_in: Optional[int] = None  # vocab size
    n_out: int = 0

    # features are (batch, time) integer ids, not (batch, time, channels)
    takes_index_sequence = True

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.size
        return {"W": init_weights(rng, (n_in, self.n_out), n_in, self.n_out,
                                  self.weight_init, self.dist, dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if x.ndim == 3 and x.shape[-1] == 1:
            x = x[..., 0]
        return params["W"][x.astype(jnp.int32)], state


@register_layer
@dataclasses.dataclass(frozen=True)
class LastTimeStep(BaseRecurrentLayer):
    """Wrap a recurrent layer and emit only the last (unmasked) timestep as a
    feed-forward activation (reference nn/graph/vertex/impl/rnn/
    LastTimeStepVertex.java as a layer wrapper)."""

    layer: Optional[LSTM] = None

    def regularizable(self):
        # params ARE the wrapped layer's params (init delegates directly)
        return self.layer.regularizable() if self.layer is not None else ()

    def output_type(self, it: InputType) -> InputType:
        inner = self.layer.output_type(it)
        return InputType.feed_forward(inner.size)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        return self.layer.init(rng, it, dtype)

    def init_carry(self, batch, dtype=jnp.float32):
        return self.layer.init_carry(batch, dtype)

    def apply_seq(self, params, carry, x, *, train=False, rng=None, mask=None):
        out, new_carry = self.layer.apply_seq(params, carry, x, train=train,
                                              rng=rng, mask=mask)
        if mask is None:
            last = out[:, -1, :]
        else:
            lengths = jnp.sum(mask, axis=1).astype(jnp.int32)
            idx = jnp.maximum(lengths - 1, 0)
            last = jnp.take_along_axis(out, idx[:, None, None], axis=1)[:, 0, :]
        return last, new_carry

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        out, _ = self.apply_seq(params, self.init_carry(x.shape[0], x.dtype), x,
                                train=train, rng=rng, mask=mask)
        return out, state

    def with_n_in(self, n_in):
        if self.layer is not None and getattr(self.layer, "n_in", 0) in (None, 0):
            return dataclasses.replace(self, layer=self.layer.with_n_in(n_in))
        return self


@register_layer
@dataclasses.dataclass(frozen=True)
class SimpleRnn(BaseRecurrentLayer):
    """Vanilla recurrent layer h_t = act(x_t W + h_{t-1} U + b)
    (reference nn/conf/layers — Keras SimpleRNN import target). Input
    projection is hoisted into one MXU matmul over all timesteps, like LSTM."""

    n_in: Optional[int] = None
    n_out: int = 0
    activation: str = "tanh"

    def regularizable(self):
        return ("W", "U")

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.size
        k1, k2 = jax.random.split(rng)
        return {
            "W": init_weights(k1, (n_in, self.n_out), n_in, self.n_out,
                              self.weight_init, self.dist, dtype),
            "U": init_weights(k2, (self.n_out, self.n_out), self.n_out,
                              self.n_out, self.weight_init, self.dist, dtype),
            "b": jnp.zeros((self.n_out,), dtype),
        }, {}

    def init_carry(self, batch, dtype=jnp.float32):
        return {"h": jnp.zeros((batch, self.n_out), dtype)}

    def apply_seq(self, params, carry, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        b, t, _ = x.shape
        act = get_activation(self.activation)
        xw = (x.reshape(b * t, -1) @ params["W"] + params["b"]).reshape(b, t, -1)
        xw_t = jnp.swapaxes(xw, 0, 1)
        m_t = None if mask is None else jnp.swapaxes(mask, 0, 1)
        U = params["U"]

        def step(c, inp):
            xw_i, m_i = inp if m_t is not None else (inp, None)
            h_prev = c["h"]
            h = act(xw_i + h_prev @ U)
            if m_i is not None:
                keep = m_i[:, None]
                h = keep * h + (1.0 - keep) * h_prev
                out = keep * h
            else:
                out = h
            return {"h": h}, out

        xs = xw_t if m_t is None else (xw_t, m_t)
        new_carry, outs = lax.scan(step, carry, xs)
        return jnp.swapaxes(outs, 0, 1), new_carry


@register_layer
@dataclasses.dataclass(frozen=True)
class GRU(BaseRecurrentLayer):
    """Gated recurrent unit (Keras GRU import target; gate order z, r, h).

    ``reset_after=False`` (classic): hh = act(xWh + (r*h)Uh + bh).
    ``reset_after=True`` (CuDNN-compatible Keras 2.x default): separate
    input/recurrent biases, hh = act(xWh + bh + r*(hUh + bhr)); params then
    carry "br" with the recurrent half."""

    n_in: Optional[int] = None
    n_out: int = 0
    activation: str = "tanh"
    gate_activation: str = "sigmoid"
    reset_after: bool = False

    def regularizable(self):
        return ("W", "U")

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.size
        n = self.n_out
        k1, k2 = jax.random.split(rng)
        params = {
            "W": init_weights(k1, (n_in, 3 * n), n_in, n, self.weight_init,
                              self.dist, dtype),
            "U": init_weights(k2, (n, 3 * n), n, n, self.weight_init,
                              self.dist, dtype),
            "b": jnp.zeros((3 * n,), dtype),
        }
        if self.reset_after:
            params["br"] = jnp.zeros((3 * n,), dtype)
        return params, {}

    def init_carry(self, batch, dtype=jnp.float32):
        return {"h": jnp.zeros((batch, self.n_out), dtype)}

    def apply_seq(self, params, carry, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        b, t, _ = x.shape
        n = self.n_out
        act = get_activation(self.activation)
        gate = get_activation(self.gate_activation)
        xw = (x.reshape(b * t, -1) @ params["W"] + params["b"]).reshape(b, t, -1)
        xw_t = jnp.swapaxes(xw, 0, 1)
        m_t = None if mask is None else jnp.swapaxes(mask, 0, 1)
        U = params["U"]
        br = params.get("br")

        def step(c, inp):
            xw_i, m_i = inp if m_t is not None else (inp, None)
            h_prev = c["h"]
            if self.reset_after:
                hu = h_prev @ U + br
                z = gate(xw_i[:, :n] + hu[:, :n])
                r = gate(xw_i[:, n:2 * n] + hu[:, n:2 * n])
                hh = act(xw_i[:, 2 * n:] + r * hu[:, 2 * n:])
            else:
                z = gate(xw_i[:, :n] + h_prev @ U[:, :n])
                r = gate(xw_i[:, n:2 * n] + h_prev @ U[:, n:2 * n])
                hh = act(xw_i[:, 2 * n:] + (r * h_prev) @ U[:, 2 * n:])
            h = z * h_prev + (1.0 - z) * hh
            if m_i is not None:
                keep = m_i[:, None]
                h = keep * h + (1.0 - keep) * h_prev
                out = keep * h
            else:
                out = h
            return {"h": h}, out

        xs = xw_t if m_t is None else (xw_t, m_t)
        new_carry, outs = lax.scan(step, carry, xs)
        return jnp.swapaxes(outs, 0, 1), new_carry

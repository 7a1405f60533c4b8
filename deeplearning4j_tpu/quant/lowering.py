"""Quantized int8 lowering of dense/conv layers for serving graphs.

The scheme is the standard integer-arithmetic PTQ recipe (Jacob et al.
2018) on the BN-folded serving graph:

- **Weights**: per-output-channel symmetric int8 — one f32 scale per
  output channel, ``Wq = clip(round(W / s_w), -127, 127)``. Per-channel
  scales cost O(C) bytes and recover most of the accuracy per-tensor
  weight quant loses on conv stacks.
- **Activations**: per-tensor symmetric int8 with a STATIC scale from
  calibration (quant/calibrate.py) — ``xq = clip(round(x / s_in))`` is the
  single quantize each layer performs on its input.
- **Compute**: the matmul/conv runs on int8 operands with **int32
  accumulation** (``preferred_element_type=jnp.int32`` — the MXU int8
  path), then ONE requantize back to f32 per layer:
  ``y = acc_int32 * (s_in * s_w[c]) + b``, bias and activation in f32.
- **Boundaries**: layers with no int8 lowering (LSTM/VAE/attention/custom
  vertices, anything not an exact Dense/Conv/Conv1D/Output layer) run
  untouched in fp32 — the dequantize above IS the explicit boundary op, so
  a mixed CNN→LSTM graph quantizes its convs and hands the recurrent stack
  ordinary f32 activations.

Everything inside ``apply`` is pure jnp — the quantized predict jits into
one XLA program with zero host syncs (trace_check-gated in
tests/test_quant.py) and shares the serving bucket ladder/warmup unchanged.

Quantized layers are registered layer configs: the model-zip config JSON
round-trips them, ``coefficients.npz`` carries the int8 weights and f32
scales, and the calibration record rides along as ``quantization.json``
(utils/serialization) — restore rebuilds the exact quantized predict.

Zero-points are identically 0 (symmetric grid): conv SAME-padding and
zero inputs stay exact and the int8 kernels need no zero-point cross
terms; the calibration record still carries ``zero_point: 0`` per layer so
the wire format is explicit about it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf.convolutional import (
    Convolution1DLayer, ConvolutionLayer, _pair,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BaseOutputLayer, DenseLayer, Layer, OutputLayer, register_layer,
)
from deeplearning4j_tpu.quant.observers import QMAX
from deeplearning4j_tpu.quant.pack import (packed_width, quantize_int4,
                                           unpack_nibbles)

__all__ = [
    "QuantizedDenseLayer", "QuantizedConvolutionLayer",
    "QuantizedConvolution1DLayer", "QuantizedOutputLayer",
    "quantize", "quantizable_kind", "quantize_weights",
    "quantize_weights_int4", "is_quantized",
    "quantized_layers", "input_quant_scale", "param_bytes",
]


# ------------------------------------------------------------- primitives
def quantize_activation(x, act_scale: float):
    """f32 → int8 on the symmetric grid with a static calibrated scale.
    This is the ONE quantize a layer performs (its dequantize is the f32
    rescale of the int32 accumulator)."""
    inv = jnp.float32(1.0 / act_scale)
    return jnp.clip(jnp.round(x * inv), -QMAX, QMAX).astype(jnp.int8)


def quantize_weights(w) -> Tuple[np.ndarray, np.ndarray]:
    """Per-output-channel symmetric int8 weight quantization (host-side,
    at ``quantize()`` time). Channel = the LAST axis for every supported
    layout ((n_in, n_out) dense, HWIO conv2d, WIO conv1d). Returns
    ``(Wq int8, scale f32[n_out])``."""
    w = np.asarray(w)
    amax = np.max(np.abs(w.reshape(-1, w.shape[-1])), axis=0)
    scale = np.maximum(amax, np.float32(1e-12)) / np.float32(QMAX)
    scale = np.ascontiguousarray(scale, dtype=w.dtype)
    q = np.clip(np.rint(w / scale), -QMAX, QMAX).astype(np.int8)
    return q, scale


def _requantize(acc_i32, act_scale: float, w_scale):
    """int32 accumulator → f32, the single per-layer dequantize:
    ``acc * (s_in * s_w[c])`` broadcast over the channel axis."""
    return acc_i32 * (jnp.float32(act_scale) * w_scale)


def quantize_weights_int4(w) -> Tuple[np.ndarray, np.ndarray]:
    """Per-output-channel symmetric int4 weight quantization: the
    :func:`quantize_weights` recipe one rung down, through
    ``quant.pack.quantize_int4``'s shared grid (codes in [-7, 7], two per
    byte). Rows of the packed matrix are OUTPUT CHANNELS — ``Wq`` is
    ``(n_out, ceil(fan_in/2))`` int8, unpacked in-kernel next to the
    int32 matmul. Returns ``(Wq packed, scale f32[n_out])``."""
    w = np.asarray(w)
    w2d = np.ascontiguousarray(w.reshape(-1, w.shape[-1]).T)  # (n_out, fan)
    packed, scales, _ = quantize_int4(w2d)
    return packed, scales.astype(np.float32)


def _dense_int4_acc(xq, wq_packed, n_in: int):
    """int8 activations × packed int4 weights → int32, unpack fused
    against the dot: the Pallas ``int4_dot`` kernel when selection
    resolves to it (2-D activations), the jnp in-program unpack (which
    XLA fuses into the dot operand) otherwise."""
    from deeplearning4j_tpu.perf import pallas as _pk
    from deeplearning4j_tpu.perf.pallas import adc as _pk_adc
    if _pk.take("int4_dot", xq.ndim == 2 and _pk_adc.int4_supported(
            xq.shape[0], *wq_packed.shape)):
        return _pk_adc.int4_matmul(xq, wq_packed, n_in)
    w8 = unpack_nibbles(wq_packed, n_in)                  # (n_out, n_in)
    return lax.dot_general(xq, w8, (((xq.ndim - 1,), (1,)), ((), ())),
                           preferred_element_type=jnp.int32)


def _conv_weight_int8(wq_packed, spatial, c_in: int, n_out: int):
    """Unpack packed int4 conv weights in-program back to the conv's
    native layout (HWIO / WIO): rows are output channels, fan-in keeps
    the (spatial..., c_in) order the lowering flattened."""
    fan = int(np.prod(spatial)) * c_in
    w8 = unpack_nibbles(wq_packed, fan)
    w8 = w8.reshape((n_out,) + tuple(spatial) + (c_in,))
    return jnp.moveaxis(w8, 0, -1)                        # (*spatial, ci, co)


# ---------------------------------------------------------------- layers
@register_layer
@dataclasses.dataclass(frozen=True)
class QuantizedDenseLayer(Layer):
    """int8 lowering of DenseLayer: y = act(deq(xq @int32 Wq) + b)."""

    n_in: Optional[int] = None
    n_out: int = 0
    has_bias: bool = True
    activation: str = "identity"
    act_scale: float = 1.0
    weight_bits: int = 8

    def input_kind(self):
        return "ff"

    def output_type(self, input_type):
        if input_type.kind == "rnn":  # broadcasts over time, like Dense
            return InputType.recurrent(self.n_out,
                                       input_type.timeseries_length)
        return InputType.feed_forward(self.n_out)

    def init(self, rng, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.flat_size()
        wq_shape = ((self.n_out, packed_width(n_in))
                    if self.weight_bits == 4 else (n_in, self.n_out))
        params = {"Wq": jnp.zeros(wq_shape, jnp.int8),
                  "w_scale": jnp.ones((self.n_out,), jnp.float32)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        xq = quantize_activation(x, self.act_scale)
        if self.weight_bits == 4:
            acc = _dense_int4_acc(xq, params["Wq"], self.n_in)
        else:
            acc = lax.dot_general(xq, params["Wq"],
                                  (((x.ndim - 1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        z = _requantize(acc, self.act_scale, params["w_scale"])
        if self.has_bias:
            z = z + params["b"]
        return get_activation(self.activation)(z), state


@register_layer
@dataclasses.dataclass(frozen=True)
class QuantizedConvolutionLayer(Layer):
    """int8 lowering of ConvolutionLayer (NHWC / HWIO, int32 accumulate).
    Symmetric quantization keeps SAME-padding zeros exact."""

    n_in: Optional[int] = None
    n_out: int = 0
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    dilation: Tuple[int, int] = (1, 1)
    has_bias: bool = True
    activation: str = "identity"
    act_scale: float = 1.0
    weight_bits: int = 8

    def input_kind(self):
        return "cnn"

    def output_type(self, it: InputType) -> InputType:
        return ConvolutionLayer.output_type(self, it)

    def with_n_in(self, n_in):
        return self  # channels come from the source conv at quantize time

    def init(self, rng, it: InputType, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        c_in = self.n_in or it.channels
        wq_shape = ((self.n_out, packed_width(kh * kw * c_in))
                    if self.weight_bits == 4
                    else (kh, kw, c_in, self.n_out))
        params = {"Wq": jnp.zeros(wq_shape, jnp.int8),
                  "w_scale": jnp.ones((self.n_out,), jnp.float32)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def _pad_cfg(self):
        if self.convolution_mode == "same":
            return "SAME"
        ph, pw = _pair(self.padding)
        return ((ph, ph), (pw, pw))

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        xq = quantize_activation(x, self.act_scale)
        if self.weight_bits == 4:
            w = _conv_weight_int8(params["Wq"], _pair(self.kernel_size),
                                  self.n_in, self.n_out)
        else:
            w = params["Wq"]
        acc = lax.conv_general_dilated(
            xq, w,
            window_strides=_pair(self.stride),
            padding=self._pad_cfg(),
            rhs_dilation=_pair(self.dilation),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.int32)
        z = _requantize(acc, self.act_scale, params["w_scale"])
        if self.has_bias:
            z = z + params["b"]
        return get_activation(self.activation)(z), state


@register_layer
@dataclasses.dataclass(frozen=True)
class QuantizedConvolution1DLayer(Layer):
    """int8 lowering of Convolution1DLayer (NWC / WIO, int32 accumulate)."""

    n_in: Optional[int] = None
    n_out: int = 0
    kernel_size: int = 3
    stride: int = 1
    padding: int = 0
    convolution_mode: str = "truncate"
    dilation: int = 1
    has_bias: bool = True
    activation: str = "identity"
    act_scale: float = 1.0
    weight_bits: int = 8

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def output_type(self, it: InputType) -> InputType:
        return Convolution1DLayer.output_type(self, it)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        c_in = self.n_in or it.size
        wq_shape = ((self.n_out, packed_width(self.kernel_size * c_in))
                    if self.weight_bits == 4
                    else (self.kernel_size, c_in, self.n_out))
        params = {"Wq": jnp.zeros(wq_shape, jnp.int8),
                  "w_scale": jnp.ones((self.n_out,), jnp.float32)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        xq = quantize_activation(x, self.act_scale)
        if self.weight_bits == 4:
            w = _conv_weight_int8(params["Wq"], (self.kernel_size,),
                                  self.n_in, self.n_out)
        else:
            w = params["Wq"]
        pad = ("SAME" if self.convolution_mode == "same"
               else ((self.padding, self.padding),))
        acc = lax.conv_general_dilated(
            xq, w, window_strides=(self.stride,), padding=pad,
            rhs_dilation=(self.dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"),
            preferred_element_type=jnp.int32)
        z = _requantize(acc, self.act_scale, params["w_scale"])
        if self.has_bias:
            z = z + params["b"]
        return get_activation(self.activation)(z), state


@register_layer
@dataclasses.dataclass(frozen=True)
class QuantizedOutputLayer(BaseOutputLayer):
    """int8 lowering of OutputLayer: the logits matmul runs int8×int8 →
    int32, everything loss/softmax-shaped stays f32 (inherited from
    BaseOutputLayer), so ``score_dataset``/``evaluate`` work unchanged on a
    quantized net."""

    n_in: Optional[int] = None
    n_out: int = 0
    has_bias: bool = True
    activation: str = "softmax"
    act_scale: float = 1.0
    weight_bits: int = 8

    def input_kind(self):
        return "ff"

    def output_type(self, input_type):
        return InputType.feed_forward(self.n_out)

    def regularizable(self):
        return ()

    def init(self, rng, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.flat_size()
        wq_shape = ((self.n_out, packed_width(n_in))
                    if self.weight_bits == 4 else (n_in, self.n_out))
        params = {"Wq": jnp.zeros(wq_shape, jnp.int8),
                  "w_scale": jnp.ones((self.n_out,), jnp.float32)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), jnp.float32)
        return params, {}

    def pre_output(self, params, x):
        xq = quantize_activation(x, self.act_scale)
        if self.weight_bits == 4:
            acc = _dense_int4_acc(xq, params["Wq"], self.n_in)
        else:
            acc = lax.dot_general(xq, params["Wq"],
                                  (((x.ndim - 1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        z = _requantize(acc, self.act_scale, params["w_scale"])
        if self.has_bias:
            z = z + params["b"]
        return z

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return (get_activation(self.activation)(self.pre_output(params, x)),
                state)


_QUANTIZED_TYPES = (QuantizedDenseLayer, QuantizedConvolutionLayer,
                    QuantizedConvolution1DLayer, QuantizedOutputLayer)


# ------------------------------------------------------------- rewriters
def quantizable_kind(layer) -> Optional[str]:
    """Which int8 lowering (if any) applies to a layer. EXACT type match:
    subclasses (CenterLoss, SeparableConv, fused blocks, ...) carry extra
    semantics the int8 kernels do not reproduce and fall back to fp32."""
    t = type(layer)
    if t is DenseLayer:
        return "dense"
    if t is ConvolutionLayer:
        return "conv"
    if t is Convolution1DLayer:
        return "conv1d"
    if t is OutputLayer:
        return "output"
    return None


def _lower_layer(layer, kind: str, params: dict, act_scale: float,
                 weight_bits: int = 8):
    """One layer's integer lowering: quantized config + quantized params
    (per-channel int8 weights, or packed per-channel int4 when
    ``weight_bits == 4``)."""
    w = np.asarray(params["W"])
    if weight_bits == 4:
        wq, ws = quantize_weights_int4(w)
    else:
        wq, ws = quantize_weights(w)
    has_bias = "b" in params
    s = float(act_scale)
    wb = int(weight_bits)
    if kind == "dense":
        ql = QuantizedDenseLayer(
            name=layer.name, n_in=w.shape[0], n_out=w.shape[1],
            has_bias=has_bias, activation=layer.activation, act_scale=s,
            weight_bits=wb)
    elif kind == "conv":
        ql = QuantizedConvolutionLayer(
            name=layer.name, n_in=w.shape[2], n_out=w.shape[3],
            kernel_size=layer.kernel_size, stride=layer.stride,
            padding=layer.padding,
            convolution_mode=layer.convolution_mode,
            dilation=layer.dilation, has_bias=has_bias,
            activation=layer.activation, act_scale=s, weight_bits=wb)
    elif kind == "conv1d":
        ql = QuantizedConvolution1DLayer(
            name=layer.name, n_in=w.shape[1], n_out=w.shape[2],
            kernel_size=layer.kernel_size, stride=layer.stride,
            padding=layer.padding,
            convolution_mode=layer.convolution_mode,
            dilation=layer.dilation, has_bias=has_bias,
            activation=layer.activation, act_scale=s, weight_bits=wb)
    elif kind == "output":
        ql = QuantizedOutputLayer(
            name=layer.name, n_in=w.shape[0], n_out=w.shape[1],
            has_bias=has_bias, activation=layer.activation,
            loss=layer.loss, loss_weights=layer.loss_weights, act_scale=s,
            weight_bits=wb)
    else:
        raise KeyError(kind)
    qp = {"Wq": jnp.asarray(wq), "w_scale": jnp.asarray(ws)}
    if has_bias:
        qp["b"] = jnp.asarray(np.asarray(params["b"]))
    return ql, qp


def _copy_tree(tree):
    import jax
    return jax.tree_util.tree_map(jnp.array, tree)


def _quantize_multilayer(net, record, weight_bits: int = 8):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    new_layers, new_params, new_state = [], [], []
    for i, l in enumerate(net.conf.layers):
        kind = quantizable_kind(l)
        key = f"layer{i}"
        if kind is None or key not in record.ranges:
            new_layers.append(l)
            new_params.append(_copy_tree(net.params[i]))
            new_state.append(_copy_tree(net.state[i]))
            continue
        ql, qp = _lower_layer(l, kind, net.params[i], record.scale(key),
                              weight_bits)
        new_layers.append(ql)
        new_params.append(qp)
        new_state.append({})
    # dtype pinned to f32: the networks' low-precision compute cast
    # (tree_map astype in _forward) must never touch the int8 buffers
    conf = dataclasses.replace(net.conf, layers=tuple(new_layers),
                               dtype="float32")
    out = MultiLayerNetwork(conf)
    out.params, out.state = new_params, new_state
    out.opt_state = [tx.init(p) for tx, p in zip(out._txs, new_params)]
    out._rng = net._rng
    out.iteration, out.epoch = net.iteration, net.epoch
    return out


def _quantize_graph(net, record, weight_bits: int = 8):
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    vertices = dict(net.conf.vertices)
    params = {n: _copy_tree(net.params[n]) for n in net.params}
    state = {n: _copy_tree(net.state[n]) for n in net.state}
    for name in net.order:
        obj, ins = net.vertices[name]
        if not isinstance(obj, Layer):
            continue
        kind = quantizable_kind(obj)
        if kind is None or name not in record.ranges:
            continue
        ql, qp = _lower_layer(obj, kind, net.params[name],
                              record.scale(name), weight_bits)
        vertices[name] = (ql, ins)
        params[name] = qp
        state[name] = {}
    conf = dataclasses.replace(net.conf, vertices=vertices, dtype="float32")
    out = ComputationGraph(conf)
    out.params = {n: params[n] for n in out.order}
    out.state = {n: state[n] for n in out.order}
    out.opt_state = {n: out._txs[n].init(out.params[n])
                     for n in out._layer_names}
    out._rng = net._rng
    out.iteration, out.epoch = net.iteration, net.epoch
    return out


def quantize(net, calibration, fold: bool = True, weight_bits: int = 8):
    """Lower a network to its integer serving graph using a calibration
    record (quant/calibrate.py).

    ``weight_bits=4`` swaps the weight grid for packed per-output-channel
    int4 (quant/pack.py — two codes per byte resident, unpacked in-kernel
    next to the int32 matmul; activations stay int8): ~8x smaller weights
    than f32. Judge the result with the SAME
    ``quant.gates.assert_accuracy_within`` gate as int8 — int4 gives up
    more accuracy, so gate before serving.

    Folds BN first (``fold=True``, the default — quantization targets the
    serving graph; pass ``fold=False`` for a net calibrated with
    ``calibrate(..., fold=False)``), verifies the record's structural
    signature matches, then rewrites every quantizable layer to its
    ``Quantized*`` lowering with per-channel int8 weights and the
    calibrated activation scale; everything else (LSTM/VAE/custom vertices,
    subclassed layers) is left in fp32 with the dequant/quant boundary
    built into the quantized layers themselves.

    Returns a NEW network of the same class. The result is a serving
    artifact: ``fit()`` on it is meaningless (weights are frozen int8).
    The calibration record is attached as ``_quant_calibration`` and rides
    along in the model zip (utils/serialization)."""
    from deeplearning4j_tpu.quant.calibrate import (CalibrationRecord,
                                                    signature_of)

    if not isinstance(calibration, CalibrationRecord):
        raise TypeError(
            "quantize() needs a CalibrationRecord (run quant.calibrate "
            f"over a representative batch stream); got "
            f"{type(calibration).__name__}")
    if int(weight_bits) not in (4, 8):
        raise ValueError(f"weight_bits must be 4 or 8; got {weight_bits}")
    if net.params is None:
        net.init()
    if fold:
        from deeplearning4j_tpu.perf.fusion import fold_bn
        net = fold_bn(net)
    sig = signature_of(net)
    if sig != calibration.signature:
        raise ValueError(
            "calibration record does not match this network's quantizable "
            f"layers (record: {list(calibration.signature)}; network: "
            f"{list(sig)}) — calibrate the same (folded) graph you "
            "quantize")
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if isinstance(net, MultiLayerNetwork):
        out = _quantize_multilayer(net, calibration, int(weight_bits))
    else:
        out = _quantize_graph(net, calibration, int(weight_bits))
    out._quant_calibration = calibration
    from deeplearning4j_tpu.obs.registry import get_registry
    reg = get_registry()
    reg.gauge(
        "quant_model_bytes", unit="bytes",
        help="parameter bytes of the most recently quantized serving "
             "model (int8 weights + f32 scales/biases)",
    ).set(param_bytes(out))
    return out


# -------------------------------------------------------------- inspection
def quantized_layers(net):
    """(slot_key, layer) for every Quantized* layer of a network."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if isinstance(net, MultiLayerNetwork):
        return [(f"layer{i}", l) for i, l in enumerate(net.layers)
                if isinstance(l, _QUANTIZED_TYPES)]
    out = []
    for name in getattr(net, "order", ()):
        obj = net.vertices[name][0]
        if isinstance(obj, _QUANTIZED_TYPES):
            out.append((name, obj))
    return out


def is_quantized(net) -> bool:
    return bool(quantized_layers(net))


def input_quant_scale(net) -> Optional[float]:
    """The activation scale of the quantized layer that consumes the
    NETWORK INPUT — the scale an int8 wire payload is encoded in (serving
    accepts ``dtype: "int8"`` tensors only when this is defined). None when
    the first layer is not quantized."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if isinstance(net, MultiLayerNetwork):
        if net.layers and isinstance(net.layers[0], _QUANTIZED_TYPES):
            return float(net.layers[0].act_scale)
        return None
    inputs = set(getattr(net.conf, "network_inputs", ()))
    for name in getattr(net, "order", ()):
        obj, ins = net.vertices[name]
        if isinstance(obj, _QUANTIZED_TYPES) and set(ins) <= inputs:
            return float(obj.act_scale)
    return None


def param_bytes(net) -> int:
    """Total parameter bytes of a network (the ``quant_model_bytes`` /
    bench ``model_bytes`` metric: int8 weights shrink this ~4x)."""
    import jax
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(net.params))

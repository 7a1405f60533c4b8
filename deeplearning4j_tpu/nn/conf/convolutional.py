"""Convolutional layer family.

Parity surface: reference ``nn/conf/layers/``: ConvolutionLayer,
Convolution1DLayer, SeparableConvolution2D, SubsamplingLayer,
Subsampling1DLayer, Upsampling1D/2D, ZeroPadding1D/2DLayer,
and impls in ``nn/layers/convolution/`` (ConvolutionLayer.java:334 im2col path,
CudnnConvolutionHelper — deeplearning4j-cuda/.../CudnnConvolutionHelper.java:54).

TPU-native design: **NHWC layout with HWIO kernels**, lowered through
``lax.conv_general_dilated`` — XLA:TPU tiles these directly onto the MXU;
there is no im2col fallback and no cuDNN-style helper indirection (the
double-implementation pattern of the reference dissolves: one traced op,
one compiler). Pooling uses ``lax.reduce_window`` (VPU-friendly windowed
reductions).

Convolution mode semantics follow the reference's ``ConvolutionMode``:
``truncate`` (= VALID, silently dropping trailing pixels) and ``same``
(= SAME padding); explicit padding tuples correspond to ``Strict`` with
manual pads.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.initializers import init_weights
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BaseLayer, Layer, register_layer, dropout_input,
)


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _conv_out(size, k, s, pad, mode, dilation=1):
    eff_k = (k - 1) * dilation + 1  # effective kernel under dilation
    if mode == "same":
        return -(-size // s)
    out = (size + 2 * pad - eff_k) // s + 1
    if out <= 0:
        raise ValueError(
            f"Invalid convolution/pooling geometry: input size {size}, kernel {k} "
            f"(effective {eff_k}), stride {s}, padding {pad} gives non-positive "
            f"output size {out}. Use convolution_mode='same' or adjust kernel/padding.")
    return out


def _padding_cfg(mode: str, padding):
    """lax padding argument (per spatial dim) for the given convolution mode."""
    if mode == "same":
        return "SAME"
    ph, pw = _pair(padding)
    return ((ph, ph), (pw, pw))


def _s2d_eligible(x, kernel_size, stride, dilation, mode):
    """See ConvolutionLayer._space_to_depth_eligible."""
    return (mode == "same"
            and _pair(kernel_size) == (7, 7)
            and _pair(stride) == (2, 2)
            and _pair(dilation) == (1, 1)
            and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0
            and x.shape[3] <= 4)


def conv2d_forward(x, w, kernel_size, stride, padding, mode, dilation=(1, 1)):
    """The one 2-D convolution lowering, shared by ConvolutionLayer and the
    fused conv→BN→act block so both take the identical compute path
    (including the ImageNet-stem space-to-depth rewrite)."""
    if _s2d_eligible(x, kernel_size, stride, dilation, mode):
        return ConvolutionLayer._space_to_depth_conv(x, w)
    return lax.conv_general_dilated(
        x, w,
        window_strides=_pair(stride),
        padding=_padding_cfg(mode, padding),
        rhs_dilation=_pair(dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@register_layer
@dataclasses.dataclass(frozen=True)
class ConvolutionLayer(BaseLayer):
    """2-D convolution (reference nn/conf/layers/ConvolutionLayer.java +
    nn/layers/convolution/ConvolutionLayer.java; cuDNN fast path
    CudnnConvolutionHelper.java:54). NHWC in, HWIO kernel, NHWC out."""

    n_in: Optional[int] = None  # input channels (inferred)
    n_out: int = 0              # output channels
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"  # truncate|same
    dilation: Tuple[int, int] = (1, 1)
    has_bias: bool = True
    activation: str = "identity"

    def input_kind(self):
        return "cnn"

    def output_type(self, it: InputType) -> InputType:
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        dh, dw = _pair(self.dilation)
        h = _conv_out(it.height, kh, sh, ph, self.convolution_mode, dh)
        w = _conv_out(it.width, kw, sw, pw, self.convolution_mode, dw)
        return InputType.convolutional(h, w, self.n_out)

    def with_n_in(self, n_in):
        # n_in is channels: set from the input type's channel count in init
        return self

    def init(self, rng, it: InputType, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        c_in = self.n_in or it.channels
        fan_in = c_in * kh * kw
        fan_out = self.n_out * kh * kw
        params = {"W": init_weights(rng, (kh, kw, c_in, self.n_out), fan_in,
                                    fan_out, self.weight_init, self.dist, dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def _space_to_depth_eligible(self, x):
        """The ImageNet-stem case (7x7 stride-2 SAME on <=4 channels) maps
        poorly onto the MXU: <8 input channels waste the systolic array's
        input tiling. Rewriting via 2x2 space-to-depth turns it into an
        exact-math 4x4 stride-1 conv over 4x the channels."""
        return (self.convolution_mode == "same"
                and _pair(self.kernel_size) == (7, 7)
                and _pair(self.stride) == (2, 2)
                and _pair(self.dilation) == (1, 1)
                and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0
                and x.shape[3] <= 4)

    @staticmethod
    def _space_to_depth_conv(x, w):
        """Exact rewrite of conv(x, w[7,7,C,F], stride 2, SAME) for even H/W.

        SAME here pads (2,3); in 2x2-block space that is pad (1,2) with the
        7x7 kernel zero-extended to 8x8 (index 7 multiplies only padding).
        Derivation: output o(i) reads input t = 2i-2..2i+4; with t = 2j+p
        (j the block index, p the parity) the kernel tap is k = 2(j-i)+p+2,
        so blocks j-i in -1..2 and W'[a, p] = w[2a+p] (a = j-i+1, w[7] = 0).
        """
        b, h, wd, c = x.shape
        f = w.shape[-1]
        x2 = x.reshape(b, h // 2, 2, wd // 2, 2, c)
        x2 = x2.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, wd // 2, 4 * c)
        w8 = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
        w2 = w8.reshape(4, 2, 4, 2, c, f)
        w2 = w2.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, f)
        return lax.conv_general_dilated(
            x2, w2, window_strides=(1, 1), padding=((1, 2), (1, 2)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        z = conv2d_forward(x, params["W"], self.kernel_size, self.stride,
                           self.padding, self.convolution_mode, self.dilation)
        if self.has_bias:
            z = z + params["b"]
        return get_activation(self.activation)(z), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SeparableConvolution2D(BaseLayer):
    """Depthwise-separable conv (reference nn/conf/layers/SeparableConvolution2D.java).
    Depthwise (feature_group_count=C) then 1x1 pointwise — both MXU-lowered."""

    n_in: Optional[int] = None
    n_out: int = 0
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    depth_multiplier: int = 1
    has_bias: bool = True
    activation: str = "identity"

    def input_kind(self):
        return "cnn"

    def regularizable(self):
        return ("W_dw", "W_pw")

    def output_type(self, it: InputType) -> InputType:
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        h = _conv_out(it.height, kh, sh, ph, self.convolution_mode)
        w = _conv_out(it.width, kw, sw, pw, self.convolution_mode)
        return InputType.convolutional(h, w, self.n_out)

    def with_n_in(self, n_in):
        return self

    def init(self, rng, it: InputType, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        c_in = self.n_in or it.channels
        k1, k2 = jax.random.split(rng)
        dw_out = c_in * self.depth_multiplier
        params = {
            "W_dw": init_weights(k1, (kh, kw, 1, dw_out), kh * kw, kh * kw * self.depth_multiplier,
                                 self.weight_init, self.dist, dtype),
            "W_pw": init_weights(k2, (1, 1, dw_out, self.n_out), dw_out, self.n_out,
                                 self.weight_init, self.dist, dtype),
        }
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        c_in = x.shape[-1]
        z = lax.conv_general_dilated(
            x, params["W_dw"],
            window_strides=_pair(self.stride),
            padding=_padding_cfg(self.convolution_mode, self.padding),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c_in,
        )
        z = lax.conv_general_dilated(
            z, params["W_pw"], window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.has_bias:
            z = z + params["b"]
        return get_activation(self.activation)(z), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SubsamplingLayer(Layer):
    """Spatial pooling (reference nn/conf/layers/SubsamplingLayer.java +
    nn/layers/convolution/subsampling/; cuDNN path CudnnSubsamplingHelper.java).
    Modes: max | avg | pnorm, via lax.reduce_window."""

    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    pooling_type: str = "max"  # max|avg|pnorm
    pnorm: int = 2

    def input_kind(self):
        return "cnn"

    def output_type(self, it: InputType) -> InputType:
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        h = _conv_out(it.height, kh, sh, ph, self.convolution_mode)
        w = _conv_out(it.width, kw, sw, pw, self.convolution_mode)
        return InputType.convolutional(h, w, it.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        if self.convolution_mode == "same":
            pad = "SAME"
        else:
            ph, pw = _pair(self.padding)
            pad = ((0, 0), (ph, ph), (pw, pw), (0, 0))
        window = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        pt = self.pooling_type.lower()
        if pt == "max":
            out = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pad)
        elif pt == "avg":
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, pad)
            out = s / (kh * kw)
        elif pt == "pnorm":
            p = float(self.pnorm)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides, pad)
            out = s ** (1.0 / p)
        else:
            raise ValueError(f"Unknown pooling type '{self.pooling_type}'")
        return out, state


@register_layer
@dataclasses.dataclass(frozen=True)
class Upsampling2D(Layer):
    """Nearest-neighbour upsampling (reference nn/conf/layers/Upsampling2D.java)."""

    size: Tuple[int, int] = (2, 2)

    def input_kind(self):
        return "cnn"

    def output_type(self, it: InputType) -> InputType:
        sh, sw = _pair(self.size)
        return InputType.convolutional(it.height * sh, it.width * sw, it.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        sh, sw = _pair(self.size)
        out = jnp.repeat(jnp.repeat(x, sh, axis=1), sw, axis=2)
        return out, state


@register_layer
@dataclasses.dataclass(frozen=True)
class ZeroPaddingLayer(Layer):
    """Spatial zero padding (reference nn/conf/layers/ZeroPaddingLayer.java).
    ``padding`` = (top, bottom, left, right) or (h, w) symmetric."""

    padding: Tuple[int, ...] = (1, 1)

    def input_kind(self):
        return "cnn"

    def _pads(self):
        p = self.padding
        if len(p) == 2:
            return (p[0], p[0], p[1], p[1])
        return tuple(int(v) for v in p)

    def output_type(self, it: InputType) -> InputType:
        t, b, l, r = self._pads()
        return InputType.convolutional(it.height + t + b, it.width + l + r, it.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        t, b, l, r = self._pads()
        return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0))), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Convolution1DLayer(BaseLayer):
    """1-D convolution over (batch, time, channels) (reference
    nn/conf/layers/Convolution1DLayer.java). Lowered as NWC/WIO conv."""

    n_in: Optional[int] = None
    n_out: int = 0
    kernel_size: int = 3
    stride: int = 1
    padding: int = 0
    convolution_mode: str = "truncate"
    dilation: int = 1
    has_bias: bool = True
    activation: str = "identity"

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def output_type(self, it: InputType) -> InputType:
        t = it.timeseries_length
        if t is not None:
            t = _conv_out(t, self.kernel_size, self.stride, self.padding,
                          self.convolution_mode, self.dilation)
        return InputType.recurrent(self.n_out, t)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        c_in = self.n_in or it.size
        fan_in = c_in * self.kernel_size
        fan_out = self.n_out * self.kernel_size
        params = {"W": init_weights(rng, (self.kernel_size, c_in, self.n_out),
                                    fan_in, fan_out, self.weight_init, self.dist, dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        pad = ("SAME" if self.convolution_mode == "same"
               else ((self.padding, self.padding),))
        z = lax.conv_general_dilated(
            x, params["W"], window_strides=(self.stride,), padding=pad,
            rhs_dilation=(self.dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"))
        if self.has_bias:
            z = z + params["b"]
        return get_activation(self.activation)(z), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Subsampling1DLayer(Layer):
    """1-D pooling over (batch, time, channels) (reference
    nn/conf/layers/Subsampling1DLayer.java)."""

    kernel_size: int = 2
    stride: int = 2
    padding: int = 0
    pooling_type: str = "max"
    pnorm: int = 2
    convolution_mode: str = "truncate"

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def output_type(self, it: InputType) -> InputType:
        t = it.timeseries_length
        if t is not None:
            t = _conv_out(t, self.kernel_size, self.stride, self.padding,
                          self.convolution_mode)
        return InputType.recurrent(it.size, t)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if self.convolution_mode == "same":
            pad = "SAME"
        else:
            pad = ((0, 0), (self.padding, self.padding), (0, 0))
        window = (1, self.kernel_size, 1)
        strides = (1, self.stride, 1)
        pt = self.pooling_type.lower()
        if pt == "max":
            out = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pad)
        elif pt == "avg":
            out = lax.reduce_window(x, 0.0, lax.add, window, strides, pad) / self.kernel_size
        elif pt == "pnorm":
            p = float(self.pnorm)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides, pad)
            out = s ** (1.0 / p)
        else:
            raise ValueError(f"Unknown pooling type '{self.pooling_type}'")
        return out, state


@register_layer
@dataclasses.dataclass(frozen=True)
class Upsampling1D(Layer):
    """(reference nn/conf/layers/Upsampling1D.java)"""

    size: int = 2

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def output_type(self, it: InputType) -> InputType:
        t = it.timeseries_length
        return InputType.recurrent(it.size, None if t is None else t * self.size)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return jnp.repeat(x, self.size, axis=1), state


@register_layer
@dataclasses.dataclass(frozen=True)
class Cropping2D(Layer):
    """Spatial cropping, the inverse of ZeroPaddingLayer (Keras Cropping2D
    import target). ``cropping`` = (top, bottom, left, right) or (h, w)."""

    cropping: Tuple[int, ...] = (0, 0)

    def input_kind(self):
        return "cnn"

    def _crops(self):
        c = self.cropping
        if len(c) == 2:
            return (c[0], c[0], c[1], c[1])
        return tuple(int(v) for v in c)

    def output_type(self, it: InputType) -> InputType:
        t, b, l, r = self._crops()
        h, w = it.height - t - b, it.width - l - r
        if h <= 0 or w <= 0:
            raise ValueError(f"Cropping {self.cropping} consumes the whole "
                             f"{it.height}x{it.width} input")
        return InputType.convolutional(h, w, it.channels)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        t, b, l, r = self._crops()
        return x[:, t:x.shape[1] - b, l:x.shape[2] - r, :], state


@register_layer
@dataclasses.dataclass(frozen=True)
class Cropping1D(Layer):
    """Temporal cropping (Keras Cropping1D). ``cropping`` = (left, right)."""

    cropping: Tuple[int, int] = (0, 0)

    def input_kind(self):
        return "cnn1d"

    def output_type(self, it: InputType) -> InputType:
        l, r = self.cropping
        t = None if it.timeseries_length is None else it.timeseries_length - l - r
        if t is not None and t <= 0:
            raise ValueError(f"Cropping {self.cropping} consumes the whole "
                             f"length-{it.timeseries_length} sequence")
        return InputType.recurrent(it.size, t)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        l, r = self.cropping
        return x[:, l:x.shape[1] - r, :], state


@register_layer
@dataclasses.dataclass(frozen=True)
class ZeroPadding1DLayer(Layer):
    """Temporal zero padding (Keras ZeroPadding1D; reference
    ZERO_PADDING_1D in KerasLayerConfiguration)."""

    padding: Tuple[int, int] = (1, 1)

    def input_kind(self):
        return "cnn1d"

    def output_type(self, it: InputType) -> InputType:
        l, r = self.padding
        t = None if it.timeseries_length is None else it.timeseries_length + l + r
        return InputType.recurrent(it.size, t)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        l, r = self.padding
        return jnp.pad(x, ((0, 0), (l, r), (0, 0))), state


# ---------------------------------------------------------------------------
# Fused Conv→BN→Activation(→residual-add) block (perf/fusion.py rewriter
# target). Motivation (tools/PROFILE_r5.md): train-mode BN costs ~4.7 full
# activation-set HBM crossings beyond the conv floor — BN backward alone
# re-reads saved activation-sized buffers. The fused block's custom VJP
# saves ONLY the conv output z plus O(C) per-batch mean/inv-std and
# recomputes x-hat (and the activation pre-image) in the backward, the
# In-Place Activated BatchNorm recipe (Bulò et al., CVPR 2018) expressed
# through jax.custom_vjp instead of a hand-written kernel.

def _bn_train_stats(z):
    """Per-channel (mean, var) with the same numerics as
    BatchNormalization.apply: single-pass f32-accumulated for low-precision
    compute, exact centered two-pass otherwise."""
    axes = tuple(range(z.ndim - 1))
    if z.dtype in (jnp.bfloat16, jnp.float16):
        zf = z.astype(jnp.float32)
        n = zf.size // zf.shape[-1]
        mean = jnp.sum(zf, axis=axes) / n
        var = jnp.maximum(jnp.sum(zf * zf, axis=axes) / n - mean * mean, 0.0)
    else:
        mean = jnp.mean(z, axis=axes)
        var = jnp.var(z, axis=axes)
    return mean, var


def _bn_act_fwd_math(act_name, eps, z, gamma, beta, res):
    # Pallas kernel family "bn_act": one VMEM-resident stats+normalize+
    # activation kernel when selection resolves to it, this jnp reference
    # otherwise — take() records kernel.pallas_/.xla_ either way
    from deeplearning4j_tpu.perf import pallas as _pk
    from deeplearning4j_tpu.perf.pallas import bn as _pk_bn
    if _pk.take("bn_act", _pk_bn.supported(z, res is not None)):
        return _pk_bn.bn_act_fwd(act_name, eps, z, gamma, beta, res)
    mean, var = _bn_train_stats(z)
    sdt = var.dtype
    inv = lax.rsqrt(var + jnp.asarray(eps, sdt))
    scale = gamma.astype(sdt) * inv
    shift = beta.astype(sdt) - mean * scale
    pre = z * scale.astype(z.dtype) + shift.astype(z.dtype)
    if res is not None:
        pre = pre + res
    return get_activation(act_name)(pre), mean, var, inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def fused_bn_act_train(act_name, eps, z, gamma, beta, res):
    """Train-mode BN + activation (+ optional residual add) over the conv
    output ``z``, with a memory-efficient VJP: the backward recomputes the
    normalized x-hat from ``z`` plus the saved O(C) (mean, inv-std) instead
    of keeping activation-sized normalize/pre-activation buffers alive.

    Returns ``(out, mean, var)``; the (mean, var) outputs exist ONLY to feed
    the running-stat EMA and are not differentiated (their cotangents are
    ignored — the running buffers are non-trainable state)."""
    out, mean, var, _ = _bn_act_fwd_math(act_name, eps, z, gamma, beta, res)
    return out, mean, var


def _fused_bn_act_fwd(act_name, eps, z, gamma, beta, res):
    out, mean, var, inv = _bn_act_fwd_math(act_name, eps, z, gamma, beta, res)
    # residuals: z (which the conv dW backward saves anyway) + O(C) vectors
    # (+ the residual-add input, itself another block's saved output)
    return (out, mean, var), (z, gamma, beta, res, mean, inv)


def _fused_bn_act_bwd(act_name, eps, saved, cts):
    z, gamma, beta, res, mean, inv = saved
    dout = cts[0]  # mean/var cotangents ignored (EMA-only outputs)
    from deeplearning4j_tpu.perf import pallas as _pk
    from deeplearning4j_tpu.perf.pallas import bn as _pk_bn
    if _pk.take("bn_act_bwd",
                _pk_bn.supported(z, res is not None, backward=True)):
        dz, dgamma, dbeta, dpre = _pk_bn.bn_act_bwd(
            act_name, eps, z, gamma, beta, res, mean, inv, dout)
        dres = None if res is None else dpre.astype(res.dtype)
        return (dz, dgamma, dbeta, dres)
    sdt = mean.dtype
    scale = gamma.astype(sdt) * inv
    shift = beta.astype(sdt) - mean * scale
    pre = z * scale.astype(z.dtype) + shift.astype(z.dtype)
    if res is not None:
        pre = pre + res
    # activation backward through the SAME activation implementation the
    # forward used (recomputed pre-image, no saved buffer)
    _, act_vjp = jax.vjp(get_activation(act_name), pre)
    dpre = act_vjp(dout)[0]
    axes = tuple(range(z.ndim - 1))
    n = z.size // z.shape[-1]
    zf = z.astype(sdt)
    xhat = (zf - mean) * inv
    dpre32 = dpre.astype(sdt)
    dgamma = jnp.sum(dpre32 * xhat, axis=axes)
    dbeta = jnp.sum(dpre32, axis=axes)
    # full train-mode BN backward (gradients flow through the batch stats):
    # dz = gamma*inv * (dpre - mean(dpre) - xhat * mean(dpre * xhat))
    dz = (scale * (dpre32 - dbeta / n - xhat * (dgamma / n))).astype(z.dtype)
    dres = None if res is None else dpre.astype(res.dtype)
    return (dz, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype), dres)


fused_bn_act_train.defvjp(_fused_bn_act_fwd, _fused_bn_act_bwd)


@register_layer
@dataclasses.dataclass(frozen=True)
class FusedSeparableConvBNActivation(BaseLayer):
    """SeparableConvolution2D → train-mode BatchNorm → activation as ONE
    layer sharing :func:`fused_bn_act_train`'s memory-efficient VJP (the
    BN backward recomputes x-hat from the saved pointwise-conv output plus
    O(C) mean/inv-std). Produced by ``perf.fusion.fuse`` from matched
    SeparableConvolution2D → BatchNormalization → ActivationLayer chains
    (the PR 4 leftover); math identical to the unfused stack within fp
    tolerance. Non-residual only — depthwise stems don't sit on residual
    adds in the reference topologies."""

    n_in: Optional[int] = None
    n_out: int = 0
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    depth_multiplier: int = 1
    has_bias: bool = False
    activation: str = "relu"
    decay: float = 0.9
    eps: float = 1e-5
    gamma: float = 1.0
    beta: float = 0.0

    def input_kind(self):
        return "cnn"

    def regularizable(self):
        return ("W_dw", "W_pw")

    def output_type(self, it: InputType) -> InputType:
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        h = _conv_out(it.height, kh, sh, ph, self.convolution_mode)
        w = _conv_out(it.width, kw, sw, pw, self.convolution_mode)
        return InputType.convolutional(h, w, self.n_out)

    def with_n_in(self, n_in):
        return self

    def init(self, rng, it: InputType, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        c_in = self.n_in or it.channels
        k1, k2 = jax.random.split(rng)
        dw_out = c_in * self.depth_multiplier
        params = {
            "W_dw": init_weights(k1, (kh, kw, 1, dw_out), kh * kw,
                                 kh * kw * self.depth_multiplier,
                                 self.weight_init, self.dist, dtype),
            "W_pw": init_weights(k2, (1, 1, dw_out, self.n_out), dw_out,
                                 self.n_out, self.weight_init, self.dist,
                                 dtype),
        }
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        params["gamma"] = jnp.full((self.n_out,), self.gamma, dtype)
        params["beta"] = jnp.full((self.n_out,), self.beta, dtype)
        state = {"mean": jnp.zeros((self.n_out,), dtype),
                 "var": jnp.ones((self.n_out,), dtype)}
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.perf.compile_watch import bump_active
        bump_active("fusion.fused_block")
        x = dropout_input(x, self.dropout, train, rng)
        z = lax.conv_general_dilated(
            x, params["W_dw"], window_strides=_pair(self.stride),
            padding=_padding_cfg(self.convolution_mode, self.padding),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=x.shape[-1])
        z = lax.conv_general_dilated(
            z, params["W_pw"], window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.has_bias:
            z = z + params["b"]
        return _fused_bn_tail(self, params, state, z, train)


@register_layer
@dataclasses.dataclass(frozen=True)
class FusedConv1DBNActivation(BaseLayer):
    """Convolution1DLayer → train-mode BatchNorm → activation as ONE layer
    over (batch, time, channels), sharing :func:`fused_bn_act_train`'s
    memory-efficient VJP (the normalize axes are 'all but last', so the
    same custom VJP covers NWC exactly as it covers NHWC). Produced by
    ``perf.fusion.fuse`` from matched Convolution1DLayer →
    BatchNormalization → ActivationLayer chains (the PR 4 leftover)."""

    n_in: Optional[int] = None
    n_out: int = 0
    kernel_size: int = 3
    stride: int = 1
    padding: int = 0
    convolution_mode: str = "truncate"
    dilation: int = 1
    has_bias: bool = False
    activation: str = "relu"
    decay: float = 0.9
    eps: float = 1e-5
    gamma: float = 1.0
    beta: float = 0.0

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def output_type(self, it: InputType) -> InputType:
        t = it.timeseries_length
        if t is not None:
            t = _conv_out(t, self.kernel_size, self.stride, self.padding,
                          self.convolution_mode, self.dilation)
        return InputType.recurrent(self.n_out, t)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        c_in = self.n_in or it.size
        fan_in = c_in * self.kernel_size
        fan_out = self.n_out * self.kernel_size
        params = {"W": init_weights(rng, (self.kernel_size, c_in, self.n_out),
                                    fan_in, fan_out, self.weight_init,
                                    self.dist, dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        params["gamma"] = jnp.full((self.n_out,), self.gamma, dtype)
        params["beta"] = jnp.full((self.n_out,), self.beta, dtype)
        state = {"mean": jnp.zeros((self.n_out,), dtype),
                 "var": jnp.ones((self.n_out,), dtype)}
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.perf.compile_watch import bump_active
        bump_active("fusion.fused_block")
        x = dropout_input(x, self.dropout, train, rng)
        pad = ("SAME" if self.convolution_mode == "same"
               else ((self.padding, self.padding),))
        z = lax.conv_general_dilated(
            x, params["W"], window_strides=(self.stride,), padding=pad,
            rhs_dilation=(self.dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"))
        if self.has_bias:
            z = z + params["b"]
        return _fused_bn_tail(self, params, state, z, train)


def _fused_bn_tail(layer, params, state, z, train):
    """Shared BN(+activation) tail of the fused blocks: train mode goes
    through the memory-efficient custom VJP, eval mode through the folded
    running-stat scale/shift — identical to FusedConvBNActivation.apply's
    non-residual path."""
    gamma, beta = params["gamma"], params["beta"]
    if train:
        out, mean, var = fused_bn_act_train(layer.activation, layer.eps,
                                            z, gamma, beta, None)
        new_state = {
            "mean": layer.decay * state["mean"] + (1.0 - layer.decay) * mean,
            "var": layer.decay * state["var"] + (1.0 - layer.decay) * var,
        }
        return out, new_state
    mean, var = state["mean"], state["var"]
    sdt = var.dtype
    inv = lax.rsqrt(var + jnp.asarray(layer.eps, sdt))
    scale = gamma.astype(sdt) * inv
    shift = beta.astype(sdt) - mean * scale
    pre = z * scale.astype(z.dtype) + shift.astype(z.dtype)
    return get_activation(layer.activation)(pre), state


@register_layer
@dataclasses.dataclass(frozen=True)
class FusedConvBNActivation(BaseLayer):
    """Conv → train-mode BatchNorm → activation (optionally + residual add
    before the activation) as ONE layer whose BN backward recomputes x-hat
    instead of re-reading activation-sized saves (see fused_bn_act_train).

    Produced by ``perf.fusion.fuse`` from matched ConvolutionLayer →
    BatchNormalization → ActivationLayer(→ ElementWiseVertex add) patterns;
    usable directly as well. ``residual=True`` (ComputationGraph only) adds
    a second vertex input to the pre-activation. Math is identical to the
    unfused stack within fp tolerance; parameter layout is the union of the
    conv's (W[, b]) and the BN's (gamma, beta) with the BN running stats in
    the layer state."""

    n_in: Optional[int] = None
    n_out: int = 0
    kernel_size: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    dilation: Tuple[int, int] = (1, 1)
    has_bias: bool = False
    activation: str = "relu"
    # BatchNormalization fields (gamma/beta are the INIT values)
    decay: float = 0.9
    eps: float = 1e-5
    gamma: float = 1.0
    beta: float = 0.0
    residual: bool = False

    @property
    def extra_inputs(self):
        # the residual-add operand is the vertex's second input
        return ("res",) if self.residual else ()

    def input_kind(self):
        return "cnn"

    def output_type(self, it: InputType) -> InputType:
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        dh, dw = _pair(self.dilation)
        h = _conv_out(it.height, kh, sh, ph, self.convolution_mode, dh)
        w = _conv_out(it.width, kw, sw, pw, self.convolution_mode, dw)
        return InputType.convolutional(h, w, self.n_out)

    def with_n_in(self, n_in):
        return self  # n_in is channels, set from the input type in init

    def init(self, rng, it: InputType, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        c_in = self.n_in or it.channels
        fan_in = c_in * kh * kw
        fan_out = self.n_out * kh * kw
        params = {"W": init_weights(rng, (kh, kw, c_in, self.n_out), fan_in,
                                    fan_out, self.weight_init, self.dist,
                                    dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        params["gamma"] = jnp.full((self.n_out,), self.gamma, dtype)
        params["beta"] = jnp.full((self.n_out,), self.beta, dtype)
        state = {"mean": jnp.zeros((self.n_out,), dtype),
                 "var": jnp.ones((self.n_out,), dtype)}
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None,
              res=None):
        from deeplearning4j_tpu.perf.compile_watch import bump_active
        bump_active("fusion.fused_block")
        x = dropout_input(x, self.dropout, train, rng)
        z = conv2d_forward(x, params["W"], self.kernel_size, self.stride,
                           self.padding, self.convolution_mode, self.dilation)
        if self.has_bias:
            z = z + params["b"]
        gamma, beta = params["gamma"], params["beta"]
        if train:
            out, mean, var = fused_bn_act_train(self.activation, self.eps,
                                                z, gamma, beta, res)
            new_state = {
                "mean": self.decay * state["mean"] + (1.0 - self.decay) * mean,
                "var": self.decay * state["var"] + (1.0 - self.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            sdt = var.dtype
            inv = lax.rsqrt(var + jnp.asarray(self.eps, sdt))
            scale = gamma.astype(sdt) * inv
            shift = beta.astype(sdt) - mean * scale
            pre = z * scale.astype(z.dtype) + shift.astype(z.dtype)
            if res is not None:
                pre = pre + res
            out = get_activation(self.activation)(pre)
            new_state = state
        return out, new_state

"""Normalization layers: BatchNormalization, LocalResponseNormalization,
RMSNorm, LayerNorm.

Parity surface: reference ``nn/conf/layers/BatchNormalization.java`` +
``nn/layers/normalization/BatchNormalization.java:57`` (helper hook; cuDNN
path CudnnBatchNormalizationHelper.java) and
``LocalResponseNormalization.java`` (+ CudnnLocalResponseNormalizationHelper).

TPU-native: one fused traced expression; the running-stat buffers live in the
layer *state* pytree (non-trainable), updated functionally inside the jitted
train step — no mutable INDArray views.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import BaseLayer, Layer, register_layer


@register_layer
@dataclasses.dataclass(frozen=True)
class BatchNormalization(BaseLayer):
    """Batch norm over the channel/feature axis (last axis in both the
    (batch, features) and NHWC layouts). Reference defaults: decay=0.9,
    eps=1e-5, lockGammaBeta=false (BatchNormalization.java conf)."""

    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    gamma: float = 1.0  # fixed value when locked
    beta: float = 0.0

    def regularizable(self):
        return ()

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n = it.channels if it.kind == "cnn" else it.flat_size()
        params = {}
        if not self.lock_gamma_beta:
            params = {"gamma": jnp.full((n,), self.gamma, dtype),
                      "beta": jnp.full((n,), self.beta, dtype)}
        state = {"mean": jnp.zeros((n,), dtype), "var": jnp.ones((n,), dtype)}
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        axes = tuple(range(x.ndim - 1))  # all but channel/feature axis
        # batch statistics and the running buffers stay float32 regardless of
        # the compute dtype (bf16 stats lose precision); the normalization
        # itself runs in x's dtype so bf16 activations stay bf16 end to end
        if train:
            if x.dtype in (jnp.bfloat16, jnp.float16):
                # low-precision compute: single-pass f32-accumulated stats.
                # sum and sum-of-squares fuse into ONE traversal of x
                # (jnp.var's mean((x-mean)^2) needs a second, dependent
                # pass — 2x the HBM reads on conv-sized activations,
                # measured ~8% of the ResNet50 train step). E[x^2]-E[x]^2
                # cancellation only bites when mean^2/var >~ 2^24; but bf16
                # DATA already loses the signal at mean^2/var ~ 2^16, so in
                # every regime where the input itself is meaningful the
                # single-pass f32 accumulator is as accurate as two-pass.
                xf = x.astype(jnp.float32)
                n = xf.size // xf.shape[-1]
                mean32 = jnp.sum(xf, axis=axes) / n
                var32 = jnp.maximum(
                    jnp.sum(xf * xf, axis=axes) / n - mean32 * mean32, 0.0)
            else:
                # full-precision compute (incl. f64 gradcheck): the exact
                # centered two-pass form — immune to cancellation for
                # channels whose mean dwarfs their std (e.g. BN applied
                # directly to unnormalized raw features)
                mean32 = jnp.mean(x, axis=axes)
                var32 = jnp.var(x, axis=axes)
            new_state = {
                "mean": self.decay * state["mean"] + (1.0 - self.decay) * mean32,
                "var": self.decay * state["var"] + (1.0 - self.decay) * var32,
            }
        else:
            mean32, var32 = state["mean"], state["var"]
            new_state = state
        # fold to one fused multiply-add per element: out = x*scale + shift.
        # scale/shift are per-channel (C,) vectors computed in f32, so the
        # per-element work is minimal and fuses into the producing conv.
        sdt = var32.dtype  # f32 for low-precision compute, f64 for gradcheck
        inv = lax.rsqrt(var32 + jnp.asarray(self.eps, sdt))
        if self.lock_gamma_beta:
            g, b = jnp.asarray(self.gamma, sdt), jnp.asarray(self.beta, sdt)
        else:
            g, b = params["gamma"].astype(sdt), params["beta"].astype(sdt)
        scale = g * inv
        shift = b - mean32 * scale
        out = x * scale.astype(x.dtype) + shift.astype(x.dtype)
        return out, new_state


@register_layer
@dataclasses.dataclass(frozen=True)
class LocalResponseNormalization(Layer):
    """Cross-channel LRN (reference nn/conf/layers/LocalResponseNormalization.java;
    defaults k=2, n=5, alpha=1e-4, beta=0.75 as in the reference conf)."""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def input_kind(self):
        return "cnn"

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # sum of squares over a centred window of 2*(n//2)+1 channels — the
        # reference loops i=1..n/2 on both sides of the centre
        # (LocalResponseNormalization.java halfN), so even n covers n+1 channels
        half = self.n // 2
        win = 2 * half + 1
        sq = x * x
        summed = lax.reduce_window(
            sq, 0.0, lax.add,
            window_dimensions=(1, 1, 1, win),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), (0, 0), (0, 0), (half, half)),
        )
        return x / jnp.power(self.k + self.alpha * summed, self.beta), state


def rms_norm(x, g, eps: float):
    """x / sqrt(mean(x^2) + eps) * g over the last axis; the mean square is
    taken in float32 whatever x's type, the result is x's type."""
    x32 = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (x32 * scale * g.astype(jnp.float32)).astype(x.dtype)


@register_layer
@dataclasses.dataclass(frozen=True)
class RMSNorm(BaseLayer):
    """Root-mean-square normalisation over the feature axis with a learned
    scale and no bias or mean subtraction (Zhang & Sennrich 2019): the
    pre-norm of today's decoder blocks. Shape-preserving. The scale is the
    leaf ``g``, started at one; with ``zero_centered`` (the Qwen3-Next
    family's norm) it is ``1 + w`` with the leaf ``w`` started at zero."""

    eps: float = 1e-5
    zero_centered: bool = False

    def regularizable(self):
        return ()

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n = it.channels if it.kind == "cnn" else it.flat_size()
        if self.zero_centered:
            return {"w": jnp.zeros((n,), dtype)}, {}
        return {"g": jnp.ones((n,), dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        g = 1.0 + params["w"] if self.zero_centered else params["g"]
        return rms_norm(x, g, self.eps), state


def layer_norm(x, weight, bias, eps: float):
    """(x - mean) / sqrt(var + eps) * weight + bias over the last axis; mean
    and variance are taken in float32 whatever x's type (the variance of the
    centred values, not a difference of means), the result is x's type."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, -1, keepdims=True)
    scale = lax.rsqrt(jnp.mean(jnp.square(centred), -1, keepdims=True) + eps)
    return (centred * scale * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


@register_layer
@dataclasses.dataclass(frozen=True)
class LayerNorm(BaseLayer):
    """Layer normalisation over the feature axis (Ba et al. 2016) with a
    learned weight and bias (leaves ``weight`` started at one, ``bias`` at
    zero): the pre-norm of the decoders that kept the mean subtraction.
    Shape-preserving; statistics in float32 (``layer_norm``)."""

    eps: float = 1e-5

    def regularizable(self):
        return ()

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n = it.channels if it.kind == "cnn" else it.flat_size()
        return {"weight": jnp.ones((n,), dtype),
                "bias": jnp.zeros((n,), dtype)}, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return layer_norm(x, params["weight"], params["bias"],
                          self.eps), state

"""The short causal convolution over time, and the token mixer built on it.

``causal_depthwise_conv`` is the one copy of the depthwise taps: the
delta-rule layers of ``linear_attention.py`` run it over q, k and v (four
taps, a SiLU behind it), ``GatedShortConv`` over a gated input (three taps,
no activation anywhere).

``GatedShortConv`` is the token mixer of the LFM2 line (``Lfm2ShortConv`` in
the public code): with d the model width and L = ``taps``,

    [B | C | u] = x W_in            (W_in d x 3d, split in that order)
    z   = B * u
    c_t = sum_{j=0..L-1} w[j] * z_{t-(L-1)+j}    a channel, zeros before 0
    out = (C * c) W_out             (W_out d x d)

two gates around a depthwise convolution, no bias anywhere. The three
pieces lie under the scopes ``sconv.in_proj``, ``sconv.gate_conv``
(both gates and the taps: everything between the two products) and
``sconv.out_proj``, forward and backward; a layer traced counts
``conv.gated_short`` once (``bump_active``). A features mask zeroes the
output at masked steps: a right-padded batch is exact, since no step reads
a later one."""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (BaseLayer, dropout_input,
                                               register_layer)
from deeplearning4j_tpu.nn.initializers import init_weights
from deeplearning4j_tpu.perf.compile_watch import bump_active


def causal_depthwise_conv(x, w):
    """``x`` (batch, time, channels), ``w`` (taps, channels):
    y_t = sum_j w[j] x_{t - (taps - 1) + j}, zeros before the start."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = padded[:, 0:t] * w[0]
    for j in range(1, taps):
        out = out + padded[:, j:j + t] * w[j]
    return out


@register_layer
@dataclasses.dataclass(frozen=True)
class GatedShortConv(BaseLayer):
    """A gated short convolution as a token mixer (see the module
    docstring). ``n_out`` (the model width) is inferred from the input when
    0; ``taps`` is the convolution's length (the model config's
    ``conv_L_cache``)."""

    n_in: Optional[int] = None
    n_out: int = 0
    taps: int = 3
    weight_init: str = "xavier_fan_in"

    supports_stateful = False

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return ("Win", "w", "Wout")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def output_type(self, it: InputType) -> InputType:
        if self.taps < 1:
            raise ValueError(f"a convolution of {self.taps} taps")
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        width = self._width(it)
        k_in, k_w, k_out = jax.random.split(rng, 3)
        return {
            "Win": init_weights(k_in, (d, 3 * width), d, 3 * width,
                                self.weight_init, self.dist, dtype),
            "w": init_weights(k_w, (self.taps, width), self.taps, 1,
                              self.weight_init, self.dist, dtype),
            "Wout": init_weights(k_out, (width, width), width, width,
                                 self.weight_init, self.dist, dtype),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        bump_active("conv.gated_short")
        width = params["Wout"].shape[0]
        with jax.named_scope("sconv.in_proj"):
            bcu = x @ params["Win"]
        with jax.named_scope("sconv.gate_conv"):
            b, c, u = (bcu[..., i * width:(i + 1) * width] for i in range(3))
            gated = c * causal_depthwise_conv(b * u, params["w"])
        with jax.named_scope("sconv.out_proj"):
            out = gated @ params["Wout"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        return out, state


__all__ = ["GatedShortConv", "causal_depthwise_conv"]

"""A state-space token mixer as a registered layer: Mamba-2's, over the
chunked form of its scan ("Transformers are SSMs", arXiv:2405.21060).

The delta-rule layers of ``linear_attention.py`` run
``S_t = (I - b k k^T) a S_{t-1} + b k v^T``; no choice of b turns that into
a state-space recurrence, which has no key-key term at all. A head of
``Mamba2Mixer`` holds an (N x P) state, N = ``state_size``, P =
``head_dim``, fed by B and read by C, both SHARED by the heads of a group:

    [z | xBC | dt] = u W_in      (d_in, d_in + 2 G N and H columns, in that
                                  order; d_in = H P, G = ``n_groups``)
    xBC = SiLU(conv_K(xBC) + b_conv)     (depthwise, causal, zeros before 0)
    [x | B | C] = xBC                    (d_in, G N and G N columns)
    dt_t,h = softplus(dt_t,h + dt_bias_h),   A_h = -exp(A_log_h)
    S_t = exp(dt_t,h A_h) S_{t-1} + dt_t,h B_t x_t,h^T       (from zero)
    y_t,h = S_t^T C_t + D_h x_t,h
    g = y * SiLU(z) in float32;  g = norm * g / sqrt(mean(g^2) + eps), the
        mean over a group's d_in / G columns: the gate BEFORE the norm
        (``gated_norm``)
    out = g W_out

``chunked_ssd`` never runs the recurrence token by token. Time is cut into
chunks of ``chunk`` steps; with c_r the running sum of dt A inside a chunk
(a head), ``G = C B^T`` is made once a GROUP and masked once a head:

    Y = ((G o L_h) Diag(dt)) X_h + Diag(exp(c)) C S_0,
    L_h[r, i] = exp(c_r - c_i) for i <= r, 0 above the diagonal
    S_L = exp(c_L) S_0 + B^T Diag(dt exp(c_L - c)) X_h

every factor the exponential of a DIFFERENCE that is at most 0, never a
quotient of two exponentials, so the form is exact at any decay. Only the
chunks' entry states are carried, in float32, by one ``lax.scan`` whose body
(a chunk's products) is rematerialised in the backward pass: what is alive
at once is one chunk's (heads, chunk, chunk) factors. There is no triangular
solve. That is the plain ``jax.numpy`` / ``lax`` form, XLA writing the
backward pass, for any shape and backend: the CPU's path, the tests'
reference, and counted ``kernel.xla_ssd_scan`` once a call. Where
``perf.pallas.ssd.supported`` takes the call (a TPU, one group, heads of 64
or 128, N and the chunk multiples of 128, a length the chunk divides) the
same algorithm runs as a forward and a backward Pallas kernel
(``ssd_scan_fwd`` / ``ssd_scan_bwd``, ``kernel.pallas_ssd_scan``) that make a
chunk's factors in VMEM and carry the states in scratch: PERF.md §5-6, PR 47
(in XLA's form the scan was a fifth of the Granite cell's step at 6% of its
roofline, PR 46).

Scopes, forward and backward: ``ssm.in_proj``, ``ssm.conv`` (taps, bias,
SiLU, the softplus of dt), ``ssm.scan`` (the D term with it),
``ssm.gate_norm``, ``ssm.out_proj``; a layer traced counts ``ssm.mamba2``
once. Rematerialised, the layer keeps the output of its one wide product
(``u W_in``) where ``keep_projection`` says so (``remat_keeps``,
``remat_kept_bytes``), and nothing else. A features mask zeroes the output
at masked steps: a right-padded batch is exact, since no step reads a later
one."""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (BaseLayer, dropout_input,
                                               register_layer)
from deeplearning4j_tpu.nn.conf.normalization import rms_norm
from deeplearning4j_tpu.nn.conf.short_conv import causal_depthwise_conv
from deeplearning4j_tpu.nn.initializers import init_weights
from deeplearning4j_tpu.perf import pallas as pk
from deeplearning4j_tpu.perf.compile_watch import bump_active
from deeplearning4j_tpu.perf.pallas import ssd

# the wide projection's output by its ``checkpoint_name``
PROJECTION_KEPT = ("state_space.projection",)


def _ssd_chunk(a_rate, state, xs):
    """One chunk of ``chunked_ssd`` met with the state at its entry.
    ``state`` (B, G, R, N, P) float32, R heads a group; ``xs`` the chunk's
    x (B, L, G, R, P), dt (B, L, G, R), B and C (B, L, G, N) in any float
    type; ``a_rate`` (G, R) = A. Returns the state at its exit and the
    chunk's y (B, L, G, R, P), float32. Decays, sums and the state are
    float32; the four products read their operands in x's type (what the
    chip's default precision rounds a float32 operand to anyway, at half
    the bytes for the (heads, L, L) factors) and accumulate in float32."""
    x, dt, bm, cm = xs
    f32, low = jnp.float32, x.dtype
    bm, cm = bm.astype(low), cm.astype(low)
    length = x.shape[1]
    dt = jnp.moveaxis(dt.astype(f32), 1, -1)         # (B, G, R, L)
    tril = jnp.tril(jnp.ones((length, length), bool))
    # the running sum of dt A, as a product with the triangle of ones at
    # full float32 precision: ``cumsum`` lowers to reduce-windows that
    # carry no ``op_name`` on the chip (3.5 ms a step that nobody owned)
    c = jnp.einsum("bgri,li->bgrl", dt * a_rate[..., None], tril.astype(f32),
                   precision=lax.Precision.HIGHEST)
    scores = jnp.einsum("brgn,bign->bgri", cm, bm,   # C B^T, once a group
                        preferred_element_type=f32)
    decay = jnp.exp(jnp.where(tril, c[..., :, None] - c[..., None, :],
                              -jnp.inf))             # L_h: (B, G, R, L, L)
    mixed = (scores[:, :, None] * decay * dt[..., None, :]).astype(low)
    y = jnp.einsum("bgkri,bigkp->brgkp", mixed, x, preferred_element_type=f32)
    carried = jnp.einsum("brgn,bgknp->brgkp", cm, state.astype(low),
                         preferred_element_type=f32)
    y = y + jnp.moveaxis(jnp.exp(c), -1, 1)[..., None] * carried
    # what each step adds to the state, decayed to the chunk's end
    weight = jnp.moveaxis(dt * jnp.exp(c[..., -1:] - c), -1, 1)
    added = (x.astype(f32) * weight[..., None]).astype(low)
    state = (jnp.exp(c[..., -1])[..., None, None] * state
             + jnp.einsum("bign,bigkp->bgknp", bm, added,
                          preferred_element_type=f32))
    return state, y


def chunked_ssd(x, dt, a_rate, bm, cm, chunk: int = 256):
    """The state-space recurrence from a zero state, chunk-wise:
    ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``, ``y_t = S_t^T C_t``, a
    head. ``x`` (batch, time, heads, P), ``dt`` (batch, time, heads) (after
    its softplus: >= 0), ``a_rate`` (heads,) (A: < 0), ``bm``, ``cm``
    (batch, time, groups, N), heads a multiple of groups, a group's heads
    lying together; any float type (the decays, the sums and the carried
    state are float32, the products' operands x's type). Returns y (batch,
    time, heads, P) in float32. ``time`` need
    not be a multiple of ``chunk`` (steps with dt = 0 and x = 0 are
    appended: they leave the state as it is)."""
    bsz, t, h, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    if h % groups:
        raise ValueError(f"{h} heads are no multiple of {groups} groups")
    if chunk < 1:
        raise ValueError(f"a chunk of {chunk} steps")
    if pk.take("ssd_scan", ssd.supported(x, dt, a_rate, bm, cm, chunk)):
        return ssd.ssd_scan(x, dt, a_rate, bm, cm, chunk)
    per = h // groups
    length = min(chunk, t)
    pad = (-t) % length
    count = (t + pad) // length

    def chunks(a, tail):                 # (B, T, ...) -> (count, B, L, ...)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((bsz, count, length) + tail), 1, 0)

    xs = (chunks(x, (groups, per, p)), chunks(dt, (groups, per)),
          chunks(bm, (groups, n)), chunks(cm, (groups, n)))
    rate = a_rate.astype(jnp.float32).reshape(groups, per)
    step = jax.checkpoint(lambda s, c: _ssd_chunk(rate, s, c))
    s0 = jnp.zeros((bsz, groups, per, n, p), jnp.float32)
    _, y = lax.scan(step, s0, xs)        # y: (count, B, L, G, R, P)
    return jnp.moveaxis(y, 0, 1).reshape(bsz, count * length, h, p)[:, :t]


def step_bias_start(key, heads: int, dtype):
    """``dt_bias`` as the public initialiser draws it: the inverse softplus
    of ``heads`` steps log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, (heads,), dtype)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return dt + jnp.log(-jnp.expm1(-dt))


def gated_norm(y, gate, weight, eps: float):
    """The mixer's norm over the last axis: the gate FIRST, then the norm
    (``norm_before_gate`` false in the public code)."""
    return rms_norm(y * gate, weight, eps)


@register_layer
@dataclasses.dataclass(frozen=True)
class Mamba2Mixer(BaseLayer):
    """Mamba-2's mixer over (batch, time, features) (see the module
    docstring): ``n_heads`` heads of ``head_dim`` over ``n_groups`` shared B
    and C of ``state_size``, a convolution of ``conv_size`` taps (with a
    bias where ``conv_bias``), the scan in chunks of ``chunk``, a gated norm
    at ``eps`` that gates before it normalises (``gated_norm``).
    ``n_out`` (the model width) is inferred from the input when 0.
    ``keep_projection``: whether a rematerialised layer holds ``u W_in``
    (a FIELD, so that a builder keeps as many layers' as its memory
    takes)."""

    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 4
    head_dim: int = 64
    state_size: int = 128
    n_groups: int = 1
    conv_size: int = 4
    conv_bias: bool = True
    chunk: int = 256
    eps: float = 1e-5
    weight_init: str = "xavier_fan_in"
    keep_projection: bool = True

    supports_stateful = False   # no rnn_time_step carry (yet)

    @property
    def remat_keeps(self):
        return PROJECTION_KEPT if self.keep_projection else ()

    def remat_kept_bytes(self, it: InputType, dtype=jnp.float32) -> int:
        time = it.timeseries_length or 1
        return time * self._columns()[-1] * jnp.dtype(dtype).itemsize

    def _columns(self):
        """(d_in, the convolution's columns, W_in's columns)."""
        inner = self.n_heads * self.head_dim
        conv = inner + 2 * self.n_groups * self.state_size
        return inner, conv, inner + conv + self.n_heads

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return ("Win", "conv", "Wout")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def output_type(self, it: InputType) -> InputType:
        if self.n_heads % self.n_groups:
            raise ValueError(f"{self.n_heads} heads are no multiple of "
                             f"{self.n_groups} groups")
        if self.conv_size < 1 or self.chunk < 1:
            raise ValueError(f"a convolution of {self.conv_size} taps, a "
                             f"chunk of {self.chunk} steps")
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        inner, conv, columns = self._columns()
        k_in, k_conv, k_dt, k_out = jax.random.split(rng, 4)
        params = {
            "Win": init_weights(k_in, (d, columns), d, columns,
                                self.weight_init, self.dist, dtype),
            "conv": (jax.random.normal(k_conv, (self.conv_size, conv), dtype)
                     / math.sqrt(self.conv_size)),
            # the public initialiser: A = 1..H, D = 1
            "dt_bias": step_bias_start(k_dt, self.n_heads, dtype),
            "A_log": jnp.log(jnp.arange(1, self.n_heads + 1, dtype=dtype)),
            "D": jnp.ones((self.n_heads,), dtype),
            "norm": jnp.ones((inner,), dtype),
            "Wout": init_weights(k_out, (inner, self._width(it)), inner,
                                 self._width(it), self.weight_init,
                                 self.dist, dtype),
        }
        if self.conv_bias:
            params["conv_b"] = jnp.zeros((conv,), dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        bump_active("ssm.mamba2")
        bsz, t, _ = x.shape
        h, p, g, n = (self.n_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, conv, _ = self._columns()
        f32 = jnp.float32
        with jax.named_scope("ssm.in_proj"):
            zxbcdt = checkpoint_name(x @ params["Win"], PROJECTION_KEPT[0])
        with jax.named_scope("ssm.conv"):
            z = zxbcdt[..., :inner]
            xbc = causal_depthwise_conv(zxbcdt[..., inner:inner + conv],
                                        params["conv"])
            if self.conv_bias:
                xbc = xbc + params["conv_b"]
            xbc = jax.nn.silu(xbc)
            dt = jax.nn.softplus(zxbcdt[..., inner + conv:].astype(f32)
                                 + params["dt_bias"].astype(f32))
        with jax.named_scope("ssm.scan"):
            xs = xbc[..., :inner]
            y = chunked_ssd(
                xs.reshape(bsz, t, h, p), dt,
                -jnp.exp(params["A_log"].astype(f32)),
                xbc[..., inner:inner + g * n].reshape(bsz, t, g, n),
                xbc[..., inner + g * n:].reshape(bsz, t, g, n), self.chunk)
            # the D term over whole rows of d_in columns, as the scan's
            # kernels read and write them: a (..., heads, P) operand is
            # half a lane tile wide, and the compiler then lays y and its
            # cotangent out time-minor and copies both round the kernels
            # (a gather, not ``repeat``: a reshaped broadcast draws its
            # neighbours back into (..., heads, P))
            skip = params["D"].astype(f32)[jnp.arange(inner) // p]
            y = y.reshape(bsz, t, inner) + skip * xs.astype(f32)
        with jax.named_scope("ssm.gate_norm"):
            gated = y.reshape(bsz, t, g, inner // g)
            gate = jax.nn.silu(z.astype(f32)).reshape(gated.shape)
            weight = params["norm"].reshape(g, inner // g)
            gated = gated_norm(gated, gate, weight, self.eps)
            gated = gated.reshape(bsz, t, inner).astype(x.dtype)
        with jax.named_scope("ssm.out_proj"):
            out = gated @ params["Wout"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        return out, state


__all__ = ["Mamba2Mixer", "chunked_ssd", "gated_norm", "step_bias_start"]

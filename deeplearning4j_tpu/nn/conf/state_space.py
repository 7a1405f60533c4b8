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
one.

The first generation (``Mamba1Mixer`` over ``chunked_selective_scan``, a
decay for every (channel, state) pair, and SambaY's ``GatedMemoryUnit``)
follows under its own marker below, with its two executions."""

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
from deeplearning4j_tpu.perf.pallas import selective_scan, ssd

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


# ------------------------------------------------- the first generation
# Mamba-1's recurrence has a decay for every (channel, state) pair, so there
# is no ``C B^T`` to mask and no matrix product: ``chunked_selective_scan``
# runs the steps in order over an (N, channels) float32 state. Two
# executions of the same arithmetic, as ``chunked_ssd`` has: the ``lax`` form
# below (any shape, any backend: the CPU's path, the tests' reference,
# ``kernel.xla_selective_scan``) and, where ``perf.pallas.selective_scan
# .supported`` takes the call (a TPU, channels whole lane tiles, N a
# multiple of 8, a length that is a multiple of the kernels' block of 256
# steps, bfloat16 or float32), a forward and a backward Pallas kernel that
# carry the state in registers (``selective_scan_fwd`` / ``selective_scan_bwd``,
# ``kernel.pallas_selective_scan``): PERF.md §5-6, PR 51 (as XLA's loops the
# two scans were 30% of the Phi-4-mini-flash cell's step at 2% of their
# roofline, PR 50).
#
# steps of a chunk an iteration of the ``lax`` form's loop runs: alone on the
# chip 1, 8, 16 and 64 read 44.4, 31.6, 33.7 and 184 ms forward + backward
# (PERF.md §6, PR 50)
_UNROLL = 8


def _selective_chunk(a_t, skip, state, xs):
    """One chunk of ``chunked_selective_scan`` met with the state at its
    entry, its steps IN ORDER. ``state`` (B, N, C) float32: the states on
    the sublanes, the channels on the lanes; ``xs`` the chunk's x and dt
    (L, B, C) and B and C (L, B, N), time first; ``a_t`` (N, C) = A
    transposed; ``skip`` (C,) = D. Returns the state at the chunk's exit
    and its y (L, B, C) float32. A step is one pass over the (N, C) state
    in registers: its decay is the exponential of a product that is at
    most 0."""
    f32 = jnp.float32

    def step(s, row):
        x, dt, bm, cm = (a.astype(f32) for a in row)
        decay = jnp.exp(dt[:, None, :] * a_t)
        s = decay * s + (dt * x)[:, None, :] * bm[:, :, None]
        return s, jnp.sum(s * cm[:, :, None], axis=1) + skip * x

    return lax.scan(step, state, xs, unroll=min(_UNROLL, xs[0].shape[0]))


def chunked_selective_scan(x, dt, a_rate, bm, cm, chunk: int = 64,
                           skip=None):
    """Mamba-1's selective recurrence from a zero state, a decay for every
    (channel, state) pair:

        S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
        y_t[c] = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]

    ``x``, ``dt`` (batch, time, channels) (dt after its softplus: >= 0),
    ``a_rate`` (channels, N) (A: < 0), ``bm``, ``cm`` (batch, time, N),
    ``skip`` (channels,) = D or None for none; any float type (the decays
    and the state are float32). Returns y (batch, time, channels) float32.

    With a decay a PAIR there is no ``C B^T`` masked by a difference of
    running sums (``chunked_ssd``'s algebra): the work is 5,120 x 16
    exponentials and multiply-adds a token on the vector units, with no
    matrix product. Time is cut into chunks of ``chunk`` steps: one
    ``lax.scan`` over the chunks carries the (N, channels) float32 state
    and its body, a chunk's steps in order (``_selective_chunk``, ``_UNROLL``
    steps an iteration of the inner loop), is rematerialised: no (time,
    channels, N) array is ever alive (2.7 GB a layer at 8,192 tokens), the
    backward pass holds time / chunk entry states and, while it runs a
    chunk, that chunk's states. Decays are ``exp(dt A)`` of a non-positive
    product, never a quotient of running products, so the form is exact at
    any decay. ``time`` need not be a multiple of ``chunk`` (steps with dt
    = 0 and x = 0 are appended: they leave the state as it is). That is the
    plain ``lax`` form, XLA writing the backward pass, counted
    ``kernel.xla_selective_scan`` once a call. Where
    ``selective_scan.supported`` takes the call the same steps run as the
    Pallas kernels of ``perf/pallas/selective_scan.py``, counted
    ``kernel.pallas_selective_scan``: their block of time is their own
    constant, and ``chunk`` is the ``lax`` form's alone."""
    bsz, t, c = x.shape
    n = bm.shape[-1]
    if a_rate.shape != (c, n):
        raise ValueError(f"A is {a_rate.shape}, not {(c, n)}")
    if chunk < 1:
        raise ValueError(f"a chunk of {chunk} steps")
    if pk.take("selective_scan",
               selective_scan.supported(x, dt, a_rate, bm, cm, skip)):
        return selective_scan.selective_scan(x, dt, a_rate, bm, cm, skip)
    f32 = jnp.float32
    length = min(chunk, t)
    pad = (-t) % length
    count = (t + pad) // length

    def chunks(a):                       # (B, T, W) -> (count, L, B, W)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((count, length) + a.shape[1:])

    a_t = a_rate.astype(f32).T
    d = (jnp.zeros((c,), f32) if skip is None else skip.astype(f32))
    step = jax.checkpoint(lambda s, xs: _selective_chunk(a_t, d, s, xs))
    s0 = jnp.zeros((bsz, n, c), f32)
    _, y = lax.scan(step, s0, tuple(map(chunks, (x, dt, bm, cm))))
    return jnp.moveaxis(y.reshape(count * length, bsz, c), 0, 1)[:, :t]


@register_layer
@dataclasses.dataclass(frozen=True)
class Mamba1Mixer(BaseLayer):
    """Mamba's first-generation mixer (arXiv:2312.00752) over (batch, time,
    features), with d_in = ``expand`` x the model width, N = ``state_size``,
    K = ``conv_size`` taps and R = ``dt_rank`` (0: ceil(width / 16)):

        [xr | z] = u W_in                     (2 d_in columns, no bias)
        xc = SiLU(conv_K(xr) + b_conv)        (depthwise, causal)
        [r | B | C] = xc W_x                  (R + N + N columns, no bias)
        dt = softplus(r W_dt + dt_bias)       (R -> d_in)
        A = -exp(A_log)                       (d_in, N)
        y = the selective recurrence over xc, dt, A, B, C with D
            (``chunked_selective_scan``: Pallas kernels where they take the
            call, else ``lax`` loops over chunks of ``chunk`` steps)
        out = (y * SiLU(z)) W_out             (no bias)

    With ``share_scan`` the layer hands ``y``, BEFORE its gate, on as the
    value ``scan`` beside its output (``shared_values``: a later
    ``GatedMemoryUnit`` reads it as ``<vertex>.scan``). Scopes, forward and
    backward: ``mamba1.in_proj``, ``mamba1.conv``, ``mamba1.dt_bc`` (the two
    narrow products and the softplus), ``mamba1.scan``, ``mamba1.gate_out``;
    a layer traced counts ``ssm.mamba1`` once. A features mask zeroes the
    output at masked steps."""

    n_in: Optional[int] = None
    n_out: int = 0
    expand: int = 2
    state_size: int = 16
    conv_size: int = 4
    dt_rank: int = 0
    chunk: int = 64
    share_scan: bool = False
    weight_init: str = "xavier_fan_in"

    supports_stateful = False   # no rnn_time_step carry (yet)

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return ("Win", "conv", "Wx", "Wdt", "Wout")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def _sizes(self, d: int):
        """(d_in, R) at a model width of ``d``."""
        return self.expand * d, self.dt_rank or -(-d // 16)

    def output_type(self, it: InputType) -> InputType:
        if min(self.expand, self.state_size, self.conv_size, self.chunk) < 1:
            raise ValueError(
                f"expand {self.expand}, a state of {self.state_size}, "
                f"{self.conv_size} taps, a chunk of {self.chunk} steps")
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def shared_values(self, it: InputType):
        if not self.share_scan:
            return {}
        inner, _ = self._sizes(self.n_in or it.size)
        return {"scan": InputType.recurrent(inner, it.timeseries_length)}

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        inner, rank = self._sizes(d)
        n, width = self.state_size, self._width(it)
        k_in, k_conv, k_x, k_dt, k_b, k_out = jax.random.split(rng, 6)

        def dense(key, n_in, n_out):
            return init_weights(key, (n_in, n_out), n_in, n_out,
                                self.weight_init, self.dist, dtype)

        return {
            "Win": dense(k_in, d, 2 * inner),
            "conv": (jax.random.normal(k_conv, (self.conv_size, inner), dtype)
                     / math.sqrt(self.conv_size)),
            "conv_b": jnp.zeros((inner,), dtype),
            "Wx": dense(k_x, inner, rank + 2 * n),
            "Wdt": dense(k_dt, rank, inner),
            # the public initialiser: A = 1..N a channel, D = 1
            "dt_bias": step_bias_start(k_b, inner, dtype),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=dtype)), (inner, n)),
            "D": jnp.ones((inner,), dtype),
            "Wout": dense(k_out, inner, width),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        bump_active("ssm.mamba1")
        inner, rank = params["Wdt"].shape[1], params["Wdt"].shape[0]
        n = self.state_size
        f32 = jnp.float32
        with jax.named_scope("mamba1.in_proj"):
            xz = x @ params["Win"]
        with jax.named_scope("mamba1.conv"):
            xc = jax.nn.silu(causal_depthwise_conv(xz[..., :inner],
                                                   params["conv"])
                             + params["conv_b"])
        with jax.named_scope("mamba1.dt_bc"):
            rbc = xc @ params["Wx"]
            dt = jax.nn.softplus(
                (rbc[..., :rank] @ params["Wdt"]).astype(f32)
                + params["dt_bias"].astype(f32))
        with jax.named_scope("mamba1.scan"):
            y = chunked_selective_scan(
                xc, dt, -jnp.exp(params["A_log"].astype(f32)),
                rbc[..., rank:rank + n], rbc[..., rank + n:], self.chunk,
                skip=params["D"]).astype(x.dtype)
        with jax.named_scope("mamba1.gate_out"):
            out = gated_memory(y, xz[..., inner:]) @ params["Wout"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        if self.share_scan:
            return (out, {"scan": y}), state
        return out, state


def gated_memory(memory, gate):
    """``memory * SiLU(gate)``, the gate's sigmoid in float32."""
    return (memory.astype(jnp.float32)
            * jax.nn.silu(gate.astype(jnp.float32))).astype(gate.dtype)


@register_layer
@dataclasses.dataclass(frozen=True)
class GatedMemoryUnit(BaseLayer):
    """SambaY's gated memory unit (arXiv:2507.06607): a layer of the
    cross-decoder that runs no scan of its own and gates another layer's,

        out = (m * SiLU(x W_1)) W_2        (no bias)

    with ``m`` (batch, time, ``memory_size``) a ``Mamba1Mixer``'s scan
    output before its gate, read as this vertex's SECOND input (the value
    ``<mixer's vertex>.scan``: ``extra_inputs``). ``W_1`` is width x
    ``memory_size``, ``W_2`` ``memory_size`` x width. Scope ``gmu.gate``
    (the gate alone; the two products lie under the layer's marker)."""

    n_in: Optional[int] = None
    n_out: int = 0
    memory_size: int = 0
    weight_init: str = "xavier_fan_in"

    extra_inputs = ("memory",)

    def extra_input_sizes(self, it: InputType):
        return {"memory": self.memory_size}

    def regularizable(self):
        return ("W1", "W2")

    def input_kind(self):
        return "rnn"

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def output_type(self, it: InputType) -> InputType:
        if self.memory_size < 1:
            raise ValueError("memory_size: the width of the memory read")
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        k1, k2 = jax.random.split(rng)
        m, width = self.memory_size, self._width(it)
        return {
            "W1": init_weights(k1, (d, m), d, m, self.weight_init, self.dist,
                               dtype),
            "W2": init_weights(k2, (m, width), m, width, self.weight_init,
                               self.dist, dtype),
        }, {}

    def apply(self, params, state, x, *, memory, train=False, rng=None,
              mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        bump_active("ssm.gated_memory")
        gate = x @ params["W1"]
        with jax.named_scope("gmu.gate"):
            gated = gated_memory(memory, gate)
        out = gated @ params["W2"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        return out, state


__all__ = ["Mamba2Mixer", "Mamba1Mixer", "GatedMemoryUnit", "chunked_ssd",
           "chunked_selective_scan", "gated_memory", "gated_norm",
           "step_bias_start"]

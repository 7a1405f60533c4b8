"""Self-attention layers: long context as a first-class LAYER API.

Parity surface: the reference line's successor API — deeplearning4j
1.0.0-beta ``nn/conf/layers/SelfAttentionLayer.java`` /
``LearnedSelfAttentionLayer.java`` (DL4J 0.9.x itself predates attention;
these layers complete the sequence-model family the way the project's own
later releases did). TPU-native: the score math runs through the Pallas
flash-attention kernel on TPU (``parallel/ring_attention.py`` — tiled
online softmax, no (T, T) materialization) when shapes satisfy the kernel's
block constraints; padded batches, tiny sequences, and off-TPU runs use the
masked dense path (``reference_attention``, shared with the ring/Ulysses
parity tests so there is exactly ONE dense implementation). For sequences
beyond one chip, the same math shards over the mesh via
``ring_self_attention`` / ``ulysses_self_attention`` (parallel/).

Beside them the three attention layers of today's decoders, all over
``blocked_causal_attention`` (tiles, own backward pass, Pallas kernels on a
TPU): ``MultiHeadLatentAttention`` (as many k/v heads as query heads, q/k
and v widths that differ; as fields a low-rank query and a decoupled
rotation in the interleaved pairing), ``GatedAttention`` (fewer k/v
heads than query heads, per-head q/k norms, a partial rotary embedding,
an output gate) and ``RotaryAttention`` (the plain decoder attention: q, k,
v, o projections, a rotary embedding over the whole head width, equal or
grouped heads, no gate; as fields a sliding window, a YaRN-scaled rotation
and per-head q/k norms).

Param layout: nested ``{"q": {"W", "b"}, "k": ..., "v": ..., "o": ...}``
(plus ``ff1``/``ff2`` in the encoder block) so the framework's bias-aware
machinery — l1_bias/l2_bias regularization, bias constraints, weight noise
``apply_to_bias`` — discovers the biases through the standard
``<prefix>/b`` sibling rule (layers.py ``_bias_keys``).

What a rematerialised layer (``remat=``) holds from its first pass to its
backward pass: ``blocked_causal_attention``'s backward rule has five
residuals, and both executions name two of them, the output and the
log-sum-exp (``kernels.KEPT``). ``MultiHeadLatentAttention`` declares those
(PR 40) and names and declares the other three itself, q, k and v as it
hands them over (``OPERANDS_KEPT``, PR 45), so its backward pass runs
neither the tile pairs forward nor ``W_qb`` / ``W_q``, ``W_kvb``, the
rotation, the concatenations and the transposes a second time; it still
makes ``W_qa x``, ``q_norm``, ``W_kva x`` and ``kv_norm`` again, which the
up-projections' weight gradients and the norms' backward passes read, and
pays 1,284 bytes a token and head of 192 / 128 in bfloat16.
``RotaryAttention`` and ``GatedAttention`` declare nothing: no policy of
theirs knows a name, the ``name`` equations lower to their operands and
their programs are what they were. ``remat="nothing_saveable"`` keeps
nothing for any type.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BaseLayer, dropout_input, register_layer,
)
from deeplearning4j_tpu.nn.initializers import init_weights
from deeplearning4j_tpu.perf import pallas as pk
from deeplearning4j_tpu.perf.pallas import attention as kernels


# q, k and v as ``MultiHeadLatentAttention`` hands them to
# ``blocked_causal_attention``, by their ``checkpoint_name``: heads-major
# (batch, heads, time, width) in the compute type, q rotated, the shared
# rotated key spread over k's heads; the layer names them, not the function
# it shares with ``RotaryAttention`` and ``GatedAttention``
OPERANDS_KEPT = ("latent_attention.q", "latent_attention.k",
                 "latent_attention.v")


def _heads(x, h):
    b, t, d = x.shape
    return x.reshape(b, t, h, d // h).transpose(0, 2, 1, 3)


def _unheads(x):
    b, h, t, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * dh)


def _proj(p, x):
    z = x @ p["W"]
    return z + p["b"] if "b" in p else z


def _attend(params, x, mask, n_heads: int, causal: bool):
    """Shared multi-head attention core over nested q/k/v/o param groups.
    Uses the Pallas flash kernel when the shapes meet its block constraints
    and there is no padding mask; the dense path is reference_attention.

    Which path the compiled program took is observable: every trace bumps a
    ``perf.CompileWatch`` counter (``attention.flash`` /
    ``attention.flash_fallback`` / ``attention.dense``) via
    ``bump_active`` — landing on the owning model's watch when traced
    inside one of its jitted programs, and on ``GLOBAL`` always — surfaced
    by ``ParallelInference.stats()``. A serving fleet silently running the
    dense path instead of the Pallas kernel shows up in its stats rather
    than only as a latency regression. Counters tick at TRACE time (once
    per compiled program), not per dispatch."""
    import jax

    from deeplearning4j_tpu.parallel.ring_attention import (
        flash_self_attention, reference_attention,
    )
    from deeplearning4j_tpu.perf.compile_watch import bump_active

    q = _heads(_proj(params["q"], x), n_heads)
    k = _heads(_proj(params["k"], x), n_heads)
    v = _heads(_proj(params["v"], x), n_heads)
    out = None
    if mask is None and q.shape[2] >= 128:
        on_tpu = jax.default_backend() == "tpu"
        try:
            out = flash_self_attention(q, k, v, causal=causal)
            bump_active("attention.flash" if on_tpu
                        else "attention.flash_unavailable")
        except ValueError:
            # kernel block constraints (shape-dependent): the silent perf
            # cliff this counter exists for — the Pallas kernel was
            # eligible but got skipped
            bump_active("attention.flash_fallback")
            out = None
    else:
        bump_active("attention.dense")  # masked/short sequence: by design
    if out is None:
        out = reference_attention(q, k, v, causal=causal, key_mask=mask)
    return _proj(params["o"], _unheads(out))


def _qkvo_params(rng, n_in: int, d: int, layer, dtype):
    ks = jax.random.split(rng, 4)
    out = {}
    for key, k_, din, dout in (("q", ks[0], n_in, d), ("k", ks[1], n_in, d),
                               ("v", ks[2], n_in, d), ("o", ks[3], d, d)):
        g = {"W": init_weights(k_, (din, dout), din, dout, layer.weight_init,
                               layer.dist, dtype)}
        if layer.has_bias:
            g["b"] = jnp.full((dout,), layer.bias_init, dtype)
        out[key] = g
    return out


@register_layer
@dataclasses.dataclass(frozen=True)
class SelfAttentionLayer(BaseLayer):
    """Multi-head self-attention over (batch, time, features).

    ``n_out`` is the model width (divisible by ``n_heads``); Q/K/V and the
    output projection are learned. ``causal=True`` gives autoregressive
    masking; the framework's feature masks become key padding masks and
    masked timesteps emit zeros (the recurrent-layer output contract).
    """

    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 4
    causal: bool = False
    has_bias: bool = True
    activation: str = "identity"

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    supports_stateful = False  # full-sequence layer: no rnn_time_step carry

    def regularizable(self):
        return ("q/W", "k/W", "v/W", "o/W")

    def output_type(self, it: InputType) -> InputType:
        if self.n_out % self.n_heads:
            raise ValueError(
                f"n_out {self.n_out} not divisible by n_heads {self.n_heads}")
        return InputType.recurrent(self.n_out, it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        n_in = self.n_in or it.size
        return _qkvo_params(rng, n_in, self.n_out, self, dtype), {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        out = get_activation(self.activation)(
            _attend(params, x, mask, self.n_heads, self.causal))
        if mask is not None:  # masked steps emit zeros, post-activation
            out = out * mask[..., None].astype(out.dtype)
        return out, state


@register_layer
@dataclasses.dataclass(frozen=True)
class TransformerEncoderBlock(BaseLayer):
    """Pre-LN transformer block: LN -> MHA -> residual, LN -> FFN(gelu) ->
    residual. Width-preserving (n_out == n_in); stack for depth. Shares the
    attention core with :class:`SelfAttentionLayer` (flash kernel on TPU)."""

    n_in: Optional[int] = None
    n_out: int = 0              # model width; inferred from input when 0
    n_heads: int = 4
    ff_size: int = 0            # defaults to 4*width
    causal: bool = False
    has_bias: bool = True
    ff_activation: str = "gelu"
    activation: str = "identity"

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    supports_stateful = False

    def regularizable(self):
        return ("q/W", "k/W", "v/W", "o/W", "ff1/W", "ff2/W")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def output_type(self, it: InputType) -> InputType:
        d = self._width(it)
        if it.size and d != it.size:
            raise ValueError(
                f"TransformerEncoderBlock is residual: width {d} must match "
                f"input size {it.size}")
        if d % self.n_heads:
            raise ValueError(
                f"width {d} not divisible by n_heads {self.n_heads}")
        return InputType.recurrent(d, it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self._width(it)
        ff = self.ff_size or 4 * d
        k_attn, k1, k2 = jax.random.split(rng, 3)
        params = _qkvo_params(k_attn, d, d, self, dtype)
        for key, k_, din, dout in (("ff1", k1, d, ff), ("ff2", k2, ff, d)):
            g = {"W": init_weights(k_, (din, dout), din, dout,
                                   self.weight_init, self.dist, dtype)}
            if self.has_bias:
                g["b"] = jnp.full((dout,), self.bias_init, dtype)
            params[key] = g
        params["ln1_g"] = jnp.ones((d,), dtype)
        params["ln1_b"] = jnp.zeros((d,), dtype)
        params["ln2_g"] = jnp.ones((d,), dtype)
        params["ln2_b"] = jnp.zeros((d,), dtype)
        return params, {}

    @staticmethod
    def _ln(x, g, b, eps=1e-5):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * g + b

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        att_in = self._ln(x, params["ln1_g"], params["ln1_b"])
        x = x + _attend(params, att_in, mask, self.n_heads, self.causal)
        ff_in = self._ln(x, params["ln2_g"], params["ln2_b"])
        h = get_activation(self.ff_activation)(_proj(params["ff1"], ff_in))
        x = get_activation(self.activation)(x + _proj(params["ff2"], h))
        if mask is not None:  # masked steps emit zeros, post-activation
            x = x * mask[..., None].astype(x.dtype)
        return x, state


# ------------------------------------------------- blocked causal attention
def _pair_scores(q_i, k_j, scale):
    return jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j,
                      preferred_element_type=jnp.float32) * scale


def _diagonal_mask(block: int):
    return jnp.tril(jnp.ones((block, block), bool))


def _band_tiles(i: int, block: int, window):
    """For query tile ``i`` under a window of ``window`` keys (None: the
    causal triangle): the first key tile whose every position the tile's
    queries see, what the window's far edge leaves of the diagonal tile
    (None: all of it), and the key tiles before the first whole one that
    the far edge crosses; each as ``far``, the pair's distance in
    positions less the window (``_tile_keep``)."""
    if window is None:
        return 0, None, []
    lo = max(0, (i * block - window + 1) // block)
    whole = max(lo, i - window // block + 1)
    return (whole, -window if window < block else None,
            [(j, (i - j) * block - window) for j in range(lo, min(whole, i))])


def _tile_keep(block: int, diagonal: bool, far):
    """What a (query, key) tile pair keeps: on the diagonal tile the keys
    at or before the query, and with ``far`` the keys less than a window
    before it (key - query > ``far`` inside the pair)."""
    keep = _diagonal_mask(block) if diagonal else None
    if far is not None:
        inside = (jnp.arange(block)[None, :] - jnp.arange(block)[:, None]
                  > far)
        keep = inside if keep is None else keep & inside
    return keep


def _blocked_forward(q, k, v, block: int, window=None):
    """(out, logsumexp) of causal softmax(q k^T / sqrt(d_q)) v, a block of
    queries at a time against the key blocks at or before it (with a
    ``window``: those that hold a key the block's queries see); one
    (block, block) score tile a head is alive at a time."""
    bsz, h, t, dq = q.shape
    scale = 1.0 / (dq ** 0.5)
    nb = t // block
    outs, lses = [], []

    def tile(a, j):
        return lax.dynamic_slice_in_dim(a, j * block, block, axis=2)

    for i in range(nb):
        q_i = q[:, :, i * block:(i + 1) * block]

        def fold(carry, s, v_j):
            m, l, acc = carry
            m_new = jnp.maximum(m, jnp.max(s, -1))
            p = jnp.exp(s - m_new[..., None])
            fix = jnp.exp(m - m_new)
            acc = acc * fix[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return m_new, l * fix + jnp.sum(p, -1), acc

        carry = (jnp.full((bsz, h, block), -jnp.inf, jnp.float32),
                 jnp.zeros((bsz, h, block), jnp.float32),
                 jnp.zeros((bsz, h, block, v.shape[-1]), jnp.float32))
        # the diagonal tile first: every row has its own key, so the
        # running maximum is finite from here on (also before a tile of
        # the window's far edge in which a row sees no key)
        whole, own_far, edge = _band_tiles(i, block, window)
        s = jnp.where(_tile_keep(block, True, own_far),
                      _pair_scores(q_i, tile(k, i), scale), -jnp.inf)
        carry = fold(carry, s, tile(v, i))
        for j, far in edge:
            carry = fold(carry, jnp.where(
                _tile_keep(block, False, far),
                _pair_scores(q_i, tile(k, j), scale), -jnp.inf), tile(v, j))
        if i > whole:
            carry = lax.fori_loop(
                whole, i, lambda j, c: fold(c, _pair_scores(
                    q_i, tile(k, j), scale), tile(v, j)),
                carry)
        m, l, acc = carry
        outs.append((acc / l[..., None]).astype(v.dtype))
        lses.append(m + jnp.log(l))
    return jnp.concatenate(outs, 2), jnp.concatenate(lses, 2)


def _blocked_backward(q, k, v, out, lse, dout, block: int, window=None):
    bsz, h, t, dq = q.shape
    scale = 1.0 / (dq ** 0.5)
    nb = t // block
    delta = jnp.sum(out.astype(jnp.float32) * dout.astype(jnp.float32), -1)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    dqs = []

    def tile(a, j):
        return lax.dynamic_slice_in_dim(a, j * block, block, axis=2)

    def add_tile(a, j, upd):
        cur = lax.dynamic_slice_in_dim(a, j * block, block, axis=2)
        return lax.dynamic_update_slice_in_dim(a, cur + upd, j * block,
                                               axis=2)

    for i in range(nb):
        sl = slice(i * block, (i + 1) * block)
        q_i, do_i = q[:, :, sl], dout[:, :, sl]
        lse_i, delta_i = lse[:, :, sl], delta[:, :, sl]

        def pair(j, carry, diagonal, far=None):
            dq_i, dk, dv = carry
            k_j, v_j = tile(k, j), tile(v, j)
            s = _pair_scores(q_i, k_j, scale)
            if diagonal or far is not None:
                s = jnp.where(_tile_keep(block, diagonal, far), s, -jnp.inf)
            p = jnp.exp(s - lse_i[..., None])
            dv = add_tile(dv, j, jnp.einsum(
                "bhqk,bhqd->bhkd", p.astype(do_i.dtype), do_i,
                preferred_element_type=jnp.float32))
            dp = jnp.einsum("bhqd,bhkd->bhqk", do_i, v_j,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_i[..., None]) * scale).astype(q.dtype)
            dq_i = dq_i + jnp.einsum("bhqk,bhkd->bhqd", ds, k_j,
                                     preferred_element_type=jnp.float32)
            dk = add_tile(dk, j, jnp.einsum(
                "bhqk,bhqd->bhkd", ds, q_i,
                preferred_element_type=jnp.float32))
            return dq_i, dk, dv

        carry = (jnp.zeros(q_i.shape, jnp.float32), dk, dv)
        whole, own_far, edge = _band_tiles(i, block, window)
        carry = pair(i, carry, True, own_far)
        for j, far in edge:
            carry = pair(j, carry, False, far)
        if i > whole:
            carry = lax.fori_loop(whole, i, lambda j, c: pair(j, c, False),
                                  carry)
        dq_i, dk, dv = carry
        dqs.append(dq_i)
    return (jnp.concatenate(dqs, 2).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blocked_attention(q, k, v, block, window=None):
    return _blocked_forward(q, k, v, block, window)[0]


def _blocked_attention_fwd(q, k, v, block, window):
    # the kernels' names for the kernels' two residuals: one contract
    out, lse = map(checkpoint_name, _blocked_forward(q, k, v, block, window),
                   kernels.KEPT)
    return out, (q, k, v, out, lse)


def _blocked_attention_bwd(block, window, res, dout):
    return _blocked_backward(*res, dout, block, window)


_blocked_attention.defvjp(_blocked_attention_fwd, _blocked_attention_bwd)


def blocked_causal_attention(q, k, v, block: int = 512, window=None):
    """Causal softmax(q k^T / sqrt(d_q)) v for ``q``, ``k`` (batch, heads,
    time, d_q) and ``v`` (batch, heads, time, d_v), d_q and d_v free to
    differ, q, k and v with the same number of heads (a caller with
    grouped-query heads repeats k and v over their group first, as
    ``GatedAttention`` does), without a (time, time) array: tiles of ``block`` x ``block``,
    key tiles after the query tile skipped, an online softmax forward and a
    backward pass that makes each tile's probabilities again from the saved
    log-sum-exp (the flash-attention recipe). ``time`` is padded up to a
    multiple of ``block``: padded keys lie after every real query, and
    padded queries are cut off. With a ``window`` w, query t sees key u
    where ``t - w < u <= t`` (w keys with its own): only the BAND of tile
    pairs that hold such a key is visited, the pairs across the window's
    far edge masked there, and a window of at least the length is the
    triangle (``window=None``, today's function bit for bit).

    One algorithm, two executions, chosen at trace time by what the code
    can observe (``perf.pallas.take("blocked_attention", supported(...))``;
    the ``kernel.pallas_blocked_attention`` / ``kernel.xla_blocked_attention``
    counters say which): the Pallas kernels of ``perf/pallas/attention.py``,
    which keep a tile pair's scores, probabilities and their cotangents in
    VMEM, on a TPU for more than one tile of a length that is a multiple
    of 128, q, k, v alike in bfloat16 or float32, head widths multiples of
    64 up to 256; plain ``jax.numpy`` in tiles of ``block`` everywhere
    else, and as the reference the kernels are held to. Either execution
    names its output and its log-sum-exp, padded as they are made, with the
    ``checkpoint_name``s of ``kernels.KEPT``: a rematerialised layer whose
    type declares them (``remat_keeps``) keeps the two and runs the forward
    pass once."""
    t = q.shape[2]
    if window is not None and window >= t:
        window = None
    block = min(block, t)
    pad = (-t) % block
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for a in (q, k, v))
    if pk.take("blocked_attention",
               kernels.supported(q, k, v, block, window)):
        out = kernels.blocked_attention(q, k, v, window)
    else:
        out = _blocked_attention(q, k, v, block, window)
    return out[:, :, :t] if pad else out


@register_layer
@dataclasses.dataclass(frozen=True)
class MultiHeadLatentAttention(BaseLayer):
    """Multi-head latent attention (the DeepSeek-V2 / V3 layout): keys and
    values come from a shared low-rank latent, ``[c; k_r] = W_kva x`` with
    ``c`` (``kv_rank``) RMS-normalised; per head ``k = [W_kb^K c; k_r]``
    (``k_r``, ``rope_dim`` wide, shared by the heads), ``v = W_kb^V c``,
    ``q = W_q x`` (``nope_dim + rope_dim``), causal attention, ``W_o`` over
    heads x ``v_dim``. What the lines after ``mla_use_nope`` add are FIELDS
    that default to their absence:

        ``q_rank`` r > 0: the query through a latent of its own,
        ``q = W_qb RMSNorm_q(W_qa x)`` (leaves ``Wqa``, ``q_norm``, ``Wqb``
        in ``Wq``'s place; scope ``mla.q_lora``);
        ``rope_theta`` > 0: the decoupled rotation, the last ``rope_dim``
        widths of every query head and the ONE ``k_r`` (turned once, before
        it is handed to the heads) turned by position in the interleaved
        pairing (``rotate_interleaved``; scope ``mla.rope``). At 0 neither
        is rotated (``mla_use_nope``).

    With both off the layer draws the leaves and lowers to the program it
    always did. q/k heads and v heads differ in width, which
    ``pallas.ops.tpu.flash_attention`` (``SelfAttentionLayer``'s kernel)
    does not take: scores go through ``blocked_causal_attention`` in tiles
    of ``block``, which on a TPU runs as the Pallas kernels of
    ``perf/pallas/attention.py`` for the shapes they take (more than one
    tile, a padded length that is a multiple of 128, head widths multiples
    of 64 up to 256, bfloat16 or float32) and as plain ``jax.numpy``
    otherwise: off a TPU, one tile, an odd width. Which path a compiled
    program took is counted at trace time (``bump_active``):
    ``attention.mla_blocked`` with more than one tile,
    ``attention.mla_single_tile`` otherwise, ``attention.mla_q_lora`` and
    ``attention.mla_rotary`` once a layer that has them, and beside them
    ``kernel.pallas_blocked_attention`` / ``kernel.xla_blocked_attention``.
    A features mask zeroes the output at masked steps (right-padded batches
    are exact).

    Rematerialised (``remat=``) the layer KEEPS all five residuals of the
    attention's backward rule (``remat_keeps``): its output and log-sum-exp
    (``kernels.KEPT``, the names both executions give them; PR 40) and q, k
    and v as the layer hands them over (``OPERANDS_KEPT``, named here;
    PR 45). The backward pass reads the five, so the tile pairs run forward
    once a layer and step (``mla_attend_fwd`` once) and so do ``W_qb`` (or
    ``W_q``), ``W_kvb``, the rotation, the two concatenations and the three
    transposes. What it still makes again is what their gradients read:
    ``W_qa x`` and ``q_norm`` (``W_qb``'s weight gradient and the norm's
    backward pass), ``W_kva x`` and ``kv_norm`` likewise. It costs
    ``remat_kept_bytes``: O, q, k and v in the type the layer computes in
    and a float32 a token and head: 260 + 2 x (2 x (``nope_dim`` +
    ``rope_dim``) + ``v_dim``) = 1,284 bytes a token and head at 192 / 128
    in bfloat16, 68 + 268 MB a layer at 8192 tokens and 32 heads (a TPU
    holds a 192-wide minor axis in 256 lanes: 335 MB of q, k, v there; the
    JoyAI step's seven layers read 2.27 GB more at its peak for 25 ms of
    353). ``remat="nothing_saveable"`` keeps nothing and gives it back.
    (PERF.md §5-6, PRs 40 and 45.)"""

    n_in: Optional[int] = None
    n_out: int = 0              # model width; inferred from the input when 0
    n_heads: int = 4
    nope_dim: int = 32
    rope_dim: int = 16
    v_dim: int = 32
    kv_rank: int = 64
    block: int = 512
    eps: float = 1e-5
    weight_init: str = "xavier_fan_in"
    q_rank: int = 0             # 0: one full-rank Wq
    rope_theta: float = 0.0     # 0: q and k_r are not rotated

    supports_stateful = False
    remat_keeps = kernels.KEPT + OPERANDS_KEPT

    def remat_kept_bytes(self, it: InputType, dtype=jnp.float32) -> int:
        """O and the log-sum-exp at the length the attention pads to, and
        q, k, v at the length the layer is given (the padding is made
        again)."""
        time = it.timeseries_length or 1
        operands = 2 * (self.nope_dim + self.rope_dim) + self.v_dim
        return kernels.kept_bytes(time, self.n_heads, self.v_dim, self.block,
                                  dtype) \
            + time * self.n_heads * operands * jnp.dtype(dtype).itemsize

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return (("Wqa", "Wqb") if self.q_rank else ("Wq",)) \
            + ("Wkva", "Wkvb", "Wo")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def output_type(self, it: InputType) -> InputType:
        if self.rope_theta and self.rope_dim % 2:
            raise ValueError(f"rope_dim {self.rope_dim} has to be even: the "
                             "rotation pairs adjacent widths")
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        h = self.n_heads
        ks = jax.random.split(rng, 4)

        def dense(key, n_in, n_out):
            return init_weights(key, (n_in, n_out), n_in, n_out,
                                self.weight_init, self.dist, dtype)

        d_q = h * (self.nope_dim + self.rope_dim)
        if self.q_rank:
            ka, kb = jax.random.split(ks[0])
            query = {"Wqa": dense(ka, d, self.q_rank),
                     "q_norm": jnp.ones((self.q_rank,), dtype),
                     "Wqb": dense(kb, self.q_rank, d_q)}
        else:
            query = {"Wq": dense(ks[0], d, d_q)}
        return {
            **query,
            "Wkva": dense(ks[1], d, self.kv_rank + self.rope_dim),
            "kv_norm": jnp.ones((self.kv_rank,), dtype),
            "Wkvb": dense(ks[2], self.kv_rank,
                          h * (self.nope_dim + self.v_dim)),
            "Wo": dense(ks[3], h * self.v_dim, self._width(it)),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.conf.normalization import rms_norm
        from deeplearning4j_tpu.perf.compile_watch import bump_active

        x = dropout_input(x, self.dropout, train, rng)
        bsz, t, _ = x.shape
        h, nope, rope = self.n_heads, self.nope_dim, self.rope_dim
        if self.q_rank:
            bump_active("attention.mla_q_lora")
            with jax.named_scope("mla.q_lora"):
                q = rms_norm(x @ params["Wqa"], params["q_norm"], self.eps) \
                    @ params["Wqb"]
        else:
            q = x @ params["Wq"]
        q = q.reshape(bsz, t, h, nope + rope)
        kva = x @ params["Wkva"]
        c = rms_norm(kva[..., :self.kv_rank], params["kv_norm"], self.eps)
        k_r = kva[..., self.kv_rank:]
        if self.rope_theta:
            bump_active("attention.mla_rotary")
            with jax.named_scope("mla.rope"):
                positions = jnp.arange(t)
                q = jnp.concatenate(
                    [q[..., :nope], rotate_interleaved(
                        q[..., nope:], positions, self.rope_theta)], -1)
                k_r = rotate_interleaved(k_r, positions, self.rope_theta)
        kvb = (c @ params["Wkvb"]).reshape(bsz, t, h, nope + self.v_dim)
        k = jnp.concatenate(
            [kvb[..., :nope],
             jnp.broadcast_to(k_r[:, :, None, :], (bsz, t, h, rope))], -1)
        v = kvb[..., nope:]
        bump_active("attention.mla_blocked" if t > self.block
                    else "attention.mla_single_tile")
        with jax.named_scope("mla.attend"):
            # named as the attention reads them: its backward rule's
            # other three residuals
            q, k, v = (checkpoint_name(a.transpose(0, 2, 1, 3), name)
                       for a, name in zip((q, k, v), OPERANDS_KEPT))
            o = blocked_causal_attention(q, k, v, self.block)
        out = o.transpose(0, 2, 1, 3).reshape(bsz, t, h * self.v_dim) \
            @ params["Wo"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        return out, state


def yarn_inv_freq(dim: int, base: float, factor: float,
                  original_length: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """The ``dim / 2`` inverse frequencies of a YaRN-scaled rotation
    (arXiv:2309.00071, as the public ``rope_type: "yarn"`` initialisation
    computes them): width pair j turns ``original_length * base^(-2j/dim)
    / (2 pi)`` times over the original context; pairs that turn more than
    ``beta_fast`` times keep their frequency, pairs that turn fewer than
    ``beta_slow`` times have it divided by ``factor``, and a linear ramp
    over j blends the two in between (its ends rounded outward to whole
    j). Float32, computed on the host."""
    def pair_that_turns(times):
        return (dim * math.log(original_length / (times * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    j = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    plain = float(base) ** (-2.0 * j / dim)
    return jnp.asarray((1.0 - ramp) * plain + ramp * plain / factor,
                       jnp.float32)


def rotate_half_split(x, positions, rotary_dim: int, theta: float,
                      inv_freq=None, factor: float = 1.0):
    """A rotary embedding over the first ``rotary_dim`` widths of ``x``
    (batch, time, heads, width), the rest left as they are: width j of the
    first half is paired with width j + rotary_dim / 2 (the "half-split"
    layout of the Llama family's public code) and the pair turned by
    ``positions * theta^(-2j / rotary_dim)``, or by ``positions *
    inv_freq[j]`` where a scaled rotation gives its own ``rotary_dim / 2``
    frequencies (``yarn_inv_freq``); cosines and sines times ``factor``
    (a scaled rotation's attention factor). Angles, sines and the turn
    itself in float32; the result in ``x``'s type."""
    half = rotary_dim // 2
    freq = (theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
            if inv_freq is None else inv_freq)
    angle = positions.astype(jnp.float32)[:, None] * freq      # (time, half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary_dim].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rotary_dim:]], -1)


@functools.lru_cache(maxsize=None)
def _pair_swap(width: int):
    """The (width, width) matrix S with ``(x @ S)[2j] = -x[2j + 1]`` and
    ``(x @ S)[2j + 1] = x[2j]``: one +-1 a column, so the float32 product
    at the highest precision is exact."""
    s = np.zeros((width, width), np.float32)
    j = np.arange(0, width, 2)
    s[j + 1, j], s[j, j + 1] = -1.0, 1.0
    return s


def rotate_interleaved(x, positions, theta: float):
    """A rotary embedding over ALL widths of ``x`` (batch, time, ..., width)
    in the interleaved pairing (``rope_interleave`` of the DeepSeek-V3
    family's configs): adjacent widths (2j, 2j + 1) are one pair, turned by
    ``positions * theta^(-2j / width)``; ``rotate_half_split`` pairs width
    j with j + width / 2. ``out = x cos + partner sin`` with
    ``partner[2j] = -x[2j + 1]``, ``partner[2j + 1] = x[2j]``; the partner is
    ``x`` times a signed permutation (``_pair_swap``: 2 width^2 operations
    a row on the MXU, exact; shifts along the width axis read 8.7 ms a
    layer and step on the chip at 8192 x 32 heads of 64, this 1.8: PERF.md
    section 6, PR 39). Angles, sines and the turn itself in float32; the
    result in ``x``'s type."""
    width = x.shape[-1]
    freq = theta ** (-jnp.arange(width // 2, dtype=jnp.float32) * 2.0 / width)
    angle = jnp.repeat(positions.astype(jnp.float32)[:, None] * freq, 2, -1)
    shape = (angle.shape[0],) + (1,) * (x.ndim - 3) + (width,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    xf = x.astype(jnp.float32)
    partner = jnp.matmul(xf, _pair_swap(width),
                         precision=lax.Precision.HIGHEST)
    return (xf * cos + partner * sin).astype(x.dtype)


@register_layer
@dataclasses.dataclass(frozen=True)
class GatedAttention(BaseLayer):
    """Causal grouped-query attention with per-head q/k norms, a partial
    rotary embedding and a sigmoid output gate, as the Qwen3-Next family's
    full-attention layers run it. With h = ``n_heads``, h_kv =
    ``n_kv_heads`` (h a multiple of it) and d = ``head_dim``:

        [q, gate] = W_q x       (h x 2d columns: each head's q, then its gate)
        k = W_k x,  v = W_v x                          (h_kv x d columns each)
        q, k = RMSNorm_head(q), RMSNorm_head(k)     (over d, weights q_norm /
                      k_norm started at zero, the scale ``1 + w``)
        the first ``rotary_dim`` widths of q and k rotated
        (``rotate_half_split``), the others left alone
        each k / v head serves h / h_kv consecutive query heads
        out = W_o (softmax(q k^T / sqrt(d)) v * sigmoid(gate)),  no bias

    The scores go through ``blocked_causal_attention`` with k and v
    REPEATED over their group in front of it (``jnp.repeat``, whose
    transpose sums dk and dv over the group on the way back): the tile
    kernels keep their one index map and the latent attention's call
    compiles as before; k and v are then read once a query head, 8 KB a
    key of the 0.5 MB of scores a head makes from them. Which path a
    compiled program took is counted at trace time (``bump_active``):
    ``attention.gqa_blocked`` with more than one tile,
    ``attention.gqa_single_tile`` otherwise, and beside them
    ``kernel.pallas_blocked_attention`` / ``kernel.xla_blocked_attention``.
    A features mask zeroes the output at masked steps (right-padded batches
    are exact)."""

    n_in: Optional[int] = None
    n_out: int = 0              # model width; inferred from the input when 0
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    rotary_dim: int = 8
    rope_theta: float = 10000.0
    block: int = 512
    eps: float = 1e-6
    weight_init: str = "xavier_fan_in"

    supports_stateful = False

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return ("Wq", "Wk", "Wv", "Wo")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def output_type(self, it: InputType) -> InputType:
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads are no multiple "
                             f"of {self.n_kv_heads} key/value heads")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim} has to be even "
                             f"and at most head_dim {self.head_dim}")
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        h, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        ks = jax.random.split(rng, 4)

        def dense(key, n_in, n_out):
            return init_weights(key, (n_in, n_out), n_in, n_out,
                                self.weight_init, self.dist, dtype)

        return {
            "Wq": dense(ks[0], d, h * 2 * dh),
            "Wk": dense(ks[1], d, hkv * dh),
            "Wv": dense(ks[2], d, hkv * dh),
            "q_norm": jnp.zeros((dh,), dtype),
            "k_norm": jnp.zeros((dh,), dtype),
            "Wo": dense(ks[3], h * dh, self._width(it)),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.conf.normalization import rms_norm
        from deeplearning4j_tpu.perf.compile_watch import bump_active

        x = dropout_input(x, self.dropout, train, rng)
        bsz, t, _ = x.shape
        h, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        qg = (x @ params["Wq"]).reshape(bsz, t, h, 2 * dh)
        q, gate = qg[..., :dh], qg[..., dh:]
        k = (x @ params["Wk"]).reshape(bsz, t, hkv, dh)
        v = (x @ params["Wv"]).reshape(bsz, t, hkv, dh)
        with jax.named_scope("gattn.qk_norm_rope"):
            positions = jnp.arange(t)
            q, k = (rotate_half_split(
                rms_norm(a, 1.0 + params[w], self.eps), positions,
                self.rotary_dim, self.rope_theta)
                for a, w in ((q, "q_norm"), (k, "k_norm")))
        bump_active("attention.gqa_blocked" if t > self.block
                    else "attention.gqa_single_tile")
        with jax.named_scope("gattn.attend"):
            k, v = (jnp.repeat(a.transpose(0, 2, 1, 3), h // hkv, axis=1)
                    for a in (k, v))
            o = blocked_causal_attention(q.transpose(0, 2, 1, 3), k, v,
                                         self.block)
        with jax.named_scope("gattn.out_gate"):
            o = o.transpose(0, 2, 1, 3) * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(x.dtype)
            out = o.reshape(bsz, t, h * dh) @ params["Wo"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        return out, state


@register_layer
@dataclasses.dataclass(frozen=True)
class RotaryAttention(BaseLayer):
    """Causal multi-head attention with a rotary embedding, as the Llama
    line of decoders (and the looped ``ouro`` models) run it, and what the
    lines after it add as FIELDS that default to its absence: a sliding
    window, a scaled rotation, per-head q/k norms. With h = ``n_heads``,
    h_kv = ``n_kv_heads`` (h when left at 0; h a multiple of it) and d =
    ``head_dim``:

        q = W_q x  (h x d columns),  k = W_k x,  v = W_v x  (h_kv x d each)
        with ``qk_norm``: q, k = RMSNorm_head(q), RMSNorm_head(k) (over d,
        plain weights ``q_norm`` / ``k_norm`` started at one, ``eps``)
        all d widths of q and k rotated (``rotate_half_split``; a part of
        the head: ``GatedAttention``): by ``rope_theta^(-2j/d)``, or with
        ``rope_scaling`` (the model config's ``rope_parameters`` entry of
        this layer's type; ``rope_type`` ``"yarn"`` is built, ``"default"``
        is none) by ``yarn_inv_freq`` with cosines and sines times its
        ``attention_factor`` (0.1 ln(factor) + 1 where the entry gives
        none), at every length
        each k / v head serves h / h_kv consecutive query heads
        query t sees keys u <= t, and with ``window`` w > 0 of them those
        with u > t - w (w keys with its own)
        out = W_o softmax(q k^T / sqrt(d)) v,  no bias, no gate
        with ``position_embedding`` ``"nope"``: no rotation and no other
        position term (the causal mask alone orders the keys); with
        ``softmax_scale`` s > 0: softmax(s q k^T), the scale folded into q
        (q * (s sqrt(d)), exact in bfloat16 where that is a power of two)
        in front of the same attention, which scales by 1 / sqrt(d)

    The scores go through ``blocked_causal_attention`` (with the window:
    the band of tile pairs); k and v are repeated over their group in
    front of it only where the group is more than one head
    (``GatedAttention`` has the reasons). Which path a compiled program
    took is counted at trace time (``bump_active``):
    ``attention.rotary_blocked`` with more than one tile,
    ``attention.rotary_single_tile`` otherwise,
    ``attention.rotary_windowed`` once a layer whose window is shorter
    than the sequence, ``attention.nope`` once a layer built without the
    rotation, and beside them ``kernel.pallas_blocked_attention``
    / ``kernel.xla_blocked_attention``. A features mask zeroes the output
    at masked steps (right-padded batches are exact)."""

    n_in: Optional[int] = None
    n_out: int = 0              # model width; inferred from the input when 0
    n_heads: int = 4
    n_kv_heads: int = 0         # 0: as many as query heads
    head_dim: int = 32
    rope_theta: float = 10000.0
    block: int = 512
    weight_init: str = "xavier_fan_in"
    window: int = 0             # 0: every key at or before the query
    rope_scaling: Optional[dict] = None
    qk_norm: bool = False
    eps: float = 1e-6           # of the q/k norms
    position_embedding: str = "rope"    # "nope": no position term at all
    softmax_scale: float = 0.0          # 0: head_dim ** -0.5

    supports_stateful = False

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return ("Wq", "Wk", "Wv", "Wo")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def _rotation(self):
        """(inverse frequencies | None for the plain ones, the factor on
        cosines and sines) of this layer's rotation."""
        scaling = self.rope_scaling
        kind = (scaling or {}).get("rope_type", "default")
        if kind == "default":
            return None, 1.0
        if kind != "yarn":
            raise NotImplementedError(
                f"rope_type {kind!r}: the plain and the YaRN-scaled "
                "rotation are built")
        factor = float(scaling["factor"])
        return (yarn_inv_freq(
            self.head_dim, float(scaling.get("rope_theta", self.rope_theta)),
            factor, int(scaling["original_max_position_embeddings"]),
            float(scaling.get("beta_fast", 32.0)),
            float(scaling.get("beta_slow", 1.0))),
            float(scaling.get("attention_factor")
                  or 0.1 * math.log(factor) + 1.0))

    def output_type(self, it: InputType) -> InputType:
        hkv = self.n_kv_heads or self.n_heads
        if self.n_heads % hkv:
            raise ValueError(f"{self.n_heads} query heads are no multiple "
                             f"of {hkv} key/value heads")
        if self.head_dim % 2:
            raise ValueError(f"head_dim {self.head_dim} has to be even: the "
                             "rotation pairs its halves")
        if self.window < 0:
            raise ValueError(f"a window of {self.window} keys")
        if self.position_embedding not in ("rope", "nope"):
            raise ValueError(f"position_embedding "
                             f"{self.position_embedding!r}: 'rope' or 'nope'")
        if self.softmax_scale < 0:
            raise ValueError(f"a softmax scale of {self.softmax_scale}")
        self._rotation()            # an unknown rope_type fails here
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        h, dh = self.n_heads, self.head_dim
        hkv = self.n_kv_heads or h
        ks = jax.random.split(rng, 4)

        def dense(key, n_in, n_out):
            return init_weights(key, (n_in, n_out), n_in, n_out,
                                self.weight_init, self.dist, dtype)

        params = {
            "Wq": dense(ks[0], d, h * dh),
            "Wk": dense(ks[1], d, hkv * dh),
            "Wv": dense(ks[2], d, hkv * dh),
            "Wo": dense(ks[3], h * dh, self._width(it)),
        }
        if self.qk_norm:
            params["q_norm"] = jnp.ones((dh,), dtype)
            params["k_norm"] = jnp.ones((dh,), dtype)
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.conf.normalization import rms_norm
        from deeplearning4j_tpu.perf.compile_watch import bump_active

        x = dropout_input(x, self.dropout, train, rng)
        bsz, t, _ = x.shape
        h, dh = self.n_heads, self.head_dim
        hkv = self.n_kv_heads or h
        q = (x @ params["Wq"]).reshape(bsz, t, h, dh)
        k = (x @ params["Wk"]).reshape(bsz, t, hkv, dh)
        v = (x @ params["Wv"]).reshape(bsz, t, hkv, dh)
        if self.qk_norm:
            with jax.named_scope("rattn.qk_norm"):
                q = rms_norm(q, params["q_norm"], self.eps)
                k = rms_norm(k, params["k_norm"], self.eps)
        if self.position_embedding == "nope":
            bump_active("attention.nope")
        else:
            with jax.named_scope("rattn.rope"):
                positions = jnp.arange(t)
                inv_freq, factor = self._rotation()
                q, k = (rotate_half_split(a, positions, dh, self.rope_theta,
                                          inv_freq, factor)
                        for a in (q, k))
        if self.softmax_scale:
            # the attention below scales by 1 / sqrt(d): q carries the rest
            q = q * jnp.asarray(self.softmax_scale * math.sqrt(dh), q.dtype)
        bump_active("attention.rotary_blocked" if t > self.block
                    else "attention.rotary_single_tile")
        window = self.window if 0 < self.window < t else None
        if window:
            bump_active("attention.rotary_windowed")
        with jax.named_scope("rattn.attend"):
            q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
            if h != hkv:
                k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
            o = blocked_causal_attention(q, k, v, self.block, window)
        out = o.transpose(0, 2, 1, 3).reshape(bsz, t, h * dh) @ params["Wo"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        return out, state


def differential_lambda_init(layer_index: int) -> float:
    """``0.8 - 0.6 exp(-0.3 i)`` at layer index i from 0 (Differential
    Transformer, arXiv:2410.05258)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


def _key_bias_held(bias, start: int, width: int):
    """``bias`` with its KEY columns (``width`` from ``start``) constant to
    autodiff. A bias on the keys shifts every score of a row alike, so no
    softmax moves and its gradient is identically zero; what arithmetic
    leaves of it is rounding, which Adam would normalise into a walk of
    learning-rate steps (in bfloat16 every step, in float32 hardly: the one
    leaf on which the two would part for no reason in the mathematics)."""
    return jnp.concatenate([
        bias[:start], lax.stop_gradient(bias[start:start + width]),
        bias[start + width:]])


def differential(first, second, lam):
    """``A1 - lambda A2``: the second softmax's output taken off the
    first's."""
    return first - lam * second


@register_layer
@dataclasses.dataclass(frozen=True)
class DifferentialAttention(BaseLayer):
    """Differential attention (arXiv:2410.05258) over grouped heads, causal
    or under a window, with no position term, as SambaY's decoders run it
    (arXiv:2507.06607). With h = ``n_heads`` query heads and h_kv =
    ``n_kv_heads`` key/value heads of d = ``head_dim`` (h and h_kv even, h a
    multiple of h_kv):

        [q | k | v] = x W_qkv + b_qkv       (h d + h_kv d + h_kv d columns)
        query heads (2j, 2j+1) are q1_j, q2_j (j < h/2); key heads (2g,
        2g+1) are k1_g, k2_g and V_g = [v_2g | v_2g+1], 2d wide (g <
        h_kv/2); pair j reads group g = j // (h / h_kv)
        A1_j = softmax(q1_j k1_g^T / sqrt(d) + mask) V_g,  A2_j likewise
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, four
            d-vectors a layer, lambda_init = ``differential_lambda_init``
            of ``layer_index`` (the PUBLISHED index of the layer)
        o_j = (1 - lambda_init) RMSNorm_2d(A1_j - lambda A2_j)   (one 2d-wide
            weight ``subln`` a layer, ``eps``)
        out = [o_0 .. o_{h/2-1}] W_o + b_o

    the mask causal (``window`` 0) or causal within ``window`` keys (the
    query's own with them). With ``share_kv`` the layer hands its ``[k |
    v]`` columns on as the value ``kv`` (``shared_values``); with
    ``kv_from`` naming such a layer's vertex it is a CROSS-attention: it
    owns ``W_q``, ``b_q`` alone of the three projections and reads
    ``<kv_from>.kv`` as its second input (``extra_inputs``), under the
    causal mask or its window. The KEY columns of ``b_qkv`` are held where they start (``_key_bias_held``:
    their gradient is identically zero, and the layer hands Adam that zero
    and not the rounding left of it).

    The two softmaxes of every pair go through ONE
    ``blocked_causal_attention`` call of h "heads": query head i against key
    head 2 (i // (2 h / h_kv)) + i % 2 and its group's 2d-wide value, k and
    V repeated over their group in front of it as ``RotaryAttention`` does,
    widths (d | d | 2d): (64, 128) reaches the kernels of
    ``perf/pallas/attention.py`` unchanged. Counted at trace time:
    ``attention.differential`` once a layer,
    ``attention.differential_windowed`` once a layer whose window is shorter
    than the sequence, ``attention.shared_kv`` once a layer that reads
    another's keys and values, beside ``kernel.pallas_blocked_attention`` /
    ``kernel.xla_blocked_attention``. Scopes, forward and backward:
    ``dattn.qkv``, ``dattn.attend`` (the repeat, the layout copies and the
    attention), ``dattn.combine`` (lambda, the subtraction, the norm),
    ``dattn.out``. A features mask zeroes the output at masked steps."""

    n_in: Optional[int] = None
    n_out: int = 0              # model width; inferred from the input when 0
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    layer_index: int = 0        # published index: lambda_init reads it
    window: int = 0             # 0: every key at or before the query
    kv_from: str = ""           # a vertex whose keys and values are read
    share_kv: bool = False      # hand [k | v] on as the value ``kv``
    block: int = 512
    eps: float = 1e-5           # of the norm after the subtraction
    weight_init: str = "xavier_fan_in"

    supports_stateful = False

    @property
    def extra_inputs(self):
        return ("kv",) if self.kv_from else ()

    @property
    def extra_input_refs(self):
        return (self.kv_from + ".kv",) if self.kv_from else ()

    def extra_input_sizes(self, it: InputType):
        return {"kv": 2 * self.n_kv_heads * self.head_dim}

    def shared_values(self, it: InputType):
        if not self.share_kv:
            return {}
        return {"kv": InputType.recurrent(
            2 * self.n_kv_heads * self.head_dim, it.timeseries_length)}

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return ("Wq", "Wo") if self.kv_from else ("Wqkv", "Wo")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def output_type(self, it: InputType) -> InputType:
        h, hkv = self.n_heads, self.n_kv_heads
        if h % 2 or hkv % 2 or h % hkv:
            raise ValueError(
                f"{h} query heads over {hkv} key/value heads: both pair up "
                "and the query heads are a multiple of the key/value heads")
        if self.window < 0:
            raise ValueError(f"a window of {self.window} keys")
        if self.kv_from and self.share_kv:
            raise ValueError("a layer that reads another's keys and values "
                             "has none of its own to hand on")
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        h, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        k_in, k_out, k_lam = jax.random.split(rng, 3)
        cols = h * dh if self.kv_from else (h + 2 * hkv) * dh
        name = "q" if self.kv_from else "qkv"

        def dense(key, n_in, n_out):
            return init_weights(key, (n_in, n_out), n_in, n_out,
                                self.weight_init, self.dist, dtype)

        params = {"W" + name: dense(k_in, d, cols),
                  "b" + name: jnp.zeros((cols,), dtype),
                  "Wo": dense(k_out, h * dh, self._width(it)),
                  "bo": jnp.zeros((self._width(it),), dtype),
                  "subln": jnp.ones((2 * dh,), dtype)}
        # the four lambda vectors N(0, 0.1^2), as the paper draws them
        for leaf, key in zip(("lambda_q1", "lambda_k1", "lambda_q2",
                              "lambda_k2"), jax.random.split(k_lam, 4)):
            params[leaf] = 0.1 * jax.random.normal(key, (dh,), dtype)
        return params, {}

    def apply(self, params, state, x, *, kv=None, train=False, rng=None,
              mask=None):
        from deeplearning4j_tpu.nn.conf.normalization import rms_norm
        from deeplearning4j_tpu.perf.compile_watch import bump_active

        x = dropout_input(x, self.dropout, train, rng)
        bsz, t, _ = x.shape
        h, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        f32 = jnp.float32
        bump_active("attention.differential")
        with jax.named_scope("dattn.qkv"):
            if self.kv_from:
                bump_active("attention.shared_kv")
                q = x @ params["Wq"] + params["bq"]
            else:
                qkv = x @ params["Wqkv"] + _key_bias_held(
                    params["bqkv"], h * dh, hkv * dh)
                q, kv = qkv[..., :h * dh], qkv[..., h * dh:]
        window = self.window if 0 < self.window < t else None
        if window:
            bump_active("attention.differential_windowed")
        with jax.named_scope("dattn.attend"):
            # query head i = 4 g + 2 p + s reads key head 2 g + s (s: which
            # softmax of the pair) and the group's 2d-wide value
            per = 2 * h // hkv           # query heads a key/value group
            k = kv[..., :hkv * dh].reshape(bsz, t, hkv // 2, 1, 2, dh)
            k = jnp.broadcast_to(k, (bsz, t, hkv // 2, per // 2, 2, dh))
            v = kv[..., hkv * dh:].reshape(bsz, t, hkv // 2, 1, 2 * dh)
            v = jnp.broadcast_to(v, (bsz, t, hkv // 2, per, 2 * dh))
            o = blocked_causal_attention(
                q.reshape(bsz, t, h, dh).transpose(0, 2, 1, 3),
                k.reshape(bsz, t, h, dh).transpose(0, 2, 1, 3),
                v.reshape(bsz, t, h, 2 * dh).transpose(0, 2, 1, 3),
                self.block, window)
            o = o.transpose(0, 2, 1, 3).reshape(bsz, t, h // 2, 2, 2 * dh)
        with jax.named_scope("dattn.combine"):
            start = differential_lambda_init(self.layer_index)
            lam = (jnp.exp(jnp.sum(params["lambda_q1"].astype(f32)
                                   * params["lambda_k1"].astype(f32)))
                   - jnp.exp(jnp.sum(params["lambda_q2"].astype(f32)
                                     * params["lambda_k2"].astype(f32)))
                   + start)
            diff = differential(o[..., 0, :].astype(f32),
                                o[..., 1, :].astype(f32), lam)
            o = ((1.0 - start) * rms_norm(diff, params["subln"], self.eps)
                 ).astype(x.dtype)
        with jax.named_scope("dattn.out"):
            out = o.reshape(bsz, t, h * dh) @ params["Wo"] + params["bo"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        if self.share_kv:
            return (out, {"kv": kv}), state
        return out, state


__all__ = ["SelfAttentionLayer", "TransformerEncoderBlock",
           "MultiHeadLatentAttention", "GatedAttention", "RotaryAttention",
           "DifferentialAttention", "differential_lambda_init",
           "rotate_interleaved",
           "blocked_causal_attention", "rotate_half_split", "yarn_inv_freq"]

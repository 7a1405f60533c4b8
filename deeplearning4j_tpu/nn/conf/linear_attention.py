"""Linear attention as registered layers: Kimi Delta Attention (KDA) and,
over the same chunked scan, Gated DeltaNet (one decay a head; see the
class).

Not in the 0.9.x reference line (it predates attention altogether); the
layer follows Kimi Linear (arXiv:2510.26692): a gated delta rule whose
decay is per CHANNEL. Per head, with d_k = d_v = ``head_dim``:

    q = L2norm(SiLU(conv(W_q x))) / sqrt(d_k),   k = L2norm(SiLU(conv(W_k x)))
    v = SiLU(conv(W_v x))                   (causal depthwise convolution)
    g_t = -exp(A_log) * softplus(W_f2 W_f1 x + dt_bias),   a_t = exp(g_t)
    b_t = sigmoid(W_b x)                                (a scalar per head)
    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t
    out = W_o (RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x))

TPU-native: the recurrence is never run token by token. ``chunked_kda``
cuts time into chunks of ``chunk`` steps. Inside a chunk everything is
matrix products: with G the running sum of g inside the chunk, the delta
rule's "pseudo values" U solve a unit-triangular system
``(I + A) U = b (V - (K exp(G)) S_0)`` with
``A[r, i] = b_r sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])`` (i < r; solved
by forward substitution over blocks of ``sub`` rows, ``chunk / sub``
dependent steps of matrix products: only the ``sub`` x ``sub`` diagonal
blocks are inverted outright, through their finite Neumann series; an
explicit inverse of the whole chunk overflows float32 where the keys are
alike), and the output is ``(Q exp(G)) S_0 + P U`` with P the same decayed
product of q and k (i <= r). Only the chunk states S_0 are carried, by one
``lax.scan`` over groups of chunks, in float32. The decayed products are
exact at ANY decay: blocks of
``sub`` x ``sub`` on the diagonal are written out channel by channel
(``exp(G_r - G_i)`` itself, never a quotient of two exponentials), blocks
below it go through the MXU around a reference row at which both factors
are at most 1.

Two executions of that one algorithm, chosen at trace time by what the
code can observe (``perf.pallas.take("kda_scan", supported(...))``, the
``kernel.pallas_kda_scan`` / ``kernel.xla_kda_scan`` counters say which):
plain ``jax.numpy`` / ``lax`` here, XLA writing the backward pass, for any
shape and backend; and, on a TPU for heads of 128 in chunks of 64, the
Pallas kernels of ``perf/pallas/kda.py``, forward and backward, which keep
a chunk's terms in VMEM.

The layers' input path (the short convolution, SiLU, the q/k head norms
and KDA's decay, between the projections' products and the scan) has two
executions in the same way (``take("kda_inputs", ...)``,
``kernel.pallas_kda_inputs`` / ``kernel.xla_kda_inputs``, once a call of a
layer): the ``jax.numpy`` lines of ``KimiDeltaAttention.apply`` and
``GatedDeltaNet.apply`` (``short_conv.causal_depthwise_conv``, ``_l2norm``) anywhere,
every CPU run included, and the tests' reference; on a TPU, for heads of
128 or 256, at most 4 taps and bfloat16 or float32, one kernel forward and
one backward of ``perf/pallas/kda_inputs.py`` that read the products'
(time, heads x K) outputs and hand the scan's kernels their (batch, heads,
time, K) windows (``kda.kda_scan_heads_major``: no transpose of q, k, v, g
in or of dq, dk, dv, dg out). Measured in the benchmark's two token cells:
PERF.md §5-6, PR 31.

Rematerialised (``remat=``), both layers keep what their scan's kernels
name (``kda.KEPT``: o, the chunks' entry states, and the chunks' solved u
and decayed scores that the backward kernel reads where it made them a
second time, float32) and outputs of
the wide projections in front of the input path (``PROJECTIONS_KEPT``: KDA's
``x Wq``, ``x Wk`` and the decay's latent ``x Wf1``, Gated DeltaNet's
``x Wqkvz``, in the compute type as the products wrote them, named in both
executions): ``remat_keeps`` is the two together and ``remat_kept_bytes``
their cost. The projections' outputs are the only residuals of the input
path (the kernel's backward makes the convolution, SiLU and norm again from
them, as ``causal_depthwise_conv``'s transpose reads them), so the backward
pass runs none of the named products a second time and makes q, k, v, g
again with the input kernel alone. NOT named: KDA's ``x Wv`` (with it the
TPU's scheduler reorders the whole Kimi step: 0.58 GB more held for no
shorter a step, PERF.md §6, PR 41; v needs no norm and is the cheapest to
make again), q, k, v, g, b themselves (1.34 GB over Kimi's four layers for
the 3.7 ms a step of ``kda_inputs_fwd``) and the narrow products (``Wf2``,
``Wb`` / ``Wba``, the gate's): those are made again. The scan's results are
read: ``kda_scan_fwd`` once a layer and step (PERF.md §5-6, PRs 36, 41, 49).
``remat="nothing_saveable"`` keeps nothing; the ``jax.numpy`` scan names
nothing of its own and checkpoints its groups of chunks.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BaseLayer, dropout_input, register_layer,
)
from deeplearning4j_tpu.nn.conf.normalization import rms_norm
from deeplearning4j_tpu.nn.conf.short_conv import causal_depthwise_conv
from deeplearning4j_tpu.nn.initializers import init_weights
from deeplearning4j_tpu.perf import pallas as pk
from deeplearning4j_tpu.perf.pallas import kda as kda_kernels
from deeplearning4j_tpu.perf.pallas import kda_inputs


# the wide projections' outputs by their ``checkpoint_name`` (one name, several
# values): what ``kda_inputs`` and ``causal_depthwise_conv`` keep as residuals
PROJECTIONS_KEPT = ("delta_rule.projections",)


def _kept_projection(product):
    """A projection's output, named as the product wrote it."""
    return checkpoint_name(product, PROJECTIONS_KEPT[0])


def _kept_bytes(it: InputType, heads: int, head_dim: int, chunk: int,
                columns: int, dtype) -> int:
    """Bytes a rematerialised delta-rule layer keeps for one sequence of
    input type ``it``: ``kda.KEPT`` where the scan's kernels take the head
    and the chunk, and ``PROJECTIONS_KEPT``, ``columns`` numbers a step in
    the compute type, at the length the kernels pad to where they run."""
    time = it.timeseries_length or 1
    scan = kda_kernels.kept_bytes(time, heads, head_dim, chunk)
    if scan:
        time += (-time) % kda_kernels.CHUNK
    return scan + time * columns * jnp.dtype(dtype).itemsize


def _l2norm(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(jnp.square(x32), -1, keepdims=True) + eps)


def _take_fused_inputs(x, spec, taps: int, chunk: int):
    """Does the layer's input path run as the ``kda_inputs`` kernels (and
    the scan behind them as ``kda_scan``'s, on heads-major operands)?
    Counted once a call (``kernel.pallas_kda_inputs`` /
    ``kernel.xla_kda_inputs``). Returns the length the kernels run at, a
    multiple of the scan's chunk, or 0: the ``jax.numpy`` lines."""
    bsz, t, _ = x.shape
    padded = t + (-t) % chunk
    if pk.take("kda_inputs", chunk == kda_kernels.CHUNK
               and pk.enabled("kda_scan")
               and kda_inputs.supported(x.dtype, bsz, padded, spec, taps)):
        return padded
    return 0


def _pad_time(x, padded: int):
    """Zero steps appended: they reach no step before them."""
    t = x.shape[1]
    return x if padded == t else jnp.pad(x, ((0, 0), (0, padded - t), (0, 0)))


def _scan_heads_major(q, k, v, g, b, t: int):
    """``chunked_kda`` for the operands ``kda_inputs`` wrote: (batch,
    heads, padded time, K), b (batch, padded time, heads) float32."""
    pk.take("kda_scan")
    return kda_kernels.kda_scan_heads_major(q, k, v, g, b)[:, :t]


def _decay_start(key_rates, key_steps, rates: int, steps: int, dtype):
    """(``A_log``, ``dt_bias``), the usual start of a gated delta layer:
    ``rates`` decay rates log-uniform in [1, 16], ``steps`` step sizes
    log-uniform in [1e-3, 1e-1] (the bias is their inverse softplus)."""
    a_log = jnp.log(jax.random.uniform(key_rates, (rates,), dtype, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(key_steps, (steps,), dtype)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


def _decayed_scores(xs, k, g_cum, sub: int):
    """For every x in the stack ``xs`` (n, ..., C, K):
    M[r, i] = sum_c x[r, c] k[i, c] exp(G[r, c] - G[i, c]) for i <= r and
    0 above the diagonal. ``k``, ``g_cum`` (..., C, K); G is non-increasing
    along C, so every exponent that is taken is <= 0. The decay factors
    are made once for the whole stack."""
    *lead, c, kd = k.shape
    ns = c // sub
    xs_b = xs.reshape(xs.shape[0], *lead, ns, sub, kd)
    ks = k.reshape(*lead, ns, sub, kd)
    gs = g_cum.reshape(*lead, ns, sub, kd)
    # diagonal blocks, channel by channel
    dg = gs[..., :, None, :] - gs[..., None, :, :]
    low = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    k_decayed = ks[..., None, :, :] * jnp.exp(jnp.where(low, dg, -jnp.inf))
    diag = jnp.sum(xs_b[..., :, None, :] * k_decayed, -1)
    # blocks below the diagonal: around the sub-block's first row R both
    # exp(G_r - R) and exp(R - G_i) are <= 1 (i lies before the sub-block)
    ref = gs[..., :, :1, :]
    xd = xs_b * jnp.exp(gs - ref)
    kd_all = k[..., None, :, :] * jnp.exp(
        jnp.minimum(ref - g_cum[..., None, :, :], 0.0))
    off = jnp.einsum("n...src,...sic->n...sri", xd, kd_all)
    before = (jnp.arange(c)[None, :] < (jnp.arange(ns) * sub)[:, None])
    off = jnp.where(before[:, None, :], off, 0.0)
    eye = jnp.eye(ns, dtype=diag.dtype)
    placed = jnp.einsum("...srj,st->...srtj", diag, eye)
    full = off + placed.reshape(off.shape)
    return full.reshape(xs.shape[0], *lead, c, c)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower-triangular ``a`` (..., n, n), n a
    power of two: a is nilpotent (a^n = 0), so the Neumann series
    sum_m (-a)^m ends and factors into log2(n) products,
    (I - a)(I + a^2)(I + a^4)...: batched matrix products where a
    triangular solve is n dependent steps. The inverse's entries can grow
    like 2^n where the rows of ``a`` are alike (keys that point the same
    way), so this is for SMALL n only: see ``_solve_unit_lower``."""
    n = a.shape[-1]
    hi = lax.Precision.HIGHEST
    inv, power = jnp.eye(n, dtype=a.dtype) - a, a
    for _ in range(max(n.bit_length() - 2, 0)):
        power = jnp.matmul(power, power, precision=hi)
        inv = inv + jnp.matmul(inv, power, precision=hi)
    return inv


def _solve_unit_lower(a, rhs, block: int):
    """x with (I + a) x = rhs for a strictly lower-triangular ``a``
    (..., C, C), by forward substitution over blocks of ``block`` rows:
    x_s = (I + a_ss)^-1 (rhs_s - a[s, :s] x[:s]). Only the small diagonal
    blocks are inverted outright (all at once); across blocks every x_s
    comes from the x before it, as in the recurrence itself, which keeps
    the solve as stable as the recurrence at any likeness of the keys
    (an explicit 64 x 64 inverse overflows float32 there). C / block
    dependent steps of matrix products."""
    c = a.shape[-1]
    nb = c // block
    hi = lax.Precision.HIGHEST
    inv = _unit_lower_inverse(jnp.stack(
        [a[..., s * block:(s + 1) * block, s * block:(s + 1) * block]
         for s in range(nb)], -3))                    # (..., nb, b, b)
    out = []
    for s in range(nb):
        r = rhs[..., s * block:(s + 1) * block, :]
        if s:
            r = r - jnp.matmul(a[..., s * block:(s + 1) * block, :s * block],
                               jnp.concatenate(out, -2), precision=hi)
        out.append(jnp.matmul(inv[..., s, :, :], r, precision=hi))
    return jnp.concatenate(out, -2)


def _chunk_terms(qc, kc, vc, gc, bc, sub: int):
    """Everything of a chunk that does not need the carried state. Inputs
    (..., C, K|V) and ``bc`` (..., C); float32."""
    c = qc.shape[-2]
    g_cum = jnp.cumsum(gc, axis=-2)
    p, a = _decayed_scores(jnp.stack([qc, kc]), kc, g_cum, sub)
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                  a * bc[..., :, None], 0.0)
    gamma = jnp.exp(g_cum)
    rhs = jnp.concatenate([kc * gamma, vc], -1) * bc[..., :, None]
    sol = _solve_unit_lower(a, rhs, sub)
    kdim = kc.shape[-1]
    w, uv = sol[..., :kdim], sol[..., kdim:]
    g_end = g_cum[..., -1:, :]
    return (p, w, uv, qc * gamma, kc * jnp.exp(g_end - g_cum),
            jnp.exp(g_end[..., 0, :]))


def _state_step(s, terms):
    """One chunk's ``_chunk_terms`` met with the state (..., K, V) at its
    entry: the state at its exit and the chunk's output."""
    p, w, uv, qd, kd, decay = terms
    u = uv - jnp.matmul(w, s)
    o = jnp.matmul(qd, s) + jnp.matmul(p, u)
    s = decay[..., :, None] * s + jnp.matmul(jnp.swapaxes(kd, -1, -2), u)
    return s, o


def chunked_kda(q, k, v, g, b, chunk: int = 64, sub: int = 8,
                group: int = 2):
    """The KDA recurrence from a zero state, chunk-wise. ``q``, ``k``,
    ``g`` (batch, time, heads, K), ``v`` (batch, time, heads, V), ``b``
    (batch, time, heads), in any float type (a group is cast to float32
    as it is taken up); returns o (batch, time, heads, V) in float32.
    ``time`` need not be a multiple of ``chunk`` (steps with k = 0, b = 0,
    g = 0 are appended: they leave the state as it is). One ``lax.scan``
    runs over groups of ``group`` chunks under ``jax.checkpoint``: a
    group's state-free terms (the channel-by-channel diagonal blocks are
    chunks x heads x sub^2 x K numbers) are made, used for its chunks'
    state updates and dropped, forward and backward, and the backward
    pass keeps one state a group. ``sub`` is the block of both the
    written-out diagonal and the forward substitution: 8 keeps the solve
    within float32 rounding of the recurrence at any likeness of the keys
    (16 reads 1e-3 off, 32 overflows). Where ``perf.pallas.kda.supported``
    takes the shape and the ``kda_scan`` family is on (a TPU, or forced),
    the same chunks run as that module's kernels instead of the scan
    below; ``group`` is then not used."""
    bsz, t, h, kdim = q.shape
    vdim = v.shape[-1]
    if chunk % sub or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} has to be a power of two and a "
                         f"multiple of sub {sub}")
    pad = (-t) % chunk
    n = (t + pad) // chunk
    if pk.take("kda_scan", kda_kernels.supported(q, k, v, g, b, chunk, sub)):
        if pad:
            q, k, v, g, b = (jnp.pad(a, ((0, 0), (0, pad))
                                     + ((0, 0),) * (a.ndim - 2))
                             for a in (q, k, v, g, b))
        return kda_kernels.kda_scan(q, k, v, g.astype(jnp.float32),
                                    b.astype(jnp.float32))[:, :t]

    def chunks(a):                       # (B, T, H, X) -> (N, B, H, C, X)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((bsz, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 2, -2 if a.ndim == 5 else -1),
                            1, 0)

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g)
    bc = chunks(b)                       # (N, B, H, C)
    per = max(d for d in range(1, min(group, n) + 1) if n % d == 0)

    def grouped(a):                      # (N, ...) -> (N / per, per, ...)
        return a.reshape((n // per, per) + a.shape[1:])

    def group_step(s, xs):
        """``per`` chunks: their state-free terms, then the state through
        them one chunk after another."""
        terms = _chunk_terms(*(a.astype(jnp.float32) for a in xs), sub=sub)
        outs = []
        for i in range(per):
            s, o = _state_step(s, tuple(a[i] for a in terms))
            outs.append(o)
        return s, jnp.stack(outs)

    s0 = jnp.zeros((bsz, h, kdim, vdim), jnp.float32)
    _, o = lax.scan(jax.checkpoint(group_step), s0,
                    tuple(grouped(a) for a in (qc, kc, vc, gc, bc)))
    o = o.reshape((n,) + o.shape[2:])    # (N, B, H, C, V)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)   # (B, N, C, H, V)
    return o.reshape(bsz, n * chunk, h, vdim)[:, :t]


@register_layer
@dataclasses.dataclass(frozen=True)
class KimiDeltaAttention(BaseLayer):
    """Kimi Delta Attention over (batch, time, features): width-preserving
    token mixing with a (heads, head_dim, head_dim) state instead of a
    cache. ``low_rank`` is the inner width of the decay and output-gate
    projections (0: ``head_dim``). A features mask zeroes the output at
    masked steps; the recurrence itself still runs over them (right-padded
    batches are exact, masks inside a sequence are not supported)."""

    n_in: Optional[int] = None
    n_out: int = 0              # model width; inferred from the input when 0
    n_heads: int = 4
    head_dim: int = 64
    conv_size: int = 4
    low_rank: int = 0
    chunk: int = 64
    eps: float = 1e-5
    weight_init: str = "xavier_fan_in"

    supports_stateful = False   # no rnn_time_step carry (yet)
    remat_keeps = kda_kernels.KEPT + PROJECTIONS_KEPT

    def remat_kept_bytes(self, it: InputType, dtype=jnp.float32) -> int:
        # x Wq, x Wk and the decay's latent x Wf1
        columns = (2 * self.n_heads * self.head_dim
                   + (self.low_rank or self.head_dim))
        return _kept_bytes(it, self.n_heads, self.head_dim, self.chunk,
                           columns, dtype)

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return ("Wq", "Wk", "Wv", "Wf1", "Wf2", "Wb", "Wg1", "Wg2", "Wo")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def output_type(self, it: InputType) -> InputType:
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        width = self._width(it)
        inner = self.n_heads * self.head_dim
        r = self.low_rank or self.head_dim
        keys = iter(jax.random.split(rng, 16))

        def dense(n_in, n_out):
            return init_weights(next(keys), (n_in, n_out), n_in, n_out,
                                self.weight_init, self.dist, dtype)

        p = {"Wq": dense(d, inner), "Wk": dense(d, inner),
             "Wv": dense(d, inner)}
        for name in ("conv_q", "conv_k", "conv_v"):
            p[name] = (jax.random.normal(next(keys), (self.conv_size, inner),
                                         dtype)
                       / math.sqrt(self.conv_size))
        p["Wf1"], p["Wf2"] = dense(d, r), dense(r, inner)
        p["A_log"], p["dt_bias"] = _decay_start(next(keys), next(keys),
                                                self.n_heads, inner, dtype)
        p["Wb"] = dense(d, self.n_heads)
        p["Wg1"], p["Wg2"] = dense(d, r), dense(r, inner)
        p["o_norm"] = jnp.ones((self.head_dim,), dtype)
        p["Wo"] = dense(inner, width)
        return p, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        bsz, t, _ = x.shape
        h, dk = self.n_heads, self.head_dim

        def heads(a):
            return a.reshape(bsz, t, h, dk)

        f32 = jnp.float32
        spec = kda_inputs.Spec(srcs=((0, 0, 0), (1, 1, 0), (2, 2, 0)),
                               decay=(3, 0), key_heads=h, rep=1, head_dim=dk)
        padded = _take_fused_inputs(x, spec, self.conv_size, self.chunk)
        xp = _pad_time(x, padded or t)
        with jax.named_scope("kda.conv"):
            if padded:
                # one kernel from the products to the scan's heads-major
                # windows (perf/pallas/kda_inputs.py)
                q, k, v, g = kda_inputs.kda_inputs(
                    (_kept_projection(xp @ params["Wq"]),
                     _kept_projection(xp @ params["Wk"]),
                     xp @ params["Wv"],
                     _kept_projection(xp @ params["Wf1"]) @ params["Wf2"]),
                    tuple(params[c].astype(f32)
                          for c in ("conv_q", "conv_k", "conv_v")),
                    (jnp.repeat(-jnp.exp(params["A_log"].astype(f32)),
                                dk)[None],
                     params["dt_bias"].astype(f32)[None]), spec)
            else:
                q, k = (jax.nn.silu(causal_depthwise_conv(
                    _kept_projection(x @ params[w]), params[c]))
                        for w, c in (("Wq", "conv_q"), ("Wk", "conv_k")))
                v = jax.nn.silu(causal_depthwise_conv(x @ params["Wv"],
                                                      params["conv_v"]))
                # normalised in float32, handed on in the compute type
                q = (_l2norm(heads(q))
                     * (1.0 / math.sqrt(dk))).astype(x.dtype)
                k = _l2norm(heads(k)).astype(x.dtype)
                f = (_kept_projection(x @ params["Wf1"])
                     @ params["Wf2"]).astype(f32)
                g = -jnp.exp(params["A_log"].astype(f32))[:, None] * heads(
                    jax.nn.softplus(f + params["dt_bias"].astype(f32)))
            b = jax.nn.sigmoid((xp @ params["Wb"]).astype(f32))
        with jax.named_scope("kda.scan"):
            o = (_scan_heads_major(q, k, v, g, b, t) if padded
                 else chunked_kda(q, k, heads(v), g, b, chunk=self.chunk))
        with jax.named_scope("kda.out_gate"):
            gate = jax.nn.sigmoid(
                heads((x @ params["Wg1"]) @ params["Wg2"]).astype(jnp.float32))
            o = rms_norm(o, params["o_norm"], self.eps) * gate
            out = o.reshape(bsz, t, h * dk).astype(x.dtype) @ params["Wo"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        return out, state


@register_layer
@dataclasses.dataclass(frozen=True)
class GatedDeltaNet(BaseLayer):
    """Gated DeltaNet over (batch, time, features), as the Qwen3-Next
    family runs it (``model_type`` ``qwen3_next``): the delta rule with ONE
    decay a value head and step, fewer key heads than value heads, one
    convolution over q|k|v and a SiLU-gated per-head RMSNorm at the output.
    With h_k = ``n_key_heads``, h_v = ``n_value_heads`` (a multiple of h_k)
    and d = ``head_dim`` for keys and values alike:

        [q, k, v, z] = W_qkvz x     (h_k d + h_k d + h_v d + h_v d columns)
        [b, a] = W_ba x                                  (h_v + h_v columns)
        [q, k, v] = SiLU(conv([q, k, v]))  (causal, depthwise, no bias)
        q = L2norm(q) / sqrt(d),  k = L2norm(k)   (each head serves h_v / h_k
                                                   consecutive value heads)
        beta_t = sigmoid(b_t),  g_t = -exp(A_log) * softplus(a_t + dt_bias)
        S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
        o_t = S_t^T q_t
        out = W_o (o_norm * o_t / sqrt(mean(o_t^2) + eps) * SiLU(z_t))

    That recurrence is KDA's with the decay alike in every channel, so it
    runs through ``chunked_kda`` (and on a TPU through the ``kda_scan``
    kernels, which are exact at any decay) with ``g`` spread over the
    head's channels; there is no scalar form of the kernels. The fused
    projections keep their columns in the order written above, plain
    blocks. A features mask zeroes the output at masked steps; the
    recurrence itself still runs over them (right-padded batches are
    exact)."""

    n_in: Optional[int] = None
    n_out: int = 0              # model width; inferred from the input when 0
    n_key_heads: int = 2
    n_value_heads: int = 4
    head_dim: int = 64
    conv_size: int = 4
    chunk: int = 64
    eps: float = 1e-6
    weight_init: str = "xavier_fan_in"

    supports_stateful = False   # no rnn_time_step carry (yet)
    remat_keeps = kda_kernels.KEPT + PROJECTIONS_KEPT

    def remat_kept_bytes(self, it: InputType, dtype=jnp.float32) -> int:
        # x Wqkvz
        columns = 2 * (self.n_key_heads + self.n_value_heads) * self.head_dim
        return _kept_bytes(it, self.n_value_heads, self.head_dim, self.chunk,
                           columns, dtype)

    def input_kind(self):
        return "rnn"

    def is_recurrent(self):
        return True

    def regularizable(self):
        return ("Wqkvz", "Wba", "Wo")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.size

    def output_type(self, it: InputType) -> InputType:
        if self.n_value_heads % self.n_key_heads:
            raise ValueError(
                f"{self.n_value_heads} value heads are no multiple of "
                f"{self.n_key_heads} key heads")
        return InputType.recurrent(self._width(it), it.timeseries_length)

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.size
        hk, hv, dh = self.n_key_heads, self.n_value_heads, self.head_dim
        ks = jax.random.split(rng, 6)

        def dense(key, n_in, n_out):
            return init_weights(key, (n_in, n_out), n_in, n_out,
                                self.weight_init, self.dist, dtype)

        a_log, dt_bias = _decay_start(ks[3], ks[4], hv, hv, dtype)
        return {
            "Wqkvz": dense(ks[0], d, 2 * (hk + hv) * dh),
            "Wba": dense(ks[1], d, 2 * hv),
            "conv": (jax.random.normal(ks[2], (self.conv_size,
                                               (2 * hk + hv) * dh), dtype)
                     / math.sqrt(self.conv_size)),
            "A_log": a_log, "dt_bias": dt_bias,      # one each a value head
            "o_norm": jnp.ones((dh,), dtype),
            "Wo": dense(ks[5], hv * dh, self._width(it)),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        bsz, t, _ = x.shape
        hk, hv, dh = self.n_key_heads, self.n_value_heads, self.head_dim
        f32 = jnp.float32
        spec = kda_inputs.Spec(srcs=((0, 0, 0), (0, 0, hk), (0, 0, 2 * hk)),
                               decay=None, key_heads=hk, rep=hv // hk,
                               head_dim=dh)
        padded = _take_fused_inputs(x, spec, self.conv_size, self.chunk)
        xp = _pad_time(x, padded or t)
        with jax.named_scope("gdn.conv"):
            # z, which the output gate reads, is a column range of it
            qkvz = _kept_projection(xp @ params["Wqkvz"])
            if padded:
                # the kernels KDA takes: q, k, v are three column ranges of
                # one product, a q/k head is written to the value heads it
                # serves (perf/pallas/kda_inputs.py)
                q, k, v = kda_inputs.kda_inputs(
                    (qkvz,), (params["conv"].astype(f32),), (), spec)
                z = qkvz[:, :t, (2 * hk + hv) * dh:].reshape(bsz, t, hv, dh)
            else:
                mixed = jax.nn.silu(causal_depthwise_conv(
                    qkvz[..., :(2 * hk + hv) * dh], params["conv"]))
                z = qkvz[..., (2 * hk + hv) * dh:].reshape(bsz, t, hv, dh)
                q = mixed[..., :hk * dh].reshape(bsz, t, hk, dh)
                k = mixed[..., hk * dh:2 * hk * dh].reshape(bsz, t, hk, dh)
                v = mixed[..., 2 * hk * dh:].reshape(bsz, t, hv, dh)
                # normalised in float32, handed on in the compute type; a key
                # head's q and k serve h_v / h_k value heads that lie together
                q = (_l2norm(q) * (1.0 / math.sqrt(dh))).astype(x.dtype)
                k = _l2norm(k).astype(x.dtype)
                if hv != hk:
                    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
            ba = (xp @ params["Wba"]).astype(f32)
            b = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(
                ba[..., hv:] + params["dt_bias"].astype(f32))
        with jax.named_scope("gdn.scan"):
            # one decay a head, spread over the head's channels
            if padded:
                o = _scan_heads_major(q, k, v, jnp.broadcast_to(
                    jnp.swapaxes(g, 1, 2)[..., None], q.shape), b, t)
            else:
                o = chunked_kda(q, k, v,
                                jnp.broadcast_to(g[..., None], q.shape), b,
                                chunk=self.chunk)
        with jax.named_scope("gdn.out_gate"):
            o = rms_norm(o, params["o_norm"], self.eps) * jax.nn.silu(
                z.astype(f32))
            out = o.reshape(bsz, t, hv * dh).astype(x.dtype) @ params["Wo"]
        if mask is not None:             # masked steps emit zeros
            out = out * mask[..., None].astype(out.dtype)
        return out, state


__all__ = ["KimiDeltaAttention", "GatedDeltaNet", "chunked_kda",
           "causal_depthwise_conv"]

"""Plain reference of the ``kimi_linear_48b_a3b_ep32`` configuration.

Kimi-Linear-48B-A3B-Instruct (``config.json`` of the Hugging Face
repository, ``model_type`` ``kimi_linear``; layer equations: Kimi Linear,
arXiv:2510.26692) on the training path: forward, loss, gradients and Adam
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
With x a block's input every block is

    h = x + Attn(RMSNorm(x)),   y = h + FFN(RMSNorm(h))

and a final RMSNorm and an untied head follow. ``Attn`` is Kimi Delta
Attention (KDA) in the layers ``linear_attn_config.kda_layers`` names and
multi-head latent attention without rotation (``mla_use_nope``) in its
``full_attn_layers``; ``FFN`` is a dense SwiGLU in the first
``first_k_dense_replace`` layers and routed experts plus one shared expert
in the rest.

KDA, per head (d_k = d_v = ``linear_attn_config.head_dim``), token by
token with ``lax.scan`` over time, no chunk algebra:

    q = L2norm(SiLU(conv4(W_q x))) / sqrt(d_k),  k = L2norm(SiLU(conv4(W_k x)))
    v = SiLU(conv4(W_v x))               (causal depthwise convolution of 4)
    g_t = -exp(A_log) * softplus(W_f2 W_f1 x + dt_bias),  a_t = exp(g_t)
    b_t = sigmoid(W_b x)                                  (a scalar per head)
    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t
    out = W_o (RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x))

MLA, NoPE: ``[c; k_r] = W_kva x`` (kv_lora_rank + qk_rope_head_dim),
``c = RMSNorm(c)``; per head ``k = [W_kb^K c; k_r]`` (k_r shared by the
heads, no rotation), ``v = W_kb^V c``, ``q = W_q x``, causal
``softmax(q k^T / sqrt(d_q)) v``, then ``W_o``.

Routed experts: ``s = sigmoid(W_r x)`` over ALL published experts, top-k of
``s + bias``, weights ``routed_scaling_factor * s_i / sum_topk s_j``,
``y = sum over chosen experts HELD HERE of w_i E_i(x) + E_shared(x)`` with
``E(x) = W_down(SiLU(W_gate x) * W_up x)``.

Departures from the published description, each to match what the
configuration states it runs:

* this chip's share of a 32-way deployment: ``num_experts`` experts of the
  ``published.num_experts`` are held (``expert_offset`` onward); what the
  absent experts would add is left out, and that partial sum goes on;
* the vocabulary is a slice (``vocab_size`` rows): ids, logits and loss are
  over the slice;
* the low-rank width of W_f1 and W_g1 is not in the config: the head size
  (``assumed``); W_g2 has no bias; the router's selection bias is a
  constant zero (its load-driven update is not in the config);
* the recurrence is rematerialised over segments of 64 steps and each
  block, the attention over blocks of queries: memory devices only, the
  arithmetic stays token by token and the score matrix whole.

``precision`` lowers only the operands of matrix products (``bf16``:
rounded to bfloat16; ``fp8``: scaled per tensor and rounded to
float8_e4m3fn, the control of the correctness check); products accumulate
in float32 and the recurrence's state is float32 throughout.

Nothing of ``deeplearning4j_tpu`` is imported. Parameter names follow the
zoo builder's vertex names (``l2_attn/Wq``) only so that the benchmark can
hand the same seeded weights to both sides."""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

SEGMENT = 64          # steps of the recurrence rematerialised together
QUERY_BLOCK = 256     # queries whose scores are alive together


# ---------------------------------------------------------------- structure
def dims(cfg: dict) -> dict:
    la = cfg["linear_attn_config"]
    return {
        "d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "kda_heads": la["num_heads"], "kda_dim": la["head_dim"],
        "conv": la["short_conv_kernel_size"],
        "low_rank": cfg.get("kda_low_rank_width", la["head_dim"]),
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "kv_rank": cfg["kv_lora_rank"],
        "dense_ff": cfg["intermediate_size"],
        "expert_ff": cfg["moe_intermediate_size"],
        "shared_ff": cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        "experts_held": cfg["num_experts"],
        "experts_total": cfg["published"]["num_experts"],
        "expert_offset": cfg.get("expert_offset", 0),
        "top_k": cfg["num_experts_per_token"],
        "scale": cfg["routed_scaling_factor"],
        "eps": cfg["rms_norm_eps"],
    }


def blocks(cfg: dict) -> List[dict]:
    """Every block kept: its published index (from 1), its attention kind
    and its feed-forward kind."""
    la = cfg["linear_attn_config"]
    out = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if i in la["kda_layers"]:
            attn = "kda"
        elif i in la["full_attn_layers"]:
            attn = "mla"
        else:
            raise ValueError(f"layer {i} is in neither list of "
                             "linear_attn_config")
        moe = (i > cfg["first_k_dense_replace"]
               and (i - 1) % cfg["moe_layer_freq"] == 0)
        out.append({"index": i, "name": f"l{i}", "attn": attn,
                    "ffn": "moe" if moe else "dense"})
    return out


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Every leaf's shape, in a fixed order (the order seeds are folded
    in)."""
    m = dims(cfg)
    d, hk, dk = m["d"], m["kda_heads"], m["kda_dim"]
    out = {"embed/W": (m["vocab"], d)}
    for blk in blocks(cfg):
        n = blk["name"]
        out[f"{n}_attn_norm/g"] = (d,)
        if blk["attn"] == "kda":
            a = f"{n}_attn/"
            for w in ("Wq", "Wk", "Wv"):
                out[a + w] = (d, hk * dk)
            for w in ("conv_q", "conv_k", "conv_v"):
                out[a + w] = (m["conv"], hk * dk)
            out[a + "Wf1"] = (d, m["low_rank"])
            out[a + "Wf2"] = (m["low_rank"], hk * dk)
            out[a + "A_log"] = (hk,)
            out[a + "dt_bias"] = (hk * dk,)
            out[a + "Wb"] = (d, hk)
            out[a + "Wg1"] = (d, m["low_rank"])
            out[a + "Wg2"] = (m["low_rank"], hk * dk)
            out[a + "o_norm"] = (dk,)
            out[a + "Wo"] = (hk * dk, d)
        else:
            a = f"{n}_attn/"
            h = m["heads"]
            out[a + "Wq"] = (d, h * (m["nope"] + m["rope"]))
            out[a + "Wkva"] = (d, m["kv_rank"] + m["rope"])
            out[a + "kv_norm"] = (m["kv_rank"],)
            out[a + "Wkvb"] = (m["kv_rank"], h * (m["nope"] + m["v_dim"]))
            out[a + "Wo"] = (h * m["v_dim"], d)
        out[f"{n}_ffn_norm/g"] = (d,)
        f = f"{n}_ffn/"
        if blk["ffn"] == "dense":
            out[f + "Wgate"] = (d, m["dense_ff"])
            out[f + "Wup"] = (d, m["dense_ff"])
            out[f + "Wdown"] = (m["dense_ff"], d)
        else:
            e, ff = m["experts_held"], m["expert_ff"]
            out[f + "Wr"] = (d, m["experts_total"])
            out[f + "Wgate"] = (e, d, ff)
            out[f + "Wup"] = (e, d, ff)
            out[f + "Wdown"] = (e, ff, d)
            out[f + "Sgate"] = (d, m["shared_ff"])
            out[f + "Sup"] = (d, m["shared_ff"])
            out[f + "Sdown"] = (m["shared_ff"], d)
    out["final_norm/g"] = (d,)
    out["head/W"] = (d, m["vocab"])
    return out


def count_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


# ------------------------------------------------------------------- FLOPs
def layers(cfg: dict) -> List[dict]:
    """What ``harness.flops`` counts, per token of a sequence of
    ``cfg["sequence_length"]``: every product as ``kind: "dense"`` with
    ``positions``. Projections once a token; MLA's scores and values at the
    causal mean of (T + 1) / 2 keys a query; KDA as the recurrence's three
    d_k x d_v products a head and token (decay-and-read k^T S, the rank-one
    update, the read S^T q); routed experts top_k x held / total a token.
    Embedding gather, convolution of 4, norms, gates and the router's
    top-k are not matrix products and are left out."""
    m = dims(cfg)
    t = cfg["sequence_length"]
    d = m["d"]
    out = []

    def add(name, n_in, n_out, positions=1.0):
        out.append({"name": name, "kind": "dense", "n_in": n_in,
                    "n_out": n_out, "positions": positions})

    for blk in blocks(cfg):
        n = blk["name"]
        if blk["attn"] == "kda":
            hk, dk, r = m["kda_heads"], m["kda_dim"], m["low_rank"]
            add(n + "_attn.qkv", d, 3 * hk * dk)
            add(n + "_attn.f", d, r), add(n + "_attn.f2", r, hk * dk)
            add(n + "_attn.g", d, r), add(n + "_attn.g2", r, hk * dk)
            add(n + "_attn.b", d, hk)
            add(n + "_attn.recurrence", dk, dk, 3 * hk)
            add(n + "_attn.o", hk * dk, d)
        else:
            h = m["heads"]
            dq = m["nope"] + m["rope"]
            add(n + "_attn.q", d, h * dq)
            add(n + "_attn.kva", d, m["kv_rank"] + m["rope"])
            add(n + "_attn.kvb", m["kv_rank"], h * (m["nope"] + m["v_dim"]))
            add(n + "_attn.scores", dq, (t + 1) / 2.0, h)
            add(n + "_attn.values", (t + 1) / 2.0, m["v_dim"], h)
            add(n + "_attn.o", h * m["v_dim"], d)
        if blk["ffn"] == "dense":
            add(n + "_ffn", d, 3 * m["dense_ff"])
        else:
            add(n + "_ffn.router", d, m["experts_total"])
            add(n + "_ffn.shared", d, 3 * m["shared_ff"])
            add(n + "_ffn.routed", d, 3 * m["expert_ff"],
                m["top_k"] * m["experts_held"] / m["experts_total"])
    add("head", d, m["vocab"])
    return out


def kda_scan_cost(cfg: dict, tokens: int, chunk: int = 64,
                  sub: int = 8) -> dict:
    """Operations and bytes one KDA layer's ``kda.scan`` scope needs for
    ``tokens`` tokens IN THE FORM THE PROGRAM COMPUTES IT (chunks of
    ``chunk``; diagonal blocks of ``sub`` written out channel by channel;
    the triangular system by forward substitution over blocks of ``sub``),
    forward once. A training step runs it forward twice (the layer is
    rematerialised) and backward once, taken as twice a forward: 4 x.

    Per head and chunk (C = chunk, K = d_k, V = d_v, n = C / sub):
    * diagonal blocks: n sub^2 K decay factors (a subtraction, an
      exponential and the product with k: 3) shared by the two score
      matrices, each a multiply and an add more: 7 n sub^2 K;
    * blocks below the diagonal: the keys scaled once a sub-block (n C K
      exponentials and products: 2 n C K), the rows once (4 C K), and one
      product a sub-block against the whole chunk for both matrices:
      2 x 2 C^2 K;
    * the forward substitution for W and U: C^2 (K + V);
    * the state through the chunk: W S, (q.decay) S, P U and K^T U:
      3 x 2 C K V + 2 C^2 V.
    Bytes: what a kernel that kept a group's terms on the chip would move:
    q, k, v read in bfloat16, g in float32, b, and the output written in
    float32. (The program's own intermediates go through memory between
    XLA's fusions; they are the gap this share shows, not part of the
    bound.)"""
    m = dims(cfg)
    h, k = m["kda_heads"], m["kda_dim"]
    v, c = k, chunk
    n_sub = c // sub
    chunks = tokens / c
    diag = 7 * n_sub * sub * sub * k
    below = 2 * n_sub * c * k + 4 * c * k + 2 * 2 * c * c * k
    solve = c * c * (k + v)
    state = 3 * 2 * c * k * v + 2 * c * c * v
    flops = h * chunks * (diag + below + solve + state)
    nbytes = tokens * h * (2 * (2 * k + v) + 4 * k + 4 + 4 * v)
    return {"flops": float(flops), "bytes": float(nbytes)}


def moe_experts_cost(cfg: dict, pairs_held: float, experts_used: int,
                     itemsize: int = 2) -> dict:
    """Operations and bytes one routed layer's ``moe.experts`` scope needs
    for ``pairs_held`` (token, expert) pairs on ``experts_used`` experts,
    forward once: three grouped products of 2 x d x ff a pair, each used
    expert's three matrices read once, the sorted rows read for gate and
    up, the hidden rows written and read, the result written. A training
    step: forward twice (rematerialised) and backward once at twice a
    forward, the backward reading the weights again and writing their
    gradient: 4 x."""
    m = dims(cfg)
    d, ff = m["d"], m["expert_ff"]
    flops = pairs_held * 3 * 2 * d * ff
    weights = experts_used * 3 * d * ff * itemsize
    rows = pairs_held * (2 * d + 4 * ff + d) * itemsize
    return {"flops": float(flops), "bytes": float(weights + rows)}


# ------------------------------------------------------------------ weights
def seed_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed it and
    the rest is folded in (a plain ``jax.random.key`` takes 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _leaf_recipe(name: str, shape: tuple) -> tuple:
    """(kind, scale) of one leaf's seeded draw."""
    leaf = name.split("/")[1]
    if leaf in ("g", "kv_norm", "o_norm"):
        return "one_plus", 0.1               # away from the symmetric point
    if leaf == "A_log":
        return "log_uniform_1_16", 0.0
    if leaf == "dt_bias":
        return "inv_softplus_dt", 0.0
    if leaf.startswith("conv_"):
        return "normal", math.sqrt(1.0 / shape[0])
    if name == "embed/W":
        return "normal", 1.0
    fan_in = shape[-2]
    return "normal", math.sqrt(1.0 / fan_in)


def _draw_leaf(key, index: int, kind: str, shape: tuple, scale: float):
    k = jax.random.fold_in(key, index)
    if kind == "log_uniform_1_16":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if kind == "inv_softplus_dt":
        # dt log-uniform in [1e-3, 1e-1]; the bias is softplus^-1(dt)
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    z = jax.random.normal(k, shape, jnp.float32) * scale
    return 1.0 + z if kind == "one_plus" else z


def _recipes(cfg: dict) -> tuple:
    return tuple((i,) + (_leaf_recipe(n, shape)[0], shape,
                         _leaf_recipe(n, shape)[1])
                 for i, (n, shape) in enumerate(param_shapes(cfg).items()))


@functools.partial(jax.jit, static_argnames=("recipes",))
def _draw(key, recipes):
    return [_draw_leaf(key, *recipe) for recipe in recipes]


@functools.partial(jax.jit, static_argnames=("recipes",))
def _change_norms(key, now, recipes):
    return [jnp.linalg.norm(a - _draw_leaf(key, *recipe))
            for a, recipe in zip(now, recipes)]


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Seeded float32 weights, made on the device in one jitted call:
    projections N(0, 1/fan_in), the embedding N(0, 1), norm weights
    1 + 0.1 N(0, 1), convolution taps N(0, 1/4), ``A_log`` = log U(1, 16)
    and ``dt_bias`` the inverse softplus of a step log-uniform in
    [0.001, 0.1] (the usual start of a gated delta layer)."""
    return dict(zip(param_shapes(cfg), _draw(seed_key(seed), _recipes(cfg))))


def change_norms(cfg: dict, seed: int, now: Dict[str, jax.Array]) -> dict:
    """Norm of every leaf's change since ``init_params(cfg, seed)``, the
    starting weights made again leaf by leaf inside one jitted call: with
    602M parameters and Adam's state on the chip there is no room to keep
    the start, or to make it again whole."""
    names = list(param_shapes(cfg))
    out = _change_norms(seed_key(seed), [now[n] for n in names],
                        _recipes(cfg))
    return {n: float(a) for n, a in zip(names, out)}


# ------------------------------------------------------------------ forward
def _operand(a, precision: str):
    """``a`` as a matrix-product operand at ``precision``. The low types
    are plain casts, so autodiff sends the cotangent through the same cast
    (see the ResNet50 reference: the float8 computation a first attempt
    would write)."""
    if precision == "highest":
        return a
    if precision == "bf16":
        return a.astype(jnp.bfloat16).astype(a.dtype)
    if precision == "fp8":
        scale = lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0)
        return (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(a, b, precision: str):
    return jnp.matmul(_operand(a, precision), _operand(b, precision),
                      precision=lax.Precision.HIGHEST)


def rms_norm(x, g, eps: float):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, w):
    """Depthwise causal convolution over time: ``x`` (B, T, C), ``w``
    (taps, C); y_t = sum_j w[j] x_{t - (taps - 1) + j}."""
    taps = w.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * w[j] for j in range(taps))


def _l2norm(x, eps: float = 1e-6):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def kda_recurrence(q, k, v, g, b):
    """The delta rule with a decay per channel, token by token. ``q``,
    ``k``, ``g`` (B, T, H, K), ``v`` (B, T, H, V), ``b`` (B, T, H).
    Returns o (B, T, H, V). The state (B, H, K, V) starts at zero."""
    bsz, t, h, kd = q.shape
    vd = v.shape[-1]

    def step(s, inp):
        qt, kt, vt, gt, bt = inp
        s = s * jnp.exp(gt)[..., None]                     # Diag(a_t) S
        read = jnp.einsum("bhk,bhkv->bhv", kt, s)          # S~^T k_t
        s = s + jnp.einsum("bhk,bhv->bhkv", kt,
                           bt[..., None] * (vt - read))
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s)

    def segment(s, inp):
        return lax.scan(step, s, inp)

    pad = (-t) % SEGMENT
    seq = [jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b)]
    if pad:        # g = 0, b = 0, k = 0 leave the state as it is
        seq = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
               for a in seq]
    seq = [a.reshape((-1, SEGMENT) + a.shape[1:]) for a in seq]
    s0 = jnp.zeros((bsz, h, kd, vd), jnp.float32)
    _, o = lax.scan(jax.checkpoint(segment), s0, tuple(seq))
    o = o.reshape((-1,) + o.shape[2:])[:t]
    return jnp.moveaxis(o, 0, 1)


def kda(m, p, pre, x, precision):
    bsz, t, _ = x.shape
    h, dk = m["kda_heads"], m["kda_dim"]

    def heads(a):
        return a.reshape(bsz, t, h, dk)

    q = heads(_silu(causal_conv(_mm(x, p[pre + "Wq"], precision),
                                p[pre + "conv_q"])))
    k = heads(_silu(causal_conv(_mm(x, p[pre + "Wk"], precision),
                                p[pre + "conv_k"])))
    v = heads(_silu(causal_conv(_mm(x, p[pre + "Wv"], precision),
                                p[pre + "conv_v"])))
    q = _l2norm(q) / math.sqrt(dk)
    k = _l2norm(k)
    f = _mm(_mm(x, p[pre + "Wf1"], precision), p[pre + "Wf2"], precision)
    g = -jnp.exp(p[pre + "A_log"])[:, None] * heads(
        jax.nn.softplus(f + p[pre + "dt_bias"]))
    b = jax.nn.sigmoid(_mm(x, p[pre + "Wb"], precision))
    o = kda_recurrence(_operand(q, precision), _operand(k, precision),
                       _operand(v, precision), g, b)
    gate = _mm(_mm(x, p[pre + "Wg1"], precision), p[pre + "Wg2"], precision)
    o = rms_norm(o, p[pre + "o_norm"], m["eps"]) * jax.nn.sigmoid(heads(gate))
    return _mm(o.reshape(bsz, t, h * dk), p[pre + "Wo"], precision)


def mla(m, p, pre, x, precision):
    bsz, t, _ = x.shape
    h, nope, rope, vd = m["heads"], m["nope"], m["rope"], m["v_dim"]
    dq = nope + rope
    q = _mm(x, p[pre + "Wq"], precision).reshape(bsz, t, h, dq)
    kva = _mm(x, p[pre + "Wkva"], precision)
    c = rms_norm(kva[..., :m["kv_rank"]], p[pre + "kv_norm"], m["eps"])
    k_r = kva[..., m["kv_rank"]:]                       # shared, not rotated
    kvb = _mm(c, p[pre + "Wkvb"], precision).reshape(bsz, t, h, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_r[:, :, None, :], (bsz, t, h, rope))], -1)
    v = kvb[..., nope:]
    kk, vv = _operand(k, precision), _operand(v, precision)

    def attend(args):
        q_blk, start = args
        s = jnp.einsum("bqhd,bkhd->bhqk", _operand(q_blk, precision), kk,
                       precision=lax.Precision.HIGHEST) / math.sqrt(dq)
        rows = start + jnp.arange(q_blk.shape[1])[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _operand(w, precision), vv,
                          precision=lax.Precision.HIGHEST)

    # one block of queries after another (lax.map), each against the whole
    # score row; rows past the end see every key and are cut off
    blk = min(QUERY_BLOCK, t)
    pad = (-t) % blk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    n = (t + pad) // blk
    q_blocks = jnp.moveaxis(qp.reshape(bsz, n, blk, h, dq), 1, 0)
    outs = lax.map(jax.checkpoint(attend),
                   (q_blocks, jnp.arange(n) * blk))
    outs = jnp.moveaxis(outs, 0, 1).reshape(bsz, n * blk, h, vd)[:, :t]
    o = outs.reshape(bsz, t, h * vd)
    return _mm(o, p[pre + "Wo"], precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    return _mm(_silu(_mm(x, w_gate, precision)) * _mm(x, w_up, precision),
               w_down, precision)


def route(m, x, w_r, bias, precision):
    """(weights (.., top_k), expert ids (.., top_k)) over ALL experts."""
    s = jax.nn.sigmoid(_mm(x, w_r, precision))
    _, idx = lax.top_k(s + bias, m["top_k"])
    chosen = jnp.take_along_axis(s, idx, -1)
    w = m["scale"] * chosen / jnp.sum(chosen, -1, keepdims=True)
    return w, idx


def routed_part(m, p, pre, x, precision, offset=None, held=None):
    """What the experts ``offset .. offset + held`` add: a plain loop with
    a mask. ``p[pre + "Wgate"]`` and kin hold those experts."""
    offset = m["expert_offset"] if offset is None else offset
    held = m["experts_held"] if held is None else held
    bias = jnp.zeros((m["experts_total"],), jnp.float32)   # frozen at zero
    w, idx = route(m, x, p[pre + "Wr"], bias, precision)
    y = jnp.zeros_like(x)
    for e in range(held):
        weight = jnp.sum(jnp.where(idx == offset + e, w, 0.0), -1)
        y = y + weight[..., None] * swiglu(
            x, p[pre + "Wgate"][e], p[pre + "Wup"][e], p[pre + "Wdown"][e],
            precision)
    return y


def moe(m, p, pre, x, precision):
    return routed_part(m, p, pre, x, precision) + swiglu(
        x, p[pre + "Sgate"], p[pre + "Sup"], p[pre + "Sdown"], precision)


def _block(cfg_json: str, blk_json: str, precision: str, p, x):
    cfg, blk = json.loads(cfg_json), json.loads(blk_json)
    m, n = dims(cfg), blk["name"]
    a = rms_norm(x, p[n + "_attn_norm/g"], m["eps"])
    attn = kda if blk["attn"] == "kda" else mla
    h = x + attn(m, p, n + "_attn/", a, precision)
    f = rms_norm(h, p[n + "_ffn_norm/g"], m["eps"])
    if blk["ffn"] == "dense":
        y = swiglu(f, p[n + "_ffn/Wgate"], p[n + "_ffn/Wup"],
                   p[n + "_ffn/Wdown"], precision)
    else:
        y = moe(m, p, n + "_ffn/", f, precision)
    return h + y


def hidden(cfg: dict, params, ids, precision: str = "highest"):
    """The final norm's output (B, T, d). Each block is rematerialised in
    the backward pass so that the float32 activations of the timed
    sequence fit beside the weights and Adam's state on one chip."""
    cfg_json = json.dumps(cfg, sort_keys=True)
    x = params["embed/W"][ids]
    for blk in blocks(cfg):
        own = {k: v for k, v in params.items()
               if k.startswith(blk["name"] + "_")}
        run = functools.partial(_block, cfg_json,
                                json.dumps(blk, sort_keys=True), precision)
        x = jax.checkpoint(run)(own, x)
    return rms_norm(x, params["final_norm/g"], cfg["rms_norm_eps"])


def logits(cfg: dict, params, ids, precision: str = "highest"):
    return _mm(hidden(cfg, params, ids, precision), params["head/W"],
               precision)


def loss(cfg: dict, params, ids, labels, precision: str = "highest"):
    """Mean over all positions of the cross-entropy of the next id."""
    logp = jax.nn.log_softmax(logits(cfg, params, ids, precision), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                 -1)
    return -jnp.mean(picked)


# ----------------------------------------------------------------- training
def _adam(upd: dict, params, grads, m, v, t):
    b1, b2 = upd["beta1"], upd["beta2"]
    m = {k: b1 * m[k] + (1 - b1) * grads[k] for k in params}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(grads[k]) for k in params}
    new = {k: params[k] - upd["learning_rate"]
           * (m[k] / (1 - b1 ** t))
           / (jnp.sqrt(v[k] / (1 - b2 ** t)) + upd["epsilon"])
           for k in params}
    return new, m, v


@functools.lru_cache(maxsize=None)
def _step_fn(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    upd = cfg["updater"]

    # the weights and both moments are given up to the step: three copies
    # of 602M float32 parameters and the gradient are what fits
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, ids, labels):
        value, g = jax.value_and_grad(
            lambda q: loss(cfg, q, ids, labels, precision))(p)
        new, m, v = _adam(upd, p, g, m, v, t)
        return new, m, v, value, {k: jnp.linalg.norm(g[k]) for k in g}

    return step


@jax.jit
def _delta_norms(a, b):
    return {k: jnp.linalg.norm(a[k] - b[k]) for k in a}


def train_steps(cfg: dict, params, batches, precision: str = "highest",
                place=None, seed: Optional[int] = None) -> dict:
    """Follow the program's first steps from the same weights and rows.
    ``batches`` is a list of host ``(ids, labels)``. ``params`` is GIVEN UP
    (donated to the first step). With ``seed``, ``params`` are
    ``init_params(cfg, seed)`` and the parameters' change is taken against
    that start made again (``change_norms``); without it a copy is kept
    throughout (small sizes). Returns the loss of every step, the norm of
    every leaf of the first gradient, and the norm of every leaf's change
    after the last step."""
    place = place or jnp.asarray
    step = _step_fn(json.dumps(cfg, sort_keys=True), precision)
    with jax.default_matmul_precision("highest"):
        keep = None
        if seed is None:
            keep = {k: jnp.array(a, copy=True) for k, a in params.items()}
        p = params
        m = {k: jnp.zeros_like(a) for k, a in params.items()}
        v = {k: jnp.zeros_like(a) for k, a in params.items()}
        losses, first = [], None
        for t, (ids, labels) in enumerate(batches, start=1):
            p, m, v, value, gn = step(p, m, v, float(t), place(ids),
                                      place(labels))
            losses.append(float(value))
            if first is None:
                first = {k: float(a) for k, a in gn.items()}
        del m, v
        if seed is None:
            delta = {k: float(a) for k, a in _delta_norms(p, keep).items()}
        else:
            delta = change_norms(cfg, seed, p)
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}

"""Plain reference of the ``qwen3_next_80b_a3b_ep16`` configuration.

Qwen3-Next-80B-A3B-Instruct (``config.json`` of the Hugging Face
repository, ``model_type`` ``qwen3_next``; the equations are those of the
model's public implementation) on the training path: forward, loss,
gradients and Adam in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. With x a block's input and i
its index from 0 every block is

    h = x + Mixer(norm(x)),   y = h + MoE(norm(h))

and a final norm and an untied head follow; no projection has a bias.
``norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (the weight is kept
zero-centred). ``Mixer`` is gated attention where
``(i + 1) % full_attention_interval == 0`` and Gated DeltaNet otherwise.

Gated DeltaNet (h_k = ``linear_num_key_heads``, h_v =
``linear_num_value_heads``, d = the linear head width), token by token with
``lax.scan`` over time, no chunk algebra:

    [q, k, v, z] = W_qkvz x,   [b, a] = W_ba x
    [q, k, v] = SiLU(conv4([q, k, v]))    (causal, depthwise, no bias)
    q = L2norm(q) / sqrt(d),  k = L2norm(k)    (eps 1e-6; a q/k head serves
                                   h_v / h_k value heads that lie together)
    beta_t = sigmoid(b_t),  g_t = -exp(A_log) * softplus(a_t + dt_bias)
    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T
    o_t = S_t^T q_t                        (S in float32, from zero)
    out = W_o (o_norm * o_t / sqrt(mean(o_t^2) + eps) * SiLU(z_t))

(the output norm's weight is plain, not ``1 + w``). Gated attention
(h = ``num_attention_heads``, h_kv = ``num_key_value_heads``, d =
``head_dim``, r = d x ``partial_rotary_factor``):

    [q, gate] = W_q x  (per head: q, then gate),  k = W_k x,  v = W_v x
    q, k = norm_head(q), norm_head(k)        (over d, the ``1 + w`` norm)
    widths 0..r of q and k rotated: width j paired with j + r/2, turned by
    t * rope_theta^(-2j / r); widths r..d left alone
    each k/v head serves h / h_kv query heads; causal
    softmax(q k^T / sqrt(d)) v;  out = W_o (attn * sigmoid(gate))

Routed experts: ``p = softmax(W_r x)`` over ALL published experts, top-k,
weights ``p_i / sum_topk p_j``,
``y = sum over chosen experts HELD HERE of w_i E_i(x)
+ sigmoid(w_sg . x) E_shared(x)``, ``E(x) = W_down(SiLU(W_gate x) * W_up x)``.

Departures from the published model, each to match what the configuration
states it runs:

* this chip's share of a 16-way deployment: ``num_experts`` experts of the
  ``published.num_experts`` are held (``expert_offset`` onward); what the
  absent experts would add is left out, and that partial sum goes on;
* the vocabulary is a slice (``vocab_size`` rows): ids, logits and loss are
  over the slice;
* the checkpoint's multi-token-prediction block is not in ``config.json``
  and the public model code drops it on load: left out;
* no auxiliary load-balancing loss (the config carries no coefficient);
* the fused projections keep their columns in plain blocks, [q | k | v | z]
  and [b | a] (the checkpoint interleaves them by key head: storage only,
  under random weights);
* the recurrence is rematerialised over segments of 64 steps, each block
  and each held expert's feed-forward (one ``lax.scan`` over the experts) as
  a whole, the attention over blocks of queries: memory devices only, the
  arithmetic stays token by token and the score row whole.

``precision`` lowers only the operands of matrix products (``bf16``:
rounded to bfloat16; ``fp8``: scaled per tensor and rounded to
float8_e4m3fn, the control of the correctness check); products accumulate
in float32 and the recurrence's state is float32 throughout.

Nothing of ``deeplearning4j_tpu`` is imported. Parameter names follow the
zoo builder's vertex names (``l3_attn/Wq``) only so that the benchmark can
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
    if cfg["linear_key_head_dim"] != cfg["linear_value_head_dim"]:
        raise ValueError("key and value heads of the linear layers differ")
    return {
        "d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "gdn_k_heads": cfg["linear_num_key_heads"],
        "gdn_v_heads": cfg["linear_num_value_heads"],
        "gdn_dim": cfg["linear_key_head_dim"],
        "conv": cfg["linear_conv_kernel_dim"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "rotary": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        "theta": float(cfg["rope_theta"]),
        "dense_ff": cfg["intermediate_size"],
        "expert_ff": cfg["moe_intermediate_size"],
        "shared_ff": cfg["shared_expert_intermediate_size"],
        "experts_held": cfg["num_experts"],
        "experts_total": cfg["published"]["num_experts"],
        "expert_offset": cfg.get("expert_offset", 0),
        "top_k": cfg["num_experts_per_tok"],
        "eps": cfg["rms_norm_eps"],
    }


def blocks(cfg: dict) -> List[dict]:
    """Every block kept: its published index (from 0), its mixer and its
    feed-forward kind."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        full = (i + 1) % cfg["full_attention_interval"] == 0
        sparse = (i not in cfg["mlp_only_layers"]
                  and (i + 1) % cfg["decoder_sparse_step"] == 0)
        out.append({"index": i, "name": f"l{i}",
                    "attn": "gattn" if full else "gdn",
                    "ffn": "moe" if sparse else "dense"})
    return out


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Every leaf's shape, in a fixed order (the order seeds are folded
    in)."""
    m = dims(cfg)
    d = m["d"]
    hk, hv, dl = m["gdn_k_heads"], m["gdn_v_heads"], m["gdn_dim"]
    h, hkv, dh = m["heads"], m["kv_heads"], m["head_dim"]
    out = {"embed/W": (m["vocab"], d)}
    for blk in blocks(cfg):
        n = blk["name"]
        out[f"{n}_attn_norm/w"] = (d,)
        a = f"{n}_attn/"
        if blk["attn"] == "gdn":
            out[a + "Wqkvz"] = (d, 2 * (hk + hv) * dl)
            out[a + "Wba"] = (d, 2 * hv)
            out[a + "conv"] = (m["conv"], (2 * hk + hv) * dl)
            out[a + "A_log"] = (hv,)
            out[a + "dt_bias"] = (hv,)
            out[a + "o_norm"] = (dl,)
            out[a + "Wo"] = (hv * dl, d)
        else:
            out[a + "Wq"] = (d, h * 2 * dh)
            out[a + "Wk"] = (d, hkv * dh)
            out[a + "Wv"] = (d, hkv * dh)
            out[a + "q_norm"] = (dh,)
            out[a + "k_norm"] = (dh,)
            out[a + "Wo"] = (h * dh, d)
        out[f"{n}_ffn_norm/w"] = (d,)
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
            out[f + "Wsg"] = (d, 1)
    out["final_norm/w"] = (d,)
    out["head/W"] = (d, m["vocab"])
    return out


def count_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


# ------------------------------------------------------------------- FLOPs
def layers(cfg: dict) -> List[dict]:
    """What ``harness.flops`` counts, per token of a sequence of
    ``cfg["sequence_length"]``: every product as ``kind: "dense"`` with
    ``positions``. Projections once a token; the attention's scores and
    values at the causal mean of (T + 1) / 2 keys a query; Gated DeltaNet
    as the recurrence's three d x d products a value head and token
    (decay-and-read k^T S, the rank-one update, the read S^T q); routed
    experts top_k x held / total a token. Embedding gather, convolution of
    4, norms, rotation, gates' sigmoids and the router's top-k are not
    matrix products and are left out."""
    m = dims(cfg)
    t = cfg["sequence_length"]
    d = m["d"]
    out = []

    def add(name, n_in, n_out, positions=1.0):
        out.append({"name": name, "kind": "dense", "n_in": n_in,
                    "n_out": n_out, "positions": positions})

    for blk in blocks(cfg):
        n = blk["name"]
        if blk["attn"] == "gdn":
            hk, hv, dl = m["gdn_k_heads"], m["gdn_v_heads"], m["gdn_dim"]
            add(n + "_attn.qkvz", d, 2 * (hk + hv) * dl)
            add(n + "_attn.ba", d, 2 * hv)
            add(n + "_attn.recurrence", dl, dl, 3 * hv)
            add(n + "_attn.o", hv * dl, d)
        else:
            h, hkv, dh = m["heads"], m["kv_heads"], m["head_dim"]
            add(n + "_attn.q", d, h * 2 * dh)
            add(n + "_attn.kv", d, 2 * hkv * dh)
            add(n + "_attn.scores", dh, (t + 1) / 2.0, h)
            add(n + "_attn.values", (t + 1) / 2.0, dh, h)
            add(n + "_attn.o", h * dh, d)
        if blk["ffn"] == "dense":
            add(n + "_ffn", d, 3 * m["dense_ff"])
        else:
            add(n + "_ffn.router", d, m["experts_total"])
            add(n + "_ffn.shared", d, 3 * m["shared_ff"])
            add(n + "_ffn.shared_gate", d, 1)
            add(n + "_ffn.routed", d, 3 * m["expert_ff"],
                m["top_k"] * m["experts_held"] / m["experts_total"])
    add("head", d, m["vocab"])
    return out


def gdn_scan_cost(cfg: dict, tokens: int, chunk: int = 64,
                  sub: int = 8) -> dict:
    """Operations and bytes one Gated DeltaNet layer's ``gdn.scan`` scope
    needs for ``tokens`` tokens IN THE FORM THE PROGRAM COMPUTES IT: the
    chunked scan of the per-channel delta rule (``chunked_kda``: chunks of
    ``chunk``; diagonal blocks of ``sub`` written out channel by channel;
    the triangular system by forward substitution over blocks of ``sub``)
    with the head's one decay spread over its channels (under the scope)
    and q, k of every value head (repeated in front of it, under
    ``gdn.conv``), forward once. A training step runs it forward twice
    (the layer is rematerialised) and backward once, taken as twice a
    forward: 4 x.

    Per value head and chunk (C = chunk, K = V = the head width,
    n = C / sub):
    * diagonal blocks: n sub^2 K decay factors (a subtraction, an
      exponential and the product with k: 3) shared by the two score
      matrices, each a multiply and an add more: 7 n sub^2 K;
    * blocks below the diagonal: the keys scaled once a sub-block
      (2 n C K), the rows once (4 C K), and one product a sub-block against
      the whole chunk for both matrices: 2 x 2 C^2 K;
    * the forward substitution for W and U: C^2 (K + V);
    * the state through the chunk: W S, (q.decay) S, P U and K^T U:
      3 x 2 C K V + 2 C^2 V.
    Bytes: what the kernels move: q, k, v of every VALUE head read in
    bfloat16, g at K float32 channels a head (the spread decay is what the
    program hands the kernels), b, and the output written in float32. A
    scalar-decay form would need a K-th of the decay's bytes and none of
    the per-channel exponentials: that gap is part of what this share
    shows."""
    m = dims(cfg)
    h, k = m["gdn_v_heads"], m["gdn_dim"]
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


def gattn_attend_cost(cfg: dict, tokens: int, tile: int = 512,
                      itemsize: int = 2) -> dict:
    """Operations and bytes one gated-attention layer's ``gattn.attend``
    scope needs for one sequence of ``tokens`` tokens IN THE FORM THE
    PROGRAM COMPUTES IT, forward once: the causal triangle's tile pairs
    (``tile`` x ``tile``, diagonal tiles whole: the kernels compute their
    masked halves), two products a pair (q k^T and p v) for each of the
    QUERY heads. Bytes: q read and the output written once, a key and a
    value tile read once a pair and QUERY head (k and v are repeated over
    their group in front of the kernels, so nothing is shared inside a
    group), and the repeat itself (h_kv heads read, h written, for k and
    v). A training step: the forward twice (rematerialised) and the
    backward, which makes five products a pair (the scores again, dv, dp,
    dk, dq): 4.5 x."""
    m = dims(cfg)
    h, hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    n = -(-tokens // tile)
    pairs = n * (n + 1) // 2
    flops = h * pairs * 2 * 2 * tile * tile * d
    nbytes = itemsize * (h * (2 * tokens * d + pairs * 2 * tile * d)
                         + 2 * (hkv + h) * tokens * d)
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
    if leaf in ("w", "q_norm", "k_norm"):
        return "normal", 0.1            # zero-centred: the scale is 1 + w
    if leaf == "o_norm":
        return "one_plus", 0.1
    if leaf == "A_log":
        return "log_uniform_1_16", 0.0
    if leaf == "dt_bias":
        return "inv_softplus_dt", 0.0
    if leaf == "conv":
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
    projections (the router and the shared gate among them) N(0, 1/fan_in),
    the embedding N(0, 1), the zero-centred norm weights 0.1 N(0, 1) (so
    their scales are 1 + 0.1 N), the output norm of the linear layers
    1 + 0.1 N(0, 1), convolution taps N(0, 1/4), ``A_log`` = log U(1, 16)
    and ``dt_bias`` the inverse softplus of a step log-uniform in
    [0.001, 0.1] (the usual start of a gated delta layer), one a value
    head."""
    return dict(zip(param_shapes(cfg), _draw(seed_key(seed), _recipes(cfg))))


def change_norms(cfg: dict, seed: int, now: Dict[str, jax.Array]) -> dict:
    """Norm of every leaf's change since ``init_params(cfg, seed)``, the
    starting weights made again leaf by leaf inside one jitted call: with
    626M parameters and Adam's state on the chip there is no room to keep
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


def norm(x, w, eps: float):
    """The model's norm: the weight is zero-centred, the scale ``1 + w``."""
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * (1.0 + w)


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


def delta_recurrence(q, k, v, g, b):
    """The gated delta rule with one decay a head, token by token. ``q``,
    ``k`` (B, T, H, K), ``v`` (B, T, H, V), ``g``, ``b`` (B, T, H). Returns
    o (B, T, H, V). The state (B, H, K, V) starts at zero."""
    bsz, t, h, kd = q.shape
    vd = v.shape[-1]

    def step(s, inp):
        qt, kt, vt, gt, bt = inp
        s = s * jnp.exp(gt)[..., None, None]               # exp(g_t) S
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


def gdn(m, p, pre, x, precision):
    bsz, t, _ = x.shape
    hk, hv, dl = m["gdn_k_heads"], m["gdn_v_heads"], m["gdn_dim"]
    qkvz = _mm(x, p[pre + "Wqkvz"], precision)
    n_qk, n_v = hk * dl, hv * dl
    mixed = _silu(causal_conv(qkvz[..., :2 * n_qk + n_v], p[pre + "conv"]))
    z = qkvz[..., 2 * n_qk + n_v:].reshape(bsz, t, hv, dl)
    q = mixed[..., :n_qk].reshape(bsz, t, hk, dl)
    k = mixed[..., n_qk:2 * n_qk].reshape(bsz, t, hk, dl)
    v = mixed[..., 2 * n_qk:].reshape(bsz, t, hv, dl)
    q = _l2norm(q) / math.sqrt(dl)
    k = _l2norm(k)
    # value head j reads q/k head j // (h_v / h_k)
    q = jnp.repeat(q, hv // hk, axis=2)
    k = jnp.repeat(k, hv // hk, axis=2)
    ba = _mm(x, p[pre + "Wba"], precision)
    b = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p[pre + "A_log"]) * jax.nn.softplus(
        ba[..., hv:] + p[pre + "dt_bias"])
    o = delta_recurrence(_operand(q, precision), _operand(k, precision),
                         _operand(v, precision), g, b)
    o = (o * lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + m["eps"])
         * p[pre + "o_norm"] * _silu(z))
    return _mm(o.reshape(bsz, t, hv * dl), p[pre + "Wo"], precision)


def rotate(x, rotary: int, theta: float):
    """``x`` (B, T, H, d): the first ``rotary`` widths turned, width j with
    width j + rotary / 2, by t * theta^(-2j / rotary); the rest left."""
    half = rotary // 2
    t = x.shape[1]
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rotary)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def gattn(m, p, pre, x, precision):
    bsz, t, _ = x.shape
    h, hkv, dh = m["heads"], m["kv_heads"], m["head_dim"]
    group = h // hkv
    qg = _mm(x, p[pre + "Wq"], precision).reshape(bsz, t, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = _mm(x, p[pre + "Wk"], precision).reshape(bsz, t, hkv, dh)
    v = _mm(x, p[pre + "Wv"], precision).reshape(bsz, t, hkv, dh)
    q = rotate(norm(q, p[pre + "q_norm"], m["eps"]), m["rotary"], m["theta"])
    k = rotate(norm(k, p[pre + "k_norm"], m["eps"]), m["rotary"], m["theta"])
    # query head j reads k/v head j // group
    q = q.reshape(bsz, t, hkv, group, dh)
    kk, vv = _operand(k, precision), _operand(v, precision)

    def attend(args):
        q_blk, start = args
        s = jnp.einsum("bqngd,bknd->bngqk", _operand(q_blk, precision), kk,
                       precision=lax.Precision.HIGHEST) / math.sqrt(dh)
        rows = start + jnp.arange(q_blk.shape[1])[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bngqk,bknd->bqngd", _operand(w, precision), vv,
                          precision=lax.Precision.HIGHEST)

    # one block of queries after another (lax.map), each against the whole
    # score row; rows past the end see every key and are cut off
    blk = min(QUERY_BLOCK, t)
    pad = (-t) % blk
    qp = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3) if pad else q
    n = (t + pad) // blk
    q_blocks = jnp.moveaxis(qp.reshape(bsz, n, blk, hkv, group, dh), 1, 0)
    outs = lax.map(jax.checkpoint(attend), (q_blocks, jnp.arange(n) * blk))
    o = jnp.moveaxis(outs, 0, 1).reshape(bsz, n * blk, h, dh)[:, :t]
    o = o * jax.nn.sigmoid(gate)
    return _mm(o.reshape(bsz, t, h * dh), p[pre + "Wo"], precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    return _mm(_silu(_mm(x, w_gate, precision)) * _mm(x, w_up, precision),
               w_down, precision)


def route(m, x, w_r, precision):
    """(weights (.., top_k), expert ids (.., top_k)) over ALL experts."""
    p = jax.nn.softmax(_mm(x, w_r, precision), axis=-1)
    chosen, idx = lax.top_k(p, m["top_k"])
    return chosen / jnp.sum(chosen, -1, keepdims=True), idx


def routed_part(m, p, pre, x, precision, offset=None, held=None):
    """What the experts ``offset .. offset + held`` add: a plain loop with
    a mask, one expert after another over EVERY token (``lax.scan`` over
    the experts, each rematerialised: 32 bodies written out cost the
    compiler four minutes). ``p[pre + "Wgate"]`` and kin hold those
    experts."""
    offset = m["expert_offset"] if offset is None else offset
    held = m["experts_held"] if held is None else held
    w, idx = route(m, x, p[pre + "Wr"], precision)

    def one(y, expert):
        e, w_gate, w_up, w_down = expert
        weight = jnp.sum(jnp.where(idx == offset + e, w, 0.0), -1)
        return y + weight[..., None] * swiglu(x, w_gate, w_up, w_down,
                                              precision), None

    y, _ = lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                    (jnp.arange(held), p[pre + "Wgate"][:held],
                     p[pre + "Wup"][:held], p[pre + "Wdown"][:held]))
    return y


def shared_part(m, p, pre, x, precision):
    """The shared expert behind its gate: what every chip computes alike."""
    return jax.nn.sigmoid(_mm(x, p[pre + "Wsg"], precision)) * swiglu(
        x, p[pre + "Sgate"], p[pre + "Sup"], p[pre + "Sdown"], precision)


def moe(m, p, pre, x, precision):
    return (routed_part(m, p, pre, x, precision)
            + shared_part(m, p, pre, x, precision))


def _block(cfg_json: str, blk_json: str, precision: str, p, x):
    cfg, blk = json.loads(cfg_json), json.loads(blk_json)
    m, n = dims(cfg), blk["name"]
    a = norm(x, p[n + "_attn_norm/w"], m["eps"])
    mixer = gdn if blk["attn"] == "gdn" else gattn
    h = x + mixer(m, p, n + "_attn/", a, precision)
    f = norm(h, p[n + "_ffn_norm/w"], m["eps"])
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
    return norm(x, params["final_norm/w"], cfg["rms_norm_eps"])


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
    # of 626M float32 parameters and the gradient are what fits
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

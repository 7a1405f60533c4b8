"""Plain reference of the ``joyai_llm_flash_48b_a2p7b_ep32`` configuration.

JoyAI-LLM-Flash (``config.json`` of the Hugging Face repository,
``model_type`` ``joyai_llm_flash``; its keys are the DeepSeek-V3 family's,
described in the DeepSeek-V3 technical report, arXiv:2412.19437: section
2.1 latent attention and the auxiliary-loss-free router, section 2.2
multi-token prediction) on the training path: forward, both loss terms,
gradients and Adam in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``. With x (T, d) a block's input
at positions t = 0..T-1, every product without bias, every block is

    a = RMSNorm(x)                     (x * rsqrt(mean x^2 + eps) * g)
    c_q = RMSNorm_q(a W_qa)            (``q_lora_rank``)
    q   = c_q W_qb                     -> h heads of [q_nope (n) | q_pe (r)]
    [c_kv (``kv_lora_rank``) | k_pe (r)] = a W_kva ;  c = RMSNorm_kv(c_kv)
    c W_kvb                            -> h heads of [k_nope (n) | v (v_dim)]
    R_t on q_pe of every head and on the ONE k_pe: pair j = 0..r/2-1, the
        ADJACENT widths (2j, 2j+1) (``rope_interleave``), turned by the
        angle t * theta^(-2j/r)
    s[t,u] = (q_nope_t . k_nope_u + R_t q_pe_t . R_u k_pe_u) / sqrt(n + r)
             for u <= t, -inf elsewhere (no factor on the scale:
             ``rope_scaling`` is null)
    o_t = sum_u softmax_u(s)[t,u] v_u ;   h1 = x + concat_heads(o) W_o
    f = RMSNorm(h1)
    y = h1 + SwiGLU_dense(f)                    in the first
                                                ``first_k_dense_replace`` layers
    y = h1 + sum_{e chosen, HELD HERE} g_e SwiGLU_e(f) + SwiGLU_shared(f)
        s = sigmoid(f W_r) over ALL published experts; the top-k of s + b
        (the bias b in the CHOICE only); g_e = scale * s_e / sum_chosen s

and ``final_norm`` and an untied head follow. The multi-token prediction
module (one; report eq. 21-25), with h^L the last block's output BEFORE
``final_norm`` and the batch's ids t_1..t_{T+1} (features t_1..t_T, labels
t_2..t_{T+1}):

    m_i = [RMSNorm_h(h^L_i) ; RMSNorm_e(Emb(t_{i+1}))] W_eh      (2d -> d)
    one more block of the routed kind over m (its own weights, positions
    0..T-1), RMSNorm_mtp, the model's ONE head
    L = L_main + lambda * L_mtp
    L_main = (1/T) sum_{i=1..T}   CE(head(final_norm(h^L_i)),    t_{i+1})
    L_mtp  = (1/T) sum_{i=1..T-1} CE(head(RMSNorm_mtp(blk(m)_i)), t_{i+2})

Departures from the published description, each to match what the
configuration states it runs (its ``assumed`` and ``deployment``):

* this chip's share of a 32-way deployment: ``n_routed_experts`` experts of
  the ``published.n_routed_experts`` are held (``expert_offset`` onward);
  what the absent experts would add is left out, and that partial sum goes
  on; the shared expert is whole;
* the vocabulary is a slice (``vocab_size`` rows): ids, logits and both
  losses are over the slice;
* the router's selection bias is a constant zero (its load-driven update
  is not in ``config.json``);
* ``config.json`` holds no lambda: ``mtp_weight`` of the configuration's
  file (0.3, the report's first-stage value); the order of the two halves
  under W_eh (state first), h^L taken before ``final_norm`` and the 1/T on
  both sums are the report's as this file reads it;
* ``Emb(t_{i+1})`` is the embedding of the LABELS (the program shifts its
  embedding vertex and feeds zeros at position T): position T is masked in
  L_mtp and, the attention being causal, nothing scored reads it;
* memory devices only, the arithmetic stays plain: each block is
  rematerialised as a whole, the attention runs a block of queries at a
  time against the WHOLE score row under an explicit ``where(u <= t)`` (no
  tiles), each held expert's feed-forward runs over EVERY token with a
  mask (one ``lax.scan`` over the experts), the losses a block of token
  rows at a time.

``precision`` lowers only the operands of matrix products (``bf16``:
rounded to bfloat16; ``fp8``: scaled per tensor and rounded to
float8_e4m3fn, the control of the correctness check); products accumulate
in float32.

Nothing of ``deeplearning4j_tpu`` is imported. Parameter names follow the
zoo builder's vertex names (``l2_attn/Wqa``, ``mtp1_combine/W``) only so
that the benchmark can hand the same seeded weights to both sides."""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 128     # queries whose whole score rows are alive together
TOKEN_BLOCK = 1024    # token rows whose logits are alive together


# ---------------------------------------------------------------- structure
def dims(cfg: dict) -> dict:
    return {
        "d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"], "q_rank": cfg["q_lora_rank"],
        "kv_rank": cfg["kv_lora_rank"], "theta": float(cfg["rope_theta"]),
        "dense_ff": cfg["intermediate_size"],
        "expert_ff": cfg["moe_intermediate_size"],
        "shared_ff": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "experts_held": cfg["n_routed_experts"],
        "experts_total": cfg["published"]["n_routed_experts"],
        "expert_offset": cfg.get("expert_offset", 0),
        "top_k": cfg["num_experts_per_tok"],
        "scale": cfg["routed_scaling_factor"],
        "eps": cfg["rms_norm_eps"],
        "modules": cfg["num_nextn_predict_layers"],
    }


def blocks(cfg: dict) -> List[dict]:
    """Every block that runs: the trunk's kept layers (published index from
    1) and then the prediction module's one block (``module`` true)."""
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the router chooses in one group")
    if cfg["num_nextn_predict_layers"] != 1:
        raise ValueError("one prediction module")
    out = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        moe = (i > cfg["first_k_dense_replace"]
               and (i - 1) % cfg["moe_layer_freq"] == 0)
        out.append({"index": i, "name": f"l{i}", "attn": "mla",
                    "ffn": "moe" if moe else "dense", "module": False})
    out.append({"index": None, "name": "mtp1", "attn": "mla", "ffn": "moe",
                "module": True})
    return out


def _block_shapes(m: dict, blk: dict) -> Dict[str, tuple]:
    d, h, n = m["d"], m["heads"], blk["name"]
    a, f = f"{n}_attn/", f"{n}_ffn/"
    out = {
        f"{n}_attn_norm/g": (d,),
        a + "Wqa": (d, m["q_rank"]),
        a + "q_norm": (m["q_rank"],),
        a + "Wqb": (m["q_rank"], h * (m["nope"] + m["rope"])),
        a + "Wkva": (d, m["kv_rank"] + m["rope"]),
        a + "kv_norm": (m["kv_rank"],),
        a + "Wkvb": (m["kv_rank"], h * (m["nope"] + m["v_dim"])),
        a + "Wo": (h * m["v_dim"], d),
        f"{n}_ffn_norm/g": (d,),
    }
    if blk["ffn"] == "dense":
        out.update({f + "Wgate": (d, m["dense_ff"]),
                    f + "Wup": (d, m["dense_ff"]),
                    f + "Wdown": (m["dense_ff"], d)})
    else:
        e, ff = m["experts_held"], m["expert_ff"]
        out.update({f + "Wr": (d, m["experts_total"]),
                    f + "Wgate": (e, d, ff), f + "Wup": (e, d, ff),
                    f + "Wdown": (e, ff, d),
                    f + "Sgate": (d, m["shared_ff"]),
                    f + "Sup": (d, m["shared_ff"]),
                    f + "Sdown": (m["shared_ff"], d)})
    return out


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Every leaf's shape, in a fixed order (the order seeds are folded
    in)."""
    m = dims(cfg)
    d = m["d"]
    out = {"embed/W": (m["vocab"], d)}
    for blk in blocks(cfg):
        if blk["module"]:
            out["mtp1_combine/h_norm"] = (d,)
            out["mtp1_combine/e_norm"] = (d,)
            out["mtp1_combine/W"] = (2 * d, d)
        out.update(_block_shapes(m, blk))
    out["mtp1_norm/g"] = (d,)
    out["final_norm/g"] = (d,)
    out["head/W"] = (d, m["vocab"])
    return out


def count_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


# ------------------------------------------------------------------- FLOPs
def kept_positions(tokens: int) -> int:
    """(query, key) pairs the causal mask keeps in one sequence: query t
    sees t + 1 keys."""
    return tokens * (tokens + 1) // 2


def layers(cfg: dict) -> List[dict]:
    """What ``harness.flops`` counts, per token of a sequence of
    ``cfg["sequence_length"]``: every product as ``kind: "dense"`` with
    ``positions``. Projections once a token; the attention's scores and
    values at the causal mean of (T + 1) / 2 keys a query; routed experts
    top_k x held / total a token; the module's combine and block as the
    trunk's; the head twice (the trunk's state and the module's, whose one
    masked position is counted: 1 in T). Embedding gather, norms, the
    rotation and the router's top-k are not matrix products and are left
    out."""
    m = dims(cfg)
    t = cfg["sequence_length"]
    d, h = m["d"], m["heads"]
    dq = m["nope"] + m["rope"]
    keys = kept_positions(t) / t
    out = []

    def add(name, n_in, n_out, positions=1.0):
        out.append({"name": name, "kind": "dense", "n_in": n_in,
                    "n_out": n_out, "positions": positions})

    for blk in blocks(cfg):
        n = blk["name"]
        if blk["module"]:
            add(n + "_combine", 2 * d, d)
        add(n + "_attn.qa", d, m["q_rank"])
        add(n + "_attn.qb", m["q_rank"], h * dq)
        add(n + "_attn.kva", d, m["kv_rank"] + m["rope"])
        add(n + "_attn.kvb", m["kv_rank"], h * (m["nope"] + m["v_dim"]))
        add(n + "_attn.scores", dq, keys, h)
        add(n + "_attn.values", keys, m["v_dim"], h)
        add(n + "_attn.o", h * m["v_dim"], d)
        if blk["ffn"] == "dense":
            add(n + "_ffn", d, 3 * m["dense_ff"])
        else:
            add(n + "_ffn.router", d, m["experts_total"])
            add(n + "_ffn.shared", d, 3 * m["shared_ff"])
            add(n + "_ffn.routed", d, 3 * m["expert_ff"],
                m["top_k"] * m["experts_held"] / m["experts_total"])
    add("head", d, m["vocab"], 1.0 + m["modules"])
    return out


def mla_attend_cost(cfg: dict, tokens: int, itemsize: int = 2) -> dict:
    """Operations and bytes one latent layer's ``mla.attend`` scope needs
    for one sequence of ``tokens`` tokens, forward once: the two products
    (q k^T over ``qk_nope_head_dim + qk_rope_head_dim`` widths and p v over
    ``v_head_dim``) over the (query, key) POSITIONS the mask keeps (t + 1
    keys a query), for each of the heads, whatever tile visits them: the
    masked half of a diagonal tile is no work the algorithm needs, so a
    kernel that trimmed it could not read over 100%. Bytes: q read and the
    output written once a head, each head's k_nope and v read once and the
    ONE rotated key once (that the program hands every head its own copy
    of it is its own cost, under the scope and so in the measured time). A
    training step: the forward twice (rematerialised) and the backward,
    which makes five products a position (the scores again, dv, dp, dk,
    dq): 4.5 x."""
    m = dims(cfg)
    h, dq, dv = m["heads"], m["nope"] + m["rope"], m["v_dim"]
    flops = h * kept_positions(tokens) * 2 * (dq + dv)
    nbytes = itemsize * tokens * (h * (dq + m["nope"] + 2 * dv) + m["rope"])
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
    gradient: 4 x. (The sibling references' convention.)"""
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
    if len(shape) == 1:                      # every vector is a norm weight
        return "one_plus", 0.1               # away from the symmetric point
    if name == "embed/W":
        return "normal", 1.0
    return "normal", math.sqrt(1.0 / shape[-2])


def _draw_leaf(key, index: int, kind: str, shape: tuple, scale: float):
    z = jax.random.normal(jax.random.fold_in(key, index), shape,
                          jnp.float32) * scale
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
    1 + 0.1 N(0, 1)."""
    return dict(zip(param_shapes(cfg), _draw(seed_key(seed), _recipes(cfg))))


def change_norms(cfg: dict, seed: int, now: Dict[str, jax.Array]) -> dict:
    """Norm of every leaf's change since ``init_params(cfg, seed)``, the
    starting weights made again leaf by leaf inside one jitted call: with
    561M parameters and Adam's state on the chip there is no room to keep
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


def norm(x, g, eps: float):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def rotate(x, theta: float):
    """R_t on ``x`` (batch, time, ..., r): pair j is the adjacent widths
    (2j, 2j + 1), turned by t * theta^(-2j/r), t the time index."""
    t, r = x.shape[1], x.shape[-1]
    j = jnp.arange(r // 2, dtype=jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * theta ** (-2.0 * j / r)
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (r // 2,))
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                        a * jnp.sin(angle) + b * jnp.cos(angle)], -1)
    return turned.reshape(x.shape)


def attention(m, p, pre, x, precision, rotated: bool = True):
    """The latent attention of one block on ``x`` (batch, T, d), already
    normalised. ``rotated`` false leaves R_t out (a fault the tests
    plant)."""
    bsz, t, _ = x.shape
    h, nope, rope, vd = m["heads"], m["nope"], m["rope"], m["v_dim"]
    dq = nope + rope
    c_q = norm(_mm(x, p[pre + "Wqa"], precision), p[pre + "q_norm"], m["eps"])
    q = _mm(c_q, p[pre + "Wqb"], precision).reshape(bsz, t, h, dq)
    kva = _mm(x, p[pre + "Wkva"], precision)
    c = norm(kva[..., :m["kv_rank"]], p[pre + "kv_norm"], m["eps"])
    k_pe = kva[..., m["kv_rank"]:]
    q_pe = q[..., nope:]
    if rotated:
        q_pe, k_pe = rotate(q_pe, m["theta"]), rotate(k_pe, m["theta"])
    kvb = _mm(c, p[pre + "Wkvb"], precision).reshape(bsz, t, h, nope + vd)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_pe[:, :, None, :], (bsz, t, h, rope))], -1)
    kk, vv = _operand(k, precision), _operand(kvb[..., nope:], precision)

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
    outs = lax.map(jax.checkpoint(attend), (q_blocks, jnp.arange(n) * blk))
    o = jnp.moveaxis(outs, 0, 1).reshape(bsz, n * blk, h, vd)[:, :t]
    return _mm(o.reshape(bsz, t, h * vd), p[pre + "Wo"], precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    return _mm(_silu(_mm(x, w_gate, precision)) * _mm(x, w_up, precision),
               w_down, precision)


def route(m, x, w_r, bias, precision):
    """(weights (.., top_k), expert ids (.., top_k)) over ALL experts: the
    bias takes part in the choice only."""
    s = jax.nn.sigmoid(_mm(x, w_r, precision))
    _, idx = lax.top_k(s + bias, m["top_k"])
    chosen = jnp.take_along_axis(s, idx, -1)
    return m["scale"] * chosen / jnp.sum(chosen, -1, keepdims=True), idx


def routed_part(m, p, pre, x, precision, offset=None, held=None):
    """What the experts ``offset .. offset + held`` add: a plain loop with
    a mask, one expert after another over EVERY token (``lax.scan`` over
    the experts, each rematerialised). ``p[pre + "Wgate"]`` and kin hold
    those experts."""
    offset = m["expert_offset"] if offset is None else offset
    held = m["experts_held"] if held is None else held
    bias = jnp.zeros((m["experts_total"],), jnp.float32)   # frozen at zero
    w, idx = route(m, x, p[pre + "Wr"], bias, precision)

    @jax.checkpoint
    def part(x, w, e, w_gate, w_up, w_down):
        weight = jnp.sum(jnp.where(idx == offset + e, w, 0.0), -1)
        return weight[..., None] * swiglu(x, w_gate, w_up, w_down, precision)

    def one(y, expert):
        return y + part(x, w, *expert), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (jnp.arange(held), p[pre + "Wgate"][:held],
                     p[pre + "Wup"][:held], p[pre + "Wdown"][:held]))
    return y


def shared_part(p, pre, x, precision):
    return swiglu(x, p[pre + "Sgate"], p[pre + "Sup"], p[pre + "Sdown"],
                  precision)


def moe(m, p, pre, x, precision):
    return routed_part(m, p, pre, x, precision) \
        + shared_part(p, pre, x, precision)


def _block(cfg_json: str, blk_json: str, precision: str, p, x):
    cfg, blk = json.loads(cfg_json), json.loads(blk_json)
    m, n = dims(cfg), blk["name"]
    a = norm(x, p[n + "_attn_norm/g"], m["eps"])
    h = x + attention(m, p, n + "_attn/", a, precision)
    f = norm(h, p[n + "_ffn_norm/g"], m["eps"])
    if blk["ffn"] == "dense":
        y = swiglu(f, p[n + "_ffn/Wgate"], p[n + "_ffn/Wup"],
                   p[n + "_ffn/Wdown"], precision)
    else:
        y = moe(m, p, n + "_ffn/", f, precision)
    return h + y


def _run_block(cfg: dict, blk: dict, params, x, precision: str):
    """One block, rematerialised in the backward pass so that the float32
    activations of the timed sequence fit beside the weights and Adam's
    state on one chip."""
    own = {k: v for k, v in params.items()
           if k.startswith((blk["name"] + "_attn", blk["name"] + "_ffn"))}
    run = functools.partial(_block, json.dumps(cfg, sort_keys=True),
                            json.dumps(blk, sort_keys=True), precision)
    return jax.checkpoint(run)(own, x)


def trunk(cfg: dict, params, ids, precision: str = "highest"):
    """h^L: the last kept layer's output (B, T, d), BEFORE the final
    norm."""
    x = params["embed/W"][ids]
    for blk in blocks(cfg):
        if not blk["module"]:
            x = _run_block(cfg, blk, params, x, precision)
    return x


def combine(cfg: dict, params, h_last, next_ids, precision: str = "highest"):
    """m = [RMSNorm_h(h^L) ; RMSNorm_e(Emb(next ids))] W_eh."""
    eps = cfg["rms_norm_eps"]
    both = jnp.concatenate(
        [norm(h_last, params["mtp1_combine/h_norm"], eps),
         norm(params["embed/W"][next_ids], params["mtp1_combine/e_norm"],
              eps)], -1)
    return _mm(both, params["mtp1_combine/W"], precision)


def states(cfg: dict, params, ids, labels, precision: str = "highest"):
    """(the trunk's state after ``final_norm``, the module's after
    ``RMSNorm_mtp``), each (B, T, d). The module reads the embedding of
    the LABELS: Emb(t_{i+1}) at position i."""
    eps = cfg["rms_norm_eps"]
    h_last = trunk(cfg, params, ids, precision)
    module = next(b for b in blocks(cfg) if b["module"])
    x = combine(cfg, params, h_last, labels, precision)
    x = _run_block(cfg, module, params, x, precision)
    return (norm(h_last, params["final_norm/g"], eps),
            norm(x, params["mtp1_norm/g"], eps))


def logits(cfg: dict, params, ids, precision: str = "highest"):
    """The trunk's logits (B, T, vocab): small sizes only."""
    x = norm(trunk(cfg, params, ids, precision), params["final_norm/g"],
             cfg["rms_norm_eps"])
    return _mm(x, params["head/W"], precision)


def _cross_entropy_sum(x, w, want, keep, precision: str):
    """sum over the kept rows of CE(x w, want), a block of ``TOKEN_BLOCK``
    token rows at a time (each rematerialised: one block's logits are
    alive at a time). ``x`` (rows, d), ``want``, ``keep`` (rows,)."""
    count = x.shape[0]
    blk = min(TOKEN_BLOCK, count)
    pad = (-count) % blk
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        want, keep = jnp.pad(want, (0, pad)), jnp.pad(keep, (0, pad))

    def block_loss(args):
        r, ids_, k = args
        logp = jax.nn.log_softmax(_mm(r, w, precision), -1)
        picked = jnp.take_along_axis(logp, ids_[:, None], -1)[:, 0]
        return -jnp.sum(jnp.where(k, picked, 0.0))

    return jnp.sum(lax.map(jax.checkpoint(block_loss),
                           (x.reshape(-1, blk, x.shape[-1]),
                            want.reshape(-1, blk), keep.reshape(-1, blk))))


def loss_terms(cfg: dict, params, ids, labels, precision: str = "highest"):
    """(L_main, L_mtp): both over 1 / (B T); the module is scored against
    the labels one step further on, its last position having none."""
    main, module = states(cfg, params, ids, labels, precision)
    bsz, t, d = main.shape
    labels = labels.astype(jnp.int32)
    every = jnp.ones((bsz * t,), bool)
    l_main = _cross_entropy_sum(main.reshape(-1, d), params["head/W"],
                                labels.reshape(-1), every, precision)
    further = jnp.concatenate([labels[:, 1:], jnp.zeros((bsz, 1), jnp.int32)],
                              1)
    has_one = jnp.broadcast_to(jnp.arange(t) < t - 1, (bsz, t))
    l_mtp = _cross_entropy_sum(module.reshape(-1, d), params["head/W"],
                               further.reshape(-1), has_one.reshape(-1),
                               precision)
    return l_main / (bsz * t), l_mtp / (bsz * t)


def loss(cfg: dict, params, ids, labels, precision: str = "highest"):
    """L = L_main + ``mtp_weight`` x L_mtp."""
    l_main, l_mtp = loss_terms(cfg, params, ids, labels, precision)
    return l_main + cfg["mtp_weight"] * l_mtp


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
    # of 561M float32 parameters and the gradient are what fits
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

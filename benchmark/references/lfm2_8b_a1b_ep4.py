"""Plain reference of the ``lfm2_8b_a1b_ep4`` configuration.

LFM2-8B-A1B (``config.json`` of the Hugging Face repository, ``model_type``
``lfm2_moe``; the token mixer is ``Lfm2ShortConv`` of the public code) on
the training path: forward, loss, gradients and Adam in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``. With d =
``hidden_size``, h = ``num_attention_heads``, h_kv =
``num_key_value_heads``, d_h = d / h, L = ``conv_L_cache``, T tokens at
positions p = 0..T-1 and i a layer's index from 0, every block is

    a   = norm(x)                  (x * rsqrt(mean x^2 + eps) * g, plain g)
    where ``layer_types[i]`` is ``conv``:
      [B | C | u] = a W_in         (W_in d x 3d, split in that order)
      z   = B * u
      c_t = sum_{j=0..L-1} w[j] * z_{t-(L-1)+j}   a channel, zeros before 0
      o   = (C * c) W_out          (no activation, no bias)
    where it is ``full_attention``:
      q   = W_q a  (h x d_h),   k = W_k a,   v = W_v a   (h_kv x d_h)
      q,k = norm_head(q), norm_head(k)   (over d_h, one weight vector each)
      q,k = rope(q, p), rope(k, p)   (all d_h widths, pairs j, j + d_h/2,
                                      by p * theta^(-2j/d_h))
      s[t,u] = q_t . k_u / sqrt(d_h)  for u <= t, -inf elsewhere
      o   = W_o softmax_u(s) v ;  k / v head n serves query heads n h/h_kv ..
    h1  = x + o
    n   = norm(h1)
    i < ``num_dense_layers``:  y = h1 + W_down (SiLU(W_gate n) * W_up n)
    otherwise:  s = sigmoid(W_r n) over ALL published experts ;  the top-k
          of s + bias (the bias 0: frozen at its start) ;
          w_e = scale * s_e / (sum_topk s + ``renorm_eps``)
          y   = h1 + sum over chosen experts HELD HERE of
                     w_e W_down,e (SiLU(W_gate,e n) * W_up,e n)

and a final norm and the head follow: ``logits = norm(y_last) E^T`` with E
the embedding's own matrix where ``tie_word_embeddings`` (ONE leaf,
``embed/W``: its gradient is the sum of the gather's and the head's terms),
a leaf ``head/W`` of its own otherwise.

Departures from the published model, each to match what the configuration
states it runs (its ``assumed`` and ``deployment``):

* this chip's share of a 4-way deployment: ``num_experts`` experts of the
  ``published.num_experts`` are held (``expert_offset`` onward); what the
  absent experts would add is left out, and that partial sum goes on; there
  is no shared expert;
* the vocabulary is a slice (``vocab_size`` rows): ids, logits and loss are
  over the slice;
* the tied head, the ``renorm_eps`` of the router's renormalising sum and
  the final norm's place are ``assumed`` (the catalog's row strips the
  first, the public code has the other two);
* the router's selection bias is layer state that no gradient trains: zero
  at the start and left there (as the Kimi and JoyAI references);
* memory devices only, the arithmetic stays plain: each block is
  rematerialised as a whole, the attention runs a block of queries at a
  time against the WHOLE score row with an explicit mask (no tiles), each
  held expert's feed-forward runs over EVERY token with a mask (one
  ``lax.scan`` over the experts), the loss a block of token rows at a time.

``precision`` lowers only the operands of matrix products (``bf16``:
rounded to bfloat16; ``fp8``: scaled per tensor and rounded to
float8_e4m3fn, the control of the correctness check); products accumulate
in float32. The gates and the taps of the short convolution are no matrix
products and stay float32 under every ``precision``.

Nothing of ``deeplearning4j_tpu`` is imported. Parameter names follow the
zoo builder's vertex names (``l3_conv/Win``) only so that the benchmark can
hand the same seeded weights to both sides."""

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
    heads = cfg["num_attention_heads"]
    return {
        "d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // heads,
        "taps": cfg["conv_L_cache"],
        "dense_ff": cfg["intermediate_size"],
        "dense_layers": cfg["num_dense_layers"],
        "expert_ff": cfg["moe_intermediate_size"],
        "experts_held": cfg["num_experts"],
        "experts_total": cfg["published"]["num_experts"],
        "expert_offset": cfg.get("expert_offset", 0),
        "top_k": cfg["num_experts_per_tok"],
        "scale": float(cfg["routed_scaling_factor"]),
        "renorm_eps": float(cfg["renorm_eps"]),
        "rope_theta": float(cfg["rope_theta"]),
        "eps": cfg["norm_eps"],
        "tied": bool(cfg["tie_word_embeddings"]),
    }


def blocks(cfg: dict) -> List[dict]:
    """Every block kept: its published index (from 0), its token mixer
    (``"attn"``: ``"full"`` for an attention layer, whose vertex is
    ``<name>_attn``, with ``window`` None; ``"conv"`` for a gated short
    convolution, whose vertex is ``<name>_conv``) and its feed-forward
    kind."""
    if cfg.get("conv_bias"):
        raise NotImplementedError("biases on the short convolution")
    out = []
    for i in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][i]
        if kind not in ("conv", "full_attention"):
            raise NotImplementedError(f"layer type {kind!r}")
        out.append({"index": i, "name": f"l{i}",
                    "attn": "full" if kind == "full_attention" else "conv",
                    "window": None,
                    "ffn": ("dense" if i < cfg["num_dense_layers"]
                            else "moe")})
    return out


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Every leaf's shape, in a fixed order (the order seeds are folded
    in). A tied head has no leaf: it reads ``embed/W``."""
    m = dims(cfg)
    d, h, hkv, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    out = {"embed/W": (m["vocab"], d)}
    for blk in blocks(cfg):
        n = blk["name"]
        out[f"{n}_op_norm/g"] = (d,)
        if blk["attn"] == "conv":
            c = f"{n}_conv/"
            out[c + "Win"] = (d, 3 * d)
            out[c + "w"] = (m["taps"], d)
            out[c + "Wout"] = (d, d)
        else:
            a = f"{n}_attn/"
            out[a + "Wq"] = (d, h * dh)
            out[a + "Wk"] = (d, hkv * dh)
            out[a + "Wv"] = (d, hkv * dh)
            out[a + "q_norm"] = (dh,)
            out[a + "k_norm"] = (dh,)
            out[a + "Wo"] = (h * dh, d)
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
    out["final_norm/g"] = (d,)
    if not m["tied"]:
        out["head/W"] = (d, m["vocab"])
    return out


def count_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


# ------------------------------------------------------------------- FLOPs
def kept_positions(tokens: int, window: Optional[int] = None) -> int:
    """(query, key) pairs the causal mask keeps in one sequence of
    ``tokens``: query t sees t + 1 keys (no layer of this model has a
    window; the argument is the sibling references')."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def layers(cfg: dict) -> List[dict]:
    """What ``harness.flops`` counts, per token of a sequence of
    ``cfg["sequence_length"]``: every product as ``kind: "dense"`` with
    ``positions``. Projections once a token (the short convolution's two
    products: d x 3d and d x d); the attention's scores and values at the
    mean number of keys a query sees, (T + 1) / 2; routed experts
    top_k x held / total a token; the tied head's product once. Embedding
    gather, norms, rotation, the gates and taps of the short convolution
    (7 operations a channel) and the router's top-k are not matrix products
    and are left out."""
    m = dims(cfg)
    t = cfg["sequence_length"]
    d, h, hkv, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    out = []

    def add(name, n_in, n_out, positions=1.0):
        out.append({"name": name, "kind": "dense", "n_in": n_in,
                    "n_out": n_out, "positions": positions})

    for blk in blocks(cfg):
        n = blk["name"]
        if blk["attn"] == "conv":
            add(n + "_conv.in", d, 3 * d)
            add(n + "_conv.out", d, d)
        else:
            keys = kept_positions(t) / t
            add(n + "_attn.q", d, h * dh)
            add(n + "_attn.kv", d, 2 * hkv * dh)
            add(n + "_attn.scores", dh, keys, h)
            add(n + "_attn.values", keys, dh, h)
            add(n + "_attn.o", h * dh, d)
        if blk["ffn"] == "dense":
            add(n + "_ffn", d, 3 * m["dense_ff"])
        else:
            add(n + "_ffn.router", d, m["experts_total"])
            add(n + "_ffn.routed", d, 3 * m["expert_ff"],
                m["top_k"] * m["experts_held"] / m["experts_total"])
    add("head", d, m["vocab"])
    return out


def attend_cost(cfg: dict, tokens: int, window: Optional[int] = None,
                itemsize: int = 2) -> dict:
    """Operations and bytes one attention layer's ``rattn.attend`` scope
    needs for one sequence of ``tokens`` tokens, forward once: the two
    products (q k^T and p v) over the (query, key) POSITIONS the mask
    keeps, for each of the query heads, whatever tile visits them. Bytes: q
    read and the output written once a query head, k and v read once a
    KEY/VALUE head (that the program repeats them over their group of 4 in
    front of its kernels, and pads 64 widths to the lanes' 128, is its own
    cost, under the scope and so in the measured time). A training step:
    the forward twice (rematerialised) and the backward, which makes five
    products a position: 4.5 x. (The sibling references' convention.)"""
    m = dims(cfg)
    h, hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    flops = h * kept_positions(tokens, window) * 2 * 2 * d
    nbytes = itemsize * tokens * d * (2 * h + 2 * hkv)
    return {"flops": float(flops), "bytes": float(nbytes)}


def gate_conv_cost(cfg: dict, tokens: int, backward: bool = False,
                   itemsize: int = 2) -> dict:
    """Operations and bytes one short-convolution layer's
    ``sconv.gate_conv`` scope (both gates and the taps: everything between
    the two products) needs for ``tokens`` tokens, whether XLA's fusions or
    a kernel run it. Forward: B, C and u read, the gated result written
    (4 d numbers a token: 16 KB at d = 2048 in bfloat16); a channel and
    token, one product for z, L products and L - 1 sums for the taps, one
    product for the output gate. Backward: B, C, u and the result's
    cotangent read, the three cotangents written (7 d numbers: 28 KB); z
    and the taps made again, the two gates' four products, the taps'
    transpose (2 L - 1) and the taps' own gradient (2 L) a channel and
    token. The taps and their gradient (L d numbers) are counted once. A
    rematerialised training step is the forward twice and the backward:
    60 KB a token, 0.98 GB and 1.2 ms a layer at 16,384 tokens against the
    chip's 819 GB/s; the operations (0.4 GFLOP) bind nothing."""
    m = dims(cfg)
    d, taps = m["d"], m["taps"]
    if backward:
        flops = tokens * d * ((1 + 2 * taps - 1) + 4 + (2 * taps - 1)
                              + 2 * taps)
        numbers = tokens * 7 * d + 2 * taps * d
    else:
        flops = tokens * d * (1 + (2 * taps - 1) + 1)
        numbers = tokens * 4 * d + taps * d
    return {"flops": float(flops), "bytes": float(itemsize * numbers)}


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


def _leaf_recipe(name: str, shape: tuple, tied: bool) -> tuple:
    """(kind, scale) of one leaf's seeded draw."""
    leaf = name.split("/")[1]
    if leaf in ("g", "q_norm", "k_norm"):
        return "one_plus", 0.1
    if name == "embed/W":
        # tied, the table is the head's matrix too: the head's scale, so
        # that the logits start at a spread of one and not of sqrt(d)
        return "normal", math.sqrt(1.0 / shape[-1]) if tied else 1.0
    return "normal", math.sqrt(1.0 / shape[-2])


def _draw_leaf(key, index: int, kind: str, shape: tuple, scale: float):
    z = jax.random.normal(jax.random.fold_in(key, index), shape,
                          jnp.float32) * scale
    return 1.0 + z if kind == "one_plus" else z


def _recipes(cfg: dict) -> tuple:
    tied = dims(cfg)["tied"]
    return tuple((i, kind, shape, scale)
                 for i, (n, shape) in enumerate(param_shapes(cfg).items())
                 for kind, scale in [_leaf_recipe(n, shape, tied)])


@functools.partial(jax.jit, static_argnames=("recipes",))
def _draw(key, recipes):
    return [_draw_leaf(key, *recipe) for recipe in recipes]


@functools.partial(jax.jit, static_argnames=("recipes",))
def _change_norms(key, now, recipes):
    return [jnp.linalg.norm(a - _draw_leaf(key, *recipe))
            for a, recipe in zip(now, recipes)]


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Seeded float32 weights, made on the device in one jitted call:
    projections (the router and the convolution's taps among them)
    N(0, 1/fan_in), every norm weight (the per-head q/k norms' too)
    1 + 0.1 N(0, 1), the embedding N(0, 1/d) where the head is tied to it
    (N(0, 1) otherwise, as the siblings')."""
    return dict(zip(param_shapes(cfg), _draw(seed_key(seed), _recipes(cfg))))


def change_norms(cfg: dict, seed: int, now: Dict[str, jax.Array]) -> dict:
    """Norm of every leaf's change since ``init_params(cfg, seed)``, the
    starting weights made again leaf by leaf inside one jitted call: with
    569M parameters and Adam's state on the chip there is no room to keep
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
    """``x`` (B, T, H, d): every width turned, width j with width j + d/2,
    by t * theta^(-2j/d)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos = jnp.cos(angle)[:, None, :]
    sin = jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(m, p, pre, x, precision):
    """The gated short convolution: ``(C * conv(B * u)) W_out``. The taps
    one shifted copy after another, zeros before the start."""
    d, taps = m["d"], m["taps"]
    t = x.shape[1]
    bcu = _mm(x, p[pre + "Win"], precision)
    b, c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    z = b * u
    w = p[pre + "w"]
    mixed = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j              # tap j reads the step ``back`` ago
        shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]
        mixed = mixed + w[j] * shifted
    return _mm(c * mixed, p[pre + "Wout"], precision)


def attention(m, p, pre, x, precision, rope=None, window=None):
    """Causal grouped-query attention with per-head q/k norms and a plain
    rotation (``rope`` and ``window`` are the sibling references'
    arguments: this model turns every layer by ``rope_theta`` and has no
    window)."""
    bsz, t, _ = x.shape
    h, hkv, dh = m["heads"], m["kv_heads"], m["head_dim"]
    group = h // hkv
    q = _mm(x, p[pre + "Wq"], precision).reshape(bsz, t, h, dh)
    k = _mm(x, p[pre + "Wk"], precision).reshape(bsz, t, hkv, dh)
    v = _mm(x, p[pre + "Wv"], precision).reshape(bsz, t, hkv, dh)
    q = rotate(norm(q, p[pre + "q_norm"], m["eps"]), m["rope_theta"])
    k = rotate(norm(k, p[pre + "k_norm"], m["eps"]), m["rope_theta"])
    # query head j reads k/v head j // group: no repeat
    q = q.reshape(bsz, t, hkv, group, dh)
    kk, vv = _operand(k, precision), _operand(v, precision)

    def attend(args):
        q_blk, start = args
        s = jnp.einsum("bqngd,bknd->bngqk", _operand(q_blk, precision), kk,
                       precision=lax.Precision.HIGHEST) / math.sqrt(dh)
        # a padded row past the end stands at the last real position and
        # is cut off below
        rows = jnp.minimum(start + jnp.arange(q_blk.shape[1]), t - 1)[:, None]
        keep = jnp.arange(t)[None, :] <= rows
        w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bngqk,bknd->bqngd", _operand(w, precision), vv,
                          precision=lax.Precision.HIGHEST)

    # one block of queries after another (lax.map), each against the whole
    # score row
    blk = min(QUERY_BLOCK, t)
    pad = (-t) % blk
    qp = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3) if pad else q
    n = (t + pad) // blk
    q_blocks = jnp.moveaxis(qp.reshape(bsz, n, blk, hkv, group, dh), 1, 0)
    outs = lax.map(jax.checkpoint(attend), (q_blocks, jnp.arange(n) * blk))
    o = jnp.moveaxis(outs, 0, 1).reshape(bsz, n * blk, h, dh)[:, :t]
    return _mm(o.reshape(bsz, t, h * dh), p[pre + "Wo"], precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    return _mm(_silu(_mm(x, w_gate, precision)) * _mm(x, w_up, precision),
               w_down, precision)


def route(m, x, w_r, bias, precision):
    """(weights (.., top_k), expert ids (.., top_k)) over ALL experts: the
    bias takes part in the choice only."""
    s = jax.nn.sigmoid(_mm(x, w_r, precision))
    _, idx = lax.top_k(s + bias, m["top_k"])
    chosen = jnp.take_along_axis(s, idx, -1)
    total = jnp.sum(chosen, -1, keepdims=True) + m["renorm_eps"]
    return m["scale"] * chosen / total, idx


def moe(m, p, pre, x, precision, offset=None, held=None):
    """What the experts ``offset .. offset + held`` add (the whole layer:
    there is no shared expert): a plain loop with a mask, one expert after
    another over EVERY token (``lax.scan`` over the experts, each
    rematerialised). ``p[pre + "Wgate"]`` and kin hold those experts."""
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


def _block(cfg_json: str, blk_json: str, precision: str, p, x):
    cfg, blk = json.loads(cfg_json), json.loads(blk_json)
    m, n = dims(cfg), blk["name"]
    a = norm(x, p[n + "_op_norm/g"], m["eps"])
    if blk["attn"] == "conv":
        h = x + short_conv(m, p, n + "_conv/", a, precision)
    else:
        h = x + attention(m, p, n + "_attn/", a, precision)
    f = norm(h, p[n + "_ffn_norm/g"], m["eps"])
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
    return norm(x, params["final_norm/g"], cfg["norm_eps"])


def head_matrix(cfg: dict, params):
    """(d, vocab): the embedding's own matrix transposed where the head is
    tied to it, the leaf ``head/W`` otherwise."""
    if cfg["tie_word_embeddings"]:
        return params["embed/W"].T
    return params["head/W"]


def logits(cfg: dict, params, ids, precision: str = "highest"):
    return _mm(hidden(cfg, params, ids, precision), head_matrix(cfg, params),
               precision)


def loss(cfg: dict, params, ids, labels, precision: str = "highest"):
    """Mean over all positions of the cross-entropy of the next id, a block
    of ``TOKEN_BLOCK`` token rows at a time (each rematerialised: one
    block's logits are alive at a time)."""
    x = hidden(cfg, params, ids, precision)
    w_head = head_matrix(cfg, params)
    rows = x.reshape(-1, x.shape[-1])
    want = labels.reshape(-1).astype(jnp.int32)
    count = rows.shape[0]
    blk = min(TOKEN_BLOCK, count)
    pad = (-count) % blk
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        want = jnp.pad(want, (0, pad))
    real = (jnp.arange(count + pad) < count).reshape(-1, blk)

    def block_loss(args):
        r, ids_, keep = args
        logp = jax.nn.log_softmax(_mm(r, w_head, precision), -1)
        picked = jnp.take_along_axis(logp, ids_[:, None], -1)[:, 0]
        return -jnp.sum(jnp.where(keep, picked, 0.0))

    sums = lax.map(jax.checkpoint(block_loss),
                   (rows.reshape(-1, blk, rows.shape[-1]),
                    want.reshape(-1, blk), real))
    return jnp.sum(sums) / count


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
    # of 569M float32 parameters and the gradient are what fits
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

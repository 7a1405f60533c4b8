"""Plain reference of the ``granite_4p0_h_micro_pp4_vp8`` configuration.

granite-4.0-h-micro (``config.json`` of the Hugging Face repository,
``model_type`` ``granitemoehybrid``; the state-space mixer is Mamba-2,
arXiv:2405.21060, as the public ``GraniteMoeHybridMambaLayer`` runs it) on
the training path: forward, loss, gradients and Adam in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``. With ids
(time,), E the embedding (vocabulary, d), i the published layer index from
0, every norm ``x * rsqrt(mean x^2 + eps) * g`` with a plain weight, m_e =
``embedding_multiplier``, m_r = ``residual_multiplier``, m_a =
``attention_multiplier``, m_l = ``logits_scaling``:

    x_0 = m_e E[ids]
    h = x + m_r Mix_i(norm(x));   y = h + m_r FFN(norm(h))
    logits = (norm(y_last) E^T) / m_l         (the head is E itself)

    Mix_i where ``layer_types[i]`` is ``mamba`` (d_in = H P, H heads of P,
    a state of N, G groups, K taps):
      [z | xBC | dt] = u W_in     (d_in, d_in + 2 G N and H columns, in
                                   THAT order, no bias)
      xBC = SiLU(conv_K(xBC) + b_conv)    (depthwise, causal, zeros before
                                           the start: K shifted sums)
      [x | B | C] = xBC                   (d_in, G N and G N columns)
      dt_t,h = softplus(dt_t,h + dt_bias_h),   A_h = -exp(A_log_h)
      S_t = exp(dt_t,h A_h) S_{t-1} + dt_t,h B_t x_t,h^T   (N x P a head,
                                   from zero, TOKEN BY TOKEN: ``lax.scan``)
      y_t,h = S_t^T C_t + D_h x_t,h
      g = y * SiLU(z);  g = w_norm * g / sqrt(mean(g^2) + eps), the mean
          over the d_in / G columns of a group (the gate first:
          ``norm_before_gate`` false in the public code)
      Mix(u) = g W_out
    where it is ``attention`` (h query heads over h_kv key/value heads of
    d_h = d / h, no bias, no q/k norm, NO rotation or other position term):
      s[t,u] = m_a q_t . k_u  for u <= t, -inf elsewhere (the whole row)
      Mix(u) = W_o softmax_u(s) v ;  k / v head n serves query heads
               n h/h_kv ..
    FFN(u) = W_down (SiLU(W_gate u) * W_up u)    at shared_intermediate_size

Departures from the published model, each to match what the configuration
states it runs (its ``assumed`` and ``deployment``):

* the first pipeline stage's ten layers, with the last stage's final norm,
  logit scale and tied head on top so that the step has the model's loss;
* the vocabulary is a slice (``vocab_size`` rows): ids, logits and loss are
  over the slice, and the tied matrix is the slice's;
* how ``A_log``, ``D`` and ``dt_bias`` start (``mamba_init``), the gated
  norm's order, no clamp on dt and the split order of ``W_in`` are
  ``assumed``: values of the configuration's file, which this module reads
  (and raises on one it does not compute);
* memory devices only, the arithmetic stays plain: the recurrence's
  ``lax.scan`` runs in segments that are rematerialised (a state a segment
  is kept, not a state a token), the attention runs a block of queries at a
  time against the WHOLE score row with an explicit mask (no tiles), the
  loss a block of token rows at a time; and the training step is the chain
  rule over jitted pieces (a block forward, a block backward, the head),
  each block's Adam update applied as soon as its gradient is there: with
  772M parameters, weights, both moments and ONE block's gradient are what
  fits beside the activations (compiled whole, the step held every leaf's
  gradient at once). The nine Mamba blocks share one compiled piece.

``precision`` lowers only the operands of matrix products (``bf16``:
rounded to bfloat16; ``fp8``: scaled per tensor and rounded to
float8_e4m3fn, the control of the correctness check); products accumulate
in float32. x, B and C are such operands (the chunked form's products read
them); the taps, the gates and the state itself stay float32.

Nothing of ``deeplearning4j_tpu`` is imported. Parameter names follow the
zoo builder's vertex names (``l3_ssm/Win``) only so that the benchmark can
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
SEGMENT = 128         # recurrence steps rematerialised together


# ---------------------------------------------------------------- structure
def dims(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    if h * p != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    if (cfg.get("mamba_in_proj_order", "z|xBC|dt") != "z|xBC|dt"
            or cfg.get("mamba_time_step_limit", [0.0, None])
            != [0.0, None]):
        raise NotImplementedError(
            "this reference splits W_in as z | xBC | dt and does not "
            "clamp dt")
    return {
        "d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "heads": heads, "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // heads,
        "ssm_heads": h, "ssm_head_dim": p, "state": n, "groups": g,
        "taps": cfg["mamba_d_conv"], "inner": h * p,
        "conv_cols": h * p + 2 * g * n,
        "in_cols": 2 * h * p + 2 * g * n + h,
        "chunk": cfg["mamba_chunk_size"],
        "ff": cfg["shared_intermediate_size"],
        "eps": cfg["rms_norm_eps"],
        "m_e": float(cfg["embedding_multiplier"]),
        "m_r": float(cfg["residual_multiplier"]),
        "m_a": float(cfg["attention_multiplier"]),
        "m_l": float(cfg["logits_scaling"]),
    }


def blocks(cfg: dict) -> List[dict]:
    """Every block kept: its published index (from 0), its name and its
    token mixer (``"attn"``: ``"ssm"`` for a Mamba-2 layer, whose vertex is
    ``<name>_ssm``; ``"nope"`` for a position-free attention layer, whose
    vertex is ``<name>_attn``, with ``window`` None)."""
    if cfg.get("num_local_experts", 0):
        raise NotImplementedError("routed experts")
    if cfg.get("position_embedding_type") != "nope":
        raise NotImplementedError("a position term in the attention")
    if cfg.get("mamba_proj_bias") or cfg.get("attention_bias"):
        raise NotImplementedError("biases on the projections")
    out = []
    for i in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][i]
        if kind not in ("mamba", "attention"):
            raise NotImplementedError(f"layer type {kind!r}")
        out.append({"index": i, "name": f"l{i}",
                    "attn": "ssm" if kind == "mamba" else "nope",
                    "window": None})
    return out


def _mixer_shapes(m: dict, kind: str, conv_bias: bool) -> Dict[str, tuple]:
    d = m["d"]
    if kind == "ssm":
        out = {"Win": (d, m["in_cols"]),
               "conv": (m["taps"], m["conv_cols"])}
        if conv_bias:
            out["conv_b"] = (m["conv_cols"],)
        out.update({"dt_bias": (m["ssm_heads"],), "A_log": (m["ssm_heads"],),
                    "D": (m["ssm_heads"],), "norm": (m["inner"],),
                    "Wout": (m["inner"], d)})
        return out
    h, hkv, dh = m["heads"], m["kv_heads"], m["head_dim"]
    return {"Wq": (d, h * dh), "Wk": (d, hkv * dh), "Wv": (d, hkv * dh),
            "Wo": (h * dh, d)}


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Every leaf's shape, in a fixed order (the order seeds are folded
    in). The tied head has no leaf: it reads ``embed/W``."""
    if not cfg["tie_word_embeddings"]:
        raise NotImplementedError("an untied head")
    m = dims(cfg)
    d = m["d"]
    out = {"embed/W": (m["vocab"], d)}
    for blk in blocks(cfg):
        n = blk["name"]
        out[f"{n}_mix_norm/g"] = (d,)
        vertex = n + ("_ssm/" if blk["attn"] == "ssm" else "_attn/")
        for leaf, shape in _mixer_shapes(m, blk["attn"],
                                         cfg["mamba_conv_bias"]).items():
            out[vertex + leaf] = shape
        out[f"{n}_ffn_norm/g"] = (d,)
        out[f"{n}_ffn/Wgate"] = (d, m["ff"])
        out[f"{n}_ffn/Wup"] = (d, m["ff"])
        out[f"{n}_ffn/Wdown"] = (m["ff"], d)
    out["final_norm/g"] = (d,)
    return out


def count_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


# ------------------------------------------------------------------- costs
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
    ``positions``. Projections once a token; a Mamba layer's scan as the
    chunked algorithm needs it (``ssd_scan_cost``): a group's C B^T and a
    head's mixing at the mean number of steps a step sees inside its
    chunk, (L + 1) / 2, and the two state products (C S_0 read, B^T X
    written) a head; the attention's scores and values at the mean number
    of keys a query sees, (T + 1) / 2; the tied head's product once.
    Embedding gather, norms, the taps, the gates, the exponentials and the
    four multipliers are no matrix products and are left out."""
    m = dims(cfg)
    t = cfg["sequence_length"]
    d, h, hkv, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    out = []

    def add(name, n_in, n_out, positions=1.0):
        out.append({"name": name, "kind": "dense", "n_in": n_in,
                    "n_out": n_out, "positions": positions})

    for blk in blocks(cfg):
        n = blk["name"]
        if blk["attn"] == "ssm":
            inside = (min(m["chunk"], t) + 1) / 2
            add(n + "_ssm.in", d, m["in_cols"])
            add(n + "_ssm.scores", m["state"], inside, m["groups"])
            add(n + "_ssm.mix", inside, m["ssm_head_dim"], m["ssm_heads"])
            add(n + "_ssm.state", m["state"], 2 * m["ssm_head_dim"],
                m["ssm_heads"])
            add(n + "_ssm.out", m["inner"], d)
        else:
            keys = kept_positions(t) / t
            add(n + "_attn.q", d, h * dh)
            add(n + "_attn.kv", d, 2 * hkv * dh)
            add(n + "_attn.scores", dh, keys, h)
            add(n + "_attn.values", keys, dh, h)
            add(n + "_attn.o", h * dh, d)
        add(n + "_ffn", d, 3 * m["ff"])
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


def ssd_scan_cost(cfg: dict, tokens: int, backward: bool = False,
                  itemsize: int = 2) -> dict:
    """Operations and bytes one Mamba layer's ``ssm.scan`` scope needs for
    ``tokens`` tokens: the ALGORITHM's least at the published chunk L,
    whether XLA's fusions or a kernel run it. Forward, a chunk: C B^T over
    the L (L + 1) / 2 positions the mask keeps, once a GROUP (2 N a
    position); a head's mixing (G o L_h) Diag(dt) X over the same positions
    (2 P); the two state products, C S_0 and B^T X (2 L N P each, a head).
    The exponentials and the masks (L^2 / 2 a head) are no products and
    bind nothing beside them. Bytes: x, B and C read and y written in the
    compute type, dt read in float32; the states stay on the chip.
    Backward: every forward product has two (dY X^T and M^T dY for the
    mixing; dG B and dG^T C for the scores; for each state product the
    cotangent of either operand), so twice the forward's operations, the
    factors made again from x, B, C, dt and not read; x, B, C, dt and dy
    read, dx, dB, dC and ddt written. A rematerialised training step is the
    forward twice and the backward."""
    m = dims(cfg)
    h, p, g, n = m["ssm_heads"], m["ssm_head_dim"], m["groups"], m["state"]
    length = min(m["chunk"], tokens)
    chunks = tokens / length
    kept = length * (length + 1) / 2
    forward = chunks * (g * 2 * n * kept
                        + h * (2 * p * kept + 2 * 2 * length * n * p))
    wide = m["inner"] + 2 * g * n            # x, B, C: numbers a token
    if backward:
        flops = 2 * forward
        nbytes = tokens * (itemsize * (2 * wide + 2 * m["inner"]) + 2 * 4 * h)
    else:
        flops = forward
        nbytes = tokens * (itemsize * (wide + m["inner"]) + 4 * h)
    return {"flops": float(flops), "bytes": float(nbytes)}


def conv_gate_cost(cfg: dict, tokens: int, backward: bool = False,
                   itemsize: int = 2) -> dict:
    """Operations and bytes one Mamba layer's ``ssm.conv`` and
    ``ssm.gate_norm`` scopes need for ``tokens`` tokens (everything between
    the two wide products but the scan). Forward: the convolution reads
    xBC and dt out of the product's output and writes SiLU(conv + bias) and
    softplus(dt + bias) (2 C numbers a token in the compute type, C = d_in
    + 2 G N, and 2 H in float32); the gated norm reads y and z and writes
    the normalised product (3 d_in). A column and token: K products, K - 1
    sums, the bias and a SiLU (4) for the convolution; a SiLU, the gate,
    the square, its sum and two scalings (9) for the norm. Backward: the
    convolution reads its input and its output's cotangent and writes its
    input's (3 C, 3 H in float32; the SiLU's derivative made again: 6, the
    taps' transpose 2 K - 1, the taps' own gradient 2 K); the norm reads y,
    z and its output's cotangent and writes two cotangents (5 d_in; about
    twice the forward's operations). The bound is bytes."""
    m = dims(cfg)
    c, inner, h, taps = m["conv_cols"], m["inner"], m["ssm_heads"], m["taps"]
    if backward:
        flops = tokens * (c * (2 * taps + 4 + 6 + 2 * taps - 1 + 2 * taps)
                          + 18 * inner)
        nbytes = tokens * (itemsize * (3 * c + 5 * inner) + 4 * 3 * h)
    else:
        flops = tokens * (c * (2 * taps + 4) + 9 * inner)
        nbytes = tokens * (itemsize * (2 * c + 3 * inner) + 4 * 2 * h)
    return {"flops": float(flops), "bytes": float(nbytes)}


# ------------------------------------------------------------------ weights
def seed_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed it and
    the rest is folded in (a plain ``jax.random.key`` takes 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _leaf_recipe(name: str, shape: tuple, init: dict) -> tuple:
    """(kind, two numbers) of one leaf's seeded draw."""
    leaf = name.split("/")[1]
    if leaf in ("g", "norm"):
        return "one_plus", 0.1, 0.0
    if leaf == "A_log":
        if init["A_log"] != "log_arange_1_to_heads":
            raise NotImplementedError(f"A_log as {init['A_log']!r}")
        return "log_arange", 0.0, 0.0
    if leaf == "D":
        return "constant", float(init["D"]), 0.0
    if leaf == "dt_bias":
        return "step_bias", float(init["dt_min"]), float(init["dt_max"])
    if leaf == "conv_b":
        return "normal", float(init["conv_bias_std"]), 0.0
    if name == "embed/W":
        # the table is the head's matrix too: the head's scale
        return "normal", math.sqrt(1.0 / shape[-1]), 0.0
    return "normal", math.sqrt(1.0 / shape[-2]), 0.0


def _draw_leaf(key, index: int, kind: str, shape: tuple, a: float, b: float):
    key = jax.random.fold_in(key, index)
    if kind == "log_arange":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    if kind == "constant":
        return jnp.full(shape, a, jnp.float32)
    if kind == "step_bias":
        # the inverse softplus of a step log-uniform in [a, b]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(b) - math.log(a)) + math.log(a))
        return dt + jnp.log(-jnp.expm1(-dt))
    z = jax.random.normal(key, shape, jnp.float32) * a
    return 1.0 + z if kind == "one_plus" else z


def _recipes(cfg: dict) -> tuple:
    out = []
    for i, (n, shape) in enumerate(param_shapes(cfg).items()):
        kind, a, b = _leaf_recipe(n, shape, cfg["mamba_init"])
        out.append((i, kind, shape, a, b))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("recipes",))
def _draw(key, recipes):
    return [_draw_leaf(key, *recipe) for recipe in recipes]


@functools.partial(jax.jit, static_argnames=("recipes",))
def _change_norms(key, now, recipes):
    return [jnp.linalg.norm(a - _draw_leaf(key, *recipe))
            for a, recipe in zip(now, recipes)]


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Seeded float32 weights, made on the device in one jitted call:
    projections and the convolution's taps N(0, 1/fan_in), every norm
    weight (the gated norm's too) 1 + 0.1 N(0, 1), the tied embedding
    N(0, 1/d), the convolution's bias N(0, ``conv_bias_std``^2), and
    ``A_log``, ``D`` and ``dt_bias`` as ``mamba_init`` of the
    configuration's file says (the public initialiser: log(1..H), 1, the
    inverse softplus of a step log-uniform in [dt_min, dt_max])."""
    return dict(zip(param_shapes(cfg), _draw(seed_key(seed), _recipes(cfg))))


def change_norms(cfg: dict, seed: int, now: Dict[str, jax.Array]) -> dict:
    """Norm of every leaf's change since ``init_params(cfg, seed)``, the
    starting weights made again leaf by leaf inside one jitted call: with
    772M parameters and Adam's state on the chip there is no room to keep
    the start, or to make it again whole."""
    names = list(param_shapes(cfg))
    out = _change_norms(seed_key(seed), [now[n] for n in names],
                        _recipes(cfg))
    return {n: float(a) for n, a in zip(names, out)}


# ------------------------------------------------------------------ forward
def _operand(a, precision: str):
    """``a`` as a matrix-product operand at ``precision``. The low types
    are plain casts, so autodiff sends the cotangent through the same cast
    (see the ResNet50 reference)."""
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


def recurrence(x, dt, a_rate, bm, cm):
    """``y_t = S_t^T C_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t
    x_t^T`` from a zero state, one token after another. ``x`` (B, T, H,
    P), ``dt`` (B, T, H), ``a_rate`` (H,), ``bm``, ``cm`` (B, T, G, N); a
    group's B and C serve its H / G consecutive heads. The scan runs in
    segments whose steps are rematerialised in the backward pass."""
    bsz, t, h, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    per = h // groups

    def step(s, xs):
        xt, dtt, bt, ct = xs             # (B, H, P), (B, H), (B, G, N) x 2
        bt, ct = (jnp.repeat(a, per, axis=1) for a in (bt, ct))
        s = (jnp.exp(dtt * a_rate)[..., None, None] * s
             + (dtt[..., None] * bt)[..., :, None] * xt[..., None, :])
        return s, jnp.sum(s * ct[..., :, None], axis=-2)

    @jax.checkpoint
    def segment(s, xs):
        return lax.scan(step, s, xs)

    seg = max(d for d in range(1, min(SEGMENT, t) + 1) if t % d == 0)

    def by_segment(a):                   # (B, T, ...) -> (T/seg, seg, B, ...)
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((t // seg, seg) + a.shape[1:])

    s0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    _, y = lax.scan(segment, s0, tuple(map(by_segment, (x, dt, bm, cm))))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def conv_taps(x, w, bias=None):
    """Depthwise causal convolution as shifted sums: tap j reads the step
    K - 1 - j ago, zeros before the start."""
    taps, t = w.shape[0], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        out = out + w[j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :t]
    return out if bias is None else out + bias


def mixer(m, p, x, precision):
    """The Mamba-2 mixer (``p`` holds its leaves by their own names)."""
    bsz, t, _ = x.shape
    h, hp, g, n = m["ssm_heads"], m["ssm_head_dim"], m["groups"], m["state"]
    inner, conv = m["inner"], m["conv_cols"]
    zxbcdt = _mm(x, p["Win"], precision)
    z = zxbcdt[..., :inner]
    xbc = _silu(conv_taps(zxbcdt[..., inner:inner + conv], p["conv"],
                          p.get("conv_b")))
    dt = jax.nn.softplus(zxbcdt[..., inner + conv:] + p["dt_bias"])
    xs = xbc[..., :inner].reshape(bsz, t, h, hp)
    bm = xbc[..., inner:inner + g * n].reshape(bsz, t, g, n)
    cm = xbc[..., inner + g * n:].reshape(bsz, t, g, n)
    y = recurrence(_operand(xs, precision), dt, -jnp.exp(p["A_log"]),
                   _operand(bm, precision), _operand(cm, precision))
    y = y + p["D"][:, None] * xs
    gated = (y.reshape(bsz, t, g, inner // g)
             * _silu(z).reshape(bsz, t, g, inner // g))
    gated = gated * lax.rsqrt(jnp.mean(jnp.square(gated), -1, keepdims=True)
                              + m["eps"])
    return _mm(gated.reshape(bsz, t, inner) * p["norm"], p["Wout"],
               precision)


def attention(m, p, x, precision):
    """Causal grouped-query attention with no position term, its softmax
    scaled by ``attention_multiplier``."""
    bsz, t, _ = x.shape
    h, hkv, dh = m["heads"], m["kv_heads"], m["head_dim"]
    group = h // hkv
    q = _mm(x, p["Wq"], precision).reshape(bsz, t, hkv, group, dh)
    k = _mm(x, p["Wk"], precision).reshape(bsz, t, hkv, dh)
    v = _mm(x, p["Wv"], precision).reshape(bsz, t, hkv, dh)
    kk, vv = _operand(k, precision), _operand(v, precision)

    def attend(args):
        q_blk, start = args
        s = m["m_a"] * jnp.einsum(
            "bqngd,bknd->bngqk", _operand(q_blk, precision), kk,
            precision=lax.Precision.HIGHEST)
        # a padded row past the end stands at the last real position and
        # is cut off below
        rows = jnp.minimum(start + jnp.arange(q_blk.shape[1]), t - 1)[:, None]
        keep = jnp.arange(t)[None, :] <= rows
        w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bngqk,bknd->bqngd", _operand(w, precision), vv,
                          precision=lax.Precision.HIGHEST)

    blk = min(QUERY_BLOCK, t)
    pad = (-t) % blk
    qp = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3) if pad else q
    count = (t + pad) // blk
    q_blocks = jnp.moveaxis(qp.reshape(bsz, count, blk, hkv, group, dh), 1, 0)
    outs = lax.map(jax.checkpoint(attend),
                   (q_blocks, jnp.arange(count) * blk))
    o = jnp.moveaxis(outs, 0, 1).reshape(bsz, count * blk, h, dh)[:, :t]
    return _mm(o.reshape(bsz, t, h * dh), p["Wo"], precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    return _mm(_silu(_mm(x, w_gate, precision)) * _mm(x, w_up, precision),
               w_down, precision)


def block(cfg: dict, kind: str, precision: str, p, x):
    """One block; ``p`` holds its leaves as ``<part>/<leaf>`` with part one
    of ``mix_norm``, ``mix`` (the mixer's vertex), ``ffn_norm``, ``ffn``."""
    m = dims(cfg)
    own = {k[len("mix/"):]: v for k, v in p.items() if k.startswith("mix/")}
    a = norm(x, p["mix_norm/g"], m["eps"])
    mixed = (mixer if kind == "ssm" else attention)(m, own, a, precision)
    h = x + m["m_r"] * mixed
    f = norm(h, p["ffn_norm/g"], m["eps"])
    return h + m["m_r"] * swiglu(f, p["ffn/Wgate"], p["ffn/Wup"],
                                 p["ffn/Wdown"], precision)


def part_names(names, blk: dict) -> dict:
    """{the name ``block`` reads a leaf by: its name in the whole model},
    for those of ``names`` that are ``blk``'s."""
    n = blk["name"]
    vertex = n + ("_ssm/" if blk["attn"] == "ssm" else "_attn/")
    out = {}
    for k in names:
        if k.startswith(vertex):
            out["mix/" + k[len(vertex):]] = k
        elif k.startswith(n + "_"):
            out[k[len(n) + 1:]] = k
    return out


def own_leaves(params, blk: dict) -> dict:
    """A block's leaves under the part names ``block`` reads."""
    return {part: params[k] for part, k in part_names(params, blk).items()}


def embed(cfg: dict, table, ids):
    return float(cfg["embedding_multiplier"]) * table[ids]


def head_loss(cfg: dict, precision: str, head, x, labels):
    """Mean over all positions of the cross-entropy of the next id over
    ``(norm(x) E^T) / m_l``, a block of ``TOKEN_BLOCK`` token rows at a time
    (each rematerialised). ``head`` holds ``W`` (the table) and ``g``."""
    x = norm(x, head["g"], cfg["rms_norm_eps"])
    w_head, scale = head["W"].T, 1.0 / float(cfg["logits_scaling"])
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
        logp = jax.nn.log_softmax(_mm(r, w_head, precision) * scale, -1)
        picked = jnp.take_along_axis(logp, ids_[:, None], -1)[:, 0]
        return -jnp.sum(jnp.where(keep, picked, 0.0))

    sums = lax.map(jax.checkpoint(block_loss),
                   (rows.reshape(-1, blk, rows.shape[-1]),
                    want.reshape(-1, blk), real))
    return jnp.sum(sums) / count


def _head_leaves(params) -> dict:
    return {"W": params["embed/W"], "g": params["final_norm/g"]}


def hidden(cfg: dict, params, ids, precision: str = "highest"):
    """The last block's output (B, T, d), before the final norm."""
    x = embed(cfg, params["embed/W"], ids)
    for blk in blocks(cfg):
        x = block(cfg, blk["attn"], precision, own_leaves(params, blk), x)
    return x


def logits(cfg: dict, params, ids, precision: str = "highest"):
    x = norm(hidden(cfg, params, ids, precision), params["final_norm/g"],
             cfg["rms_norm_eps"])
    return (_mm(x, params["embed/W"].T, precision)
            / float(cfg["logits_scaling"]))


def loss(cfg: dict, params, ids, labels, precision: str = "highest"):
    """The model's loss as ONE function of every leaf (small sizes, and
    what ``loss_and_grads``'s pieces are held to)."""
    return head_loss(cfg, precision, _head_leaves(params),
                     hidden(cfg, params, ids, precision), labels)


# ----------------------------------------------------------------- training
def _static(fn):
    return jax.jit(fn, static_argnames=("cfg_json", "kind", "precision"))


@_static
def _block_forward(p, x, *, cfg_json, kind, precision):
    return block(json.loads(cfg_json), kind, precision, p, x)


@_static
def _block_backward(p, x, ct, *, cfg_json, kind, precision):
    _, pull = jax.vjp(functools.partial(block, json.loads(cfg_json), kind,
                                        precision), p, x)
    return pull(ct)


@functools.partial(jax.jit, static_argnames=("cfg_json", "precision"))
def _head_backward(head, x, labels, *, cfg_json, precision):
    value, (g_head, ct) = jax.value_and_grad(
        functools.partial(head_loss, json.loads(cfg_json), precision),
        argnums=(0, 1))(head, x, labels)
    return value, g_head, ct


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed_backward(g_table, ids, ct, *, scale):
    """The gather's term added to the head's: one leaf, two uses."""
    return g_table.at[ids].add(scale * ct)


@functools.partial(jax.jit, static_argnames=("upd_json",),
                   donate_argnums=(0, 2, 3))
def _adam(params, grads, m, v, t, *, upd_json):
    """One Adam step on some leaves; the weights and both moments are given
    up to it (the gradient has no output of its shape to serve). Also the
    norm of every leaf of the gradient."""
    upd = json.loads(upd_json)
    b1, b2 = upd["beta1"], upd["beta2"]
    norms = {k: jnp.linalg.norm(g) for k, g in grads.items()}
    m = {k: b1 * m[k] + (1 - b1) * grads[k] for k in params}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(grads[k]) for k in params}
    new = {k: params[k] - upd["learning_rate"]
           * (m[k] / (1 - b1 ** t))
           / (jnp.sqrt(v[k] / (1 - b2 ** t)) + upd["epsilon"])
           for k in params}
    return new, m, v, norms


def loss_and_grads(cfg: dict, params, ids, labels, precision: str = "highest",
                   on_grads=None):
    """(loss, its gradient by leaf): forward block by block keeping every
    block's input, the head, then backward block by block from the last.
    With ``on_grads`` (``train_steps``'s Adam), a group of leaves' gradient
    is handed over as soon as it is whole and not kept: {} is returned in
    the gradient's place."""
    kw = {"cfg_json": json.dumps(cfg, sort_keys=True), "precision": precision}
    blks = blocks(cfg)
    x = embed(cfg, params["embed/W"], ids)
    inputs = []
    for blk in blks:
        inputs.append(x)
        x = _block_forward(own_leaves(params, blk), x, kind=blk["attn"], **kw)
    value, g_head, ct = _head_backward(_head_leaves(params), x, labels, **kw)
    del x
    grads = {}
    give = on_grads or grads.update
    give({"final_norm/g": g_head["g"]})
    for blk in reversed(blks):
        g, ct = _block_backward(own_leaves(params, blk), inputs.pop(), ct,
                                kind=blk["attn"], **kw)
        names = part_names(params, blk)
        give({names[k]: v for k, v in g.items()})
        del g
    give({"embed/W": _embed_backward(
        g_head["W"], ids, ct, scale=float(cfg["embedding_multiplier"]))})
    return value, grads


@jax.jit
def _delta_norms(a, b):
    return {k: jnp.linalg.norm(a[k] - b[k]) for k in a}


def train_steps(cfg: dict, params, batches, precision: str = "highest",
                place=None, seed: Optional[int] = None) -> dict:
    """Follow the program's first steps from the same weights and rows.
    ``batches`` is a list of host ``(ids, labels)``. ``params`` is GIVEN UP
    (each group of leaves is donated to its update as its gradient
    arrives: a leaf is read by its block's backward before it is replaced,
    and the tied table last of all). With ``seed``, ``params`` are
    ``init_params(cfg, seed)`` and the parameters' change is taken against
    that start made again (``change_norms``); without it a copy is kept
    throughout (small sizes). Returns the loss of every step, the norm of
    every leaf of the first gradient, and the norm of every leaf's change
    after the last step."""
    place = place or jnp.asarray
    upd_json = json.dumps(cfg["updater"], sort_keys=True)
    with jax.default_matmul_precision("highest"):
        keep = None
        if seed is None:
            keep = {k: jnp.array(a, copy=True) for k, a in params.items()}
        p = params
        m = {k: jnp.zeros_like(a) for k, a in params.items()}
        v = {k: jnp.zeros_like(a) for k, a in params.items()}
        losses, first = [], None
        for t, (ids, labels) in enumerate(batches, start=1):
            norms, fresh = {}, {}

            def update(grads, t=t, norms=norms, fresh=fresh):
                names = list(grads)
                # the new weights wait in ``fresh``: blocks before this one
                # read nothing of it, but the tied table is read by the
                # embedding's gather at the start and by the head
                new, m_new, v_new, gn = _adam(
                    {k: p[k] for k in names}, grads,
                    {k: m.pop(k) for k in names},
                    {k: v.pop(k) for k in names}, float(t),
                    upd_json=upd_json)
                for k in names:
                    del p[k]
                fresh.update(new)
                m.update(m_new)
                v.update(v_new)
                norms.update(gn)

            value, _ = loss_and_grads(cfg, p, place(ids), place(labels),
                                      precision, on_grads=update)
            p = fresh
            losses.append(float(value))
            if first is None:
                first = {k: float(a) for k, a in norms.items()}
        del m, v
        if seed is None:
            delta = {k: float(a) for k, a in _delta_norms(p, keep).items()}
        else:
            delta = change_norms(cfg, seed, p)
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}

"""Plain reference of the ``phi4_mini_flash_pp6_vp8`` configuration.

Phi-4-mini-flash-reasoning (``config.json`` of the Hugging Face repository,
``model_type`` ``phi4flash``; the architecture is SambaY, arXiv:2507.06607,
with differential attention, arXiv:2410.05258, and the Mamba-1 mixer,
arXiv:2312.00752) on the training path: forward, loss, gradients and Adam
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
Width d = 2,560, 32 layers, no dropout, NO position term anywhere. With ids
(time,), E the embedding (vocabulary, d), i the PUBLISHED layer index from
0 and ``LN`` a layer norm with weight and bias at ``layer_norm_eps``:

    x_0 = E[ids]
    h = x + Mix_i(LN(x));   y = h + MLP_i(LN'(h))
    logits = LN_f(y_last) E^T                  (the head is E itself)

    MLP (every layer): [g | u] = x W_gu (d -> 2 x 10,240; held as the two
      leaves ``Wgate`` and ``Wup``), (SiLU(g) * u) W_d, no bias.

    Mamba-1 (even layers 0-16; d_in = 5,120, N = 16, K = 4 taps, R = 160):
      [xr | z] = x W_in                     (d -> 2 d_in, no bias)
      xc = SiLU(conv_K(xr) + b_c)           (depthwise, causal: K shifted
                                             sums, zeros before the start)
      [r | B | C] = xc W_x                  (d_in -> R + N + N, no bias)
      dt = softplus(r W_dt + b_dt)          (R -> d_in)
      A = -exp(A_log)                       (d_in x N)
      S_t[c,n] = exp(dt_t[c] A[c,n]) S_{t-1}[c,n] + dt_t[c] B_t[n] xc_t[c]
                                            (from S_{-1} = 0, TOKEN BY
                                             TOKEN: ``lax.scan``)
      y_t[c] = sum_n C_t[n] S_t[c,n] + D[c] xc_t[c]
      Mix(x) = (y * SiLU(z)) W_out          (d_in -> d, no bias)
      Layer 16 also hands y, BEFORE the gate, to the cross-decoder as its
      memory m.

    Differential attention (odd layers 1-17; 40 query and 20 key/value
    heads of 64):
      [q | k | v] = x W_qkv + b             (d -> 2,560 + 1,280 + 1,280)
      query heads (2j, 2j+1) are q1_j, q2_j (j < 20); key heads (2g, 2g+1)
      are k1_g, k2_g and V_g = [v_2g | v_2g+1], 128 wide (g < 10); pair j
      reads group g = j // 2
      A1_j = softmax(q1_j k1_g^T / 8 + mask) V_g,  A2_j from q2_j, k2_g
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init, four
        64-vectors a layer, lambda_init = 0.8 - 0.6 exp(-0.3 i)
      o_j = (1 - lambda_init) RMSNorm_128(A1_j - lambda A2_j)   (one
        128-wide weight a layer, eps 1e-5)
      Mix(x) = [o_0 .. o_19] W_o + b_o
      the mask causal within a WINDOW of 512 (512 keys with the query's
      own) at layers 1, 3, .., 15 and causal at layer 17, whose k and v the
      cross-decoder reads too. Every row is a WHOLE masked softmax.

    Gated memory unit (even layers 18-30):
      Mix(x) = (m * SiLU(x W_1)) W_2        (d -> d_in -> d, no bias)

    Cross-attention (odd layers 19-31): q = x W_q + b_q, the differential
      form above over layer 17's k and v under the causal mask, its own
      lambdas, norm weight, W_o, b_o; no W_k, W_v.

Departures from the published model, each to match what the configuration
states it runs (its ``assumed`` and ``deployment``):

* published layers 14-19 alone (``layer_indices``), with the first stage's
  embedding under them and the last stage's final norm and tied head on
  top so that the step has the model's loss;
* the vocabulary is a slice (``vocab_size`` rows): ids, logits and loss are
  over the slice, and the tied matrix is the slice's;
* the four Mamba-1 sizes, the layer kinds and where the window lies, the
  head pairing, ``lambda_init`` by layer index, which products have a bias,
  the ``[g | u]`` order and the initialiser are ``assumed``: values of the
  configuration's file, which this module reads (and raises on one it does
  not compute);
* memory devices only, the arithmetic stays plain: the recurrence's
  ``lax.scan`` runs in segments that are rematerialised (a state a segment
  is kept, not a state a token), the attention runs a block of queries at a
  time against the WHOLE score row with an explicit mask (no tiles), the
  loss a block of token rows at a time; and the training step is the chain
  rule over jitted pieces (a block forward, a block backward, the head),
  each block's Adam update applied as soon as its gradient is there. A
  block hands on, beside x, the values the cross-decoder reads (``m``,
  ``kv``) and takes their cotangents back: the gradient of layer 17's
  ``W_qkv`` and of layer 16's scan leaves is the sum over their readers.

``precision`` lowers only the operands of matrix products (``bf16``:
rounded to bfloat16; ``fp8``: scaled per tensor and rounded to
float8_e4m3fn, the control of the correctness check); products accumulate
in float32. The recurrence, the taps, the gates, the norms and the
softmaxes' statistics stay float32.

Nothing of ``deeplearning4j_tpu`` is imported. Parameter names follow the
zoo builder's vertex names (``l16_ssm/Win``) only so that the benchmark
can hand the same seeded weights to both sides."""

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

KINDS = ("mamba", "swa", "mamba_memory", "full_shared", "gmu", "cross")


# ---------------------------------------------------------------- structure
def dims(cfg: dict) -> dict:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    if (cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_expand"]) != (16, 4, 2):
        raise NotImplementedError(
            "Mamba-1 sizes other than the assumed d_state 16, d_conv 4, "
            "expand 2")
    rank = cfg["mamba_dt_rank"]
    if rank != -(-d // 16):
        raise NotImplementedError(f"dt_rank {rank}, not ceil({d} / 16)")
    if heads % 2 or kv % 2 or heads % kv or d % heads:
        raise ValueError("the heads do not pair up")
    wrong = [k for k, want in (
        ("attention_bias", True), ("mamba_proj_bias", False),
        ("mlp_bias", False), ("lm_head_bias", False),
        ("mlp_order", "gate|up"), ("head_pairing", "adjacent"),
        ("lambda_init", "0.8-0.6exp(-0.3i)"),
        ("tie_word_embeddings", True)) if cfg.get(k) != want]
    if wrong:
        raise NotImplementedError(f"this reference does not compute {wrong} "
                                  "as the configuration states them")
    return {
        "d": d, "vocab": cfg["vocab_size"], "heads": heads, "kv_heads": kv,
        "head_dim": d // heads, "inner": cfg["mamba_expand"] * d,
        "state": cfg["mamba_d_state"], "taps": cfg["mamba_d_conv"],
        "rank": rank, "ff": cfg["intermediate_size"],
        "eps": cfg["layer_norm_eps"], "window": cfg["sliding_window"],
    }


def derive_layer_types(cfg: dict) -> List[str]:
    """The kind of every PUBLISHED layer from ``mb_per_layer``, the
    published ``num_hidden_layers`` and ``sliding_window``: the
    self-decoder is the first half (Mamba and window attention by turns),
    the next two layers fill the cross-decoder's memory, the rest read it."""
    count = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    if cfg["mb_per_layer"] != 2 or count % 2 or not cfg["sliding_window"]:
        raise NotImplementedError("another layer pattern than Mamba and "
                                  "attention by turns under a window")
    half = count // 2
    return [("mamba" if i < half else "mamba_memory" if i == half else "gmu")
            if i % 2 == 0 else
            ("swa" if i < half else "full_shared" if i == half + 1
             else "cross") for i in range(count)]


def blocks(cfg: dict) -> List[dict]:
    """Every block kept: its published index (from 0), its name, its
    ``kind`` (one of ``KINDS``), its mixer's vertex, and for the readers
    that tell attention layers apart ``attn`` (``"swa"`` | ``"full"`` |
    ``"cross"`` for the three uses of the differential attention,
    ``"mamba1"``, ``"gmu"``) and ``window``."""
    kinds = derive_layer_types(cfg)
    if cfg.get("layer_types") and list(cfg["layer_types"]) != kinds:
        raise NotImplementedError("layer_types other than the derived ones")
    indices = cfg.get("layer_indices") or list(range(len(kinds)))
    if len(indices) != cfg["num_hidden_layers"]:
        raise ValueError("layer_indices and num_hidden_layers disagree")
    m = dims(cfg)
    out = []
    for i in indices:
        kind = kinds[i]
        attn = {"mamba": "mamba1", "mamba_memory": "mamba1", "gmu": "gmu",
                "swa": "swa", "full_shared": "full", "cross": "cross"}[kind]
        suffix = {"mamba1": "_ssm", "gmu": "_gmu"}.get(attn, "_attn")
        out.append({"index": i, "name": f"l{i}", "kind": kind, "attn": attn,
                    "vertex": f"l{i}{suffix}",
                    "window": m["window"] if kind == "swa" else None})
    made = set()
    for b in out:
        need = {"gmu": "mamba_memory", "cross": "full_shared"}.get(b["kind"])
        if need and need not in made:
            raise ValueError(f"layer {b['index']} reads a {need} layer and "
                             "none is kept before it")
        made.add(b["kind"])
    return out


def _mixer_shapes(m: dict, kind: str) -> Dict[str, tuple]:
    d, inner, n, rank = m["d"], m["inner"], m["state"], m["rank"]
    h, hkv, dh = m["heads"], m["kv_heads"], m["head_dim"]
    if kind in ("mamba", "mamba_memory"):
        return {"Win": (d, 2 * inner), "conv": (m["taps"], inner),
                "conv_b": (inner,), "Wx": (inner, rank + 2 * n),
                "Wdt": (rank, inner), "dt_bias": (inner,),
                "A_log": (inner, n), "D": (inner,), "Wout": (inner, d)}
    if kind == "gmu":
        return {"W1": (d, inner), "W2": (inner, d)}
    if kind == "cross":
        first = {"Wq": (d, h * dh), "bq": (h * dh,)}
    else:
        first = {"Wqkv": (d, (h + 2 * hkv) * dh),
                 "bqkv": ((h + 2 * hkv) * dh,)}
    return {**first, "Wo": (h * dh, d), "bo": (d,), "subln": (2 * dh,),
            "lambda_q1": (dh,), "lambda_k1": (dh,), "lambda_q2": (dh,),
            "lambda_k2": (dh,)}


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Every leaf's shape, in a fixed order (the order seeds are folded
    in). The tied head has no leaf: it reads ``embed/W``."""
    m = dims(cfg)
    d = m["d"]
    out = {"embed/W": (m["vocab"], d)}
    for blk in blocks(cfg):
        n = blk["name"]
        out[f"{n}_ln1/weight"] = (d,)
        out[f"{n}_ln1/bias"] = (d,)
        for leaf, shape in _mixer_shapes(m, blk["kind"]).items():
            out[f"{blk['vertex']}/{leaf}"] = shape
        out[f"{n}_ln2/weight"] = (d,)
        out[f"{n}_ln2/bias"] = (d,)
        out[f"{n}_ffn/Wgate"] = (d, m["ff"])
        out[f"{n}_ffn/Wup"] = (d, m["ff"])
        out[f"{n}_ffn/Wdown"] = (m["ff"], d)
    out["final_norm/weight"] = (d,)
    out["final_norm/bias"] = (d,)
    return out


def params_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def published_config(cfg: dict) -> dict:
    """The configuration with every published layer and the whole
    vocabulary: what ``reduced`` cut, put back."""
    out = {**cfg, **cfg["published"]}
    out.pop("layer_indices", None)
    return out


# ------------------------------------------------------------------- costs
def kept_positions(tokens: int, window: Optional[int] = None) -> int:
    """(query, key) pairs the mask keeps in one sequence of ``tokens``:
    query t sees t + 1 keys, under a window at most ``window``."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def layers(cfg: dict) -> List[dict]:
    """What ``harness.flops`` counts, per token of a sequence of
    ``cfg["sequence_length"]``: every MATRIX product as ``kind: "dense"``
    with ``positions``. Projections once a token; an attention's scores (64
    wide) and values (128 wide) for each of the 40 query heads at the mean
    number of keys a query sees; the tied head's product once. The
    selective recurrence is no matrix product (81,920 exponentials and
    multiply-adds a token and layer on the vector units) and is left out
    with the gather, the norms, the taps, the gates and the lambdas: a
    utilisation built on this count is of the MXU's work alone."""
    m = dims(cfg)
    t = cfg["sequence_length"]
    d, h, hkv, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    inner, n, rank = m["inner"], m["state"], m["rank"]
    out = []

    def add(name, n_in, n_out, positions=1.0):
        out.append({"name": name, "kind": "dense", "n_in": n_in,
                    "n_out": n_out, "positions": positions})

    for blk in blocks(cfg):
        v, kind = blk["vertex"], blk["kind"]
        if kind in ("mamba", "mamba_memory"):
            add(v + ".in", d, 2 * inner)
            add(v + ".x", inner, rank + 2 * n)
            add(v + ".dt", rank, inner)
            add(v + ".out", inner, d)
        elif kind == "gmu":
            add(v + ".w1", d, inner)
            add(v + ".w2", inner, d)
        else:
            keys = kept_positions(t, blk["window"]) / t
            add(v + ".q", d, h * dh)
            if kind != "cross":
                add(v + ".kv", d, 2 * hkv * dh)
            add(v + ".scores", dh, keys, h)
            add(v + ".values", keys, 2 * dh, h)
            add(v + ".o", h * dh, d)
        add(blk["name"] + "_ffn", d, 3 * m["ff"])
    add("head", d, m["vocab"])
    return out


def attend_cost(cfg: dict, tokens: int, window: Optional[int] = None,
                itemsize: int = 2) -> dict:
    """Operations and bytes one differential attention layer's
    ``dattn.attend`` scope needs for one sequence of ``tokens`` tokens,
    forward once: the two products (q k^T, 64 wide, and p V, 128 wide) over
    the (query, key) POSITIONS the mask keeps, for each of the 40 query
    heads, whatever tile visits them. Bytes: q read (64) and the output
    written (128) once a query head, k and v read once a KEY/VALUE head
    (that the program repeats k over 2 and V over 4 heads in front of its
    kernels, and pads 64 widths to the lanes' 128, is its own cost, under
    the scope and so in the measured time). A training step: the forward
    twice (rematerialised) and the backward, which makes five products a
    position: 4.5 x. (The sibling references' convention.)"""
    m = dims(cfg)
    h, hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    flops = h * kept_positions(tokens, window) * 2 * (d + 2 * d)
    nbytes = itemsize * tokens * (h * (d + 2 * d) + 2 * hkv * d)
    return {"flops": float(flops), "bytes": float(nbytes)}


def selective_scan_cost(cfg: dict, tokens: int, backward: bool = False,
                        itemsize: int = 2) -> dict:
    """Operations and bytes one Mamba-1 layer's ``mamba1.scan`` scope needs
    for ``tokens`` tokens: the ALGORITHM's least, whatever runs it. A
    (token, channel, state) element, forward: the product dt A, its
    exponential, the decayed state, dt x B, their sum, times C and into the
    sum over the states: 7 operations (14 backward: every product has two
    cotangents). Bytes: xc (the compute type) and dt (float32) read, B and C
    read, y written once a pass (the compute type); backward also dy read
    and the four cotangents written; the state stays on the chip. These are
    VECTOR operations: against the matrix unit's peak, the only peak
    ``harness/peaks.py`` has, they are nothing, so the roofline this cost
    gives is bound by BYTES, and a scan bound by the vector units reads
    well under 100% of it."""
    m = dims(cfg)
    inner, n = m["inner"], m["state"]
    element = tokens * inner * n
    row = inner * (itemsize + 4 + itemsize) + 2 * n * itemsize
    if backward:
        return {"flops": 14.0 * element,
                "bytes": float(tokens * (row + inner * (itemsize + 4)
                                         + 2 * n * itemsize))}
    return {"flops": 7.0 * element, "bytes": float(tokens * row)}


# ------------------------------------------------------------------ weights
def seed_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed it and
    the rest is folded in (a plain ``jax.random.key`` takes 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _leaf_recipe(name: str, shape: tuple, init: dict) -> tuple:
    """(kind, two numbers) of one leaf's seeded draw."""
    leaf = name.split("/")[1]
    if leaf in ("weight", "subln"):
        return "one_plus", 0.1, 0.0
    if leaf == "A_log":
        if init["A_log"] != "log_arange_1_to_states":
            raise NotImplementedError(f"A_log as {init['A_log']!r}")
        return "log_arange", 0.0, 0.0
    if leaf == "D":
        return "constant", float(init["D"]), 0.0
    if leaf == "dt_bias":
        return "step_bias", float(init["dt_min"]), float(init["dt_max"])
    if leaf.startswith("lambda_"):
        return "normal", float(init["lambda_std"]), 0.0
    if leaf in ("bias", "conv_b", "bqkv", "bq", "bo"):
        return "normal", float(init["bias_std"]), 0.0
    if name == "embed/W":
        # the table is the head's matrix too: the head's scale
        return "normal", math.sqrt(1.0 / shape[-1]), 0.0
    return "normal", math.sqrt(1.0 / shape[-2]), 0.0


def _draw_leaf(key, index: int, kind: str, shape: tuple, a: float, b: float):
    key = jax.random.fold_in(key, index)
    if kind == "log_arange":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
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
        kind, a, b = _leaf_recipe(n, shape, cfg["init"])
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
    weight (the 128-wide one too) 1 + 0.1 N(0, 1), every bias N(0,
    ``bias_std``^2) so that its gradient is checked, the tied embedding
    N(0, 1/d), the four lambda vectors N(0, ``lambda_std``^2), and
    ``A_log``, ``D`` and ``dt_bias`` as ``init`` of the configuration's
    file says (the public Mamba initialiser: log(1..N) a channel, 1, the
    inverse softplus of a step log-uniform in [dt_min, dt_max])."""
    return dict(zip(param_shapes(cfg), _draw(seed_key(seed), _recipes(cfg))))


def change_norms(cfg: dict, seed: int, now: Dict[str, jax.Array]) -> dict:
    """Norm of every leaf's change since ``init_params(cfg, seed)``, the
    starting weights made again leaf by leaf inside one jitted call: with
    697M parameters and Adam's state on the chip there is no room to keep
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


def layer_norm(x, weight, bias, eps: float):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return (centred * lax.rsqrt(jnp.mean(jnp.square(centred), -1,
                                         keepdims=True) + eps)
            * weight + bias)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def recurrence(x, dt, a_rate, bm, cm):
    """``y_t[c] = sum_n C_t[n] S_t[c,n]`` with ``S_t[c,n] = exp(dt_t[c]
    A[c,n]) S_{t-1}[c,n] + dt_t[c] B_t[n] x_t[c]`` from a zero state, one
    token after another. ``x``, ``dt`` (B, T, C), ``a_rate`` (C, N),
    ``bm``, ``cm`` (B, T, N). The scan runs in segments whose steps are
    rematerialised in the backward pass."""
    bsz, t, c = x.shape
    n = bm.shape[-1]

    def step(s, xs):
        xt, dtt, bt, ct = xs             # (B, C), (B, C), (B, N), (B, N)
        s = (jnp.exp(dtt[..., None] * a_rate) * s
             + (dtt * xt)[..., None] * bt[:, None, :])
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def segment(s, xs):
        return lax.scan(step, s, xs)

    seg = max(d for d in range(1, min(SEGMENT, t) + 1) if t % d == 0)

    def by_segment(a):                   # (B, T, ...) -> (T/seg, seg, B, ...)
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((t // seg, seg) + a.shape[1:])

    s0 = jnp.zeros((bsz, c, n), jnp.float32)
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


def selective_scan(m, p, x, precision):
    """The Mamba-1 mixer up to its gate: (y before the gate, z)."""
    inner, n, rank = m["inner"], m["state"], m["rank"]
    xz = _mm(x, p["Win"], precision)
    xc = _silu(conv_taps(xz[..., :inner], p["conv"], p["conv_b"]))
    rbc = _mm(xc, p["Wx"], precision)
    dt = jax.nn.softplus(_mm(rbc[..., :rank], p["Wdt"], precision)
                         + p["dt_bias"])
    y = recurrence(xc, dt, -jnp.exp(p["A_log"]), rbc[..., rank:rank + n],
                   rbc[..., rank + n:])
    return y + p["D"] * xc, xz[..., inner:]


def mamba(m, p, x, precision):
    """(the Mamba-1 mixer's output, its scan output before the gate)."""
    y, z = selective_scan(m, p, x, precision)
    return _mm(y * _silu(z), p["Wout"], precision), y


def gated_memory_unit(m, p, x, memory, precision):
    return _mm(memory * _silu(_mm(x, p["W1"], precision)), p["W2"],
               precision)


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def differential_attention(m, p, x, index: int, window, precision,
                           kv=None):
    """(the layer's output, its [k | v] columns). ``kv`` given: the
    cross-attention over another layer's keys and values."""
    bsz, t, _ = x.shape
    h, hkv, dh = m["heads"], m["kv_heads"], m["head_dim"]
    groups, pairs = hkv // 2, h // hkv   # pairs of query heads a group
    if kv is None:
        qkv = _mm(x, p["Wqkv"], precision) + p["bqkv"]
        q, kv = qkv[..., :h * dh], qkv[..., h * dh:]
    else:
        q = _mm(x, p["Wq"], precision) + p["bq"]
    q = q.reshape(bsz, t, groups, pairs, 2, dh)
    k = _operand(kv[..., :hkv * dh].reshape(bsz, t, groups, 2, dh),
                 precision)
    v = _operand(kv[..., hkv * dh:].reshape(bsz, t, groups, 2 * dh),
                 precision)

    def attend(args):
        q_blk, start = args
        s = jnp.einsum("bqgpsd,bkgsd->bgpsqk", _operand(q_blk, precision),
                       k, precision=lax.Precision.HIGHEST) / math.sqrt(dh)
        # a padded row past the end stands at the last real position and
        # is cut off below
        rows = jnp.minimum(start + jnp.arange(q_blk.shape[1]), t - 1)[:, None]
        keys = jnp.arange(t)[None, :]
        keep = keys <= rows
        if window is not None:
            keep = keep & (keys > rows - window)
        w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgpsqk,bkge->bqgpse", _operand(w, precision), v,
                          precision=lax.Precision.HIGHEST)

    blk = min(QUERY_BLOCK, t)
    pad = (-t) % blk
    qp = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 4) if pad else q
    count = (t + pad) // blk
    q_blocks = jnp.moveaxis(
        qp.reshape(bsz, count, blk, groups, pairs, 2, dh), 1, 0)
    outs = lax.map(jax.checkpoint(attend),
                   (q_blocks, jnp.arange(count) * blk))
    a = jnp.moveaxis(outs, 0, 1).reshape(
        bsz, count * blk, groups, pairs, 2, 2 * dh)[:, :t]
    start = lambda_init(index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + start)
    diff = a[..., 0, :] - lam * a[..., 1, :]
    o = (1.0 - start) * (diff * lax.rsqrt(
        jnp.mean(jnp.square(diff), -1, keepdims=True) + m["eps"])
        * p["subln"])
    return _mm(o.reshape(bsz, t, h * dh), p["Wo"], precision) + p["bo"], kv


def swiglu(x, w_gate, w_up, w_down, precision):
    return _mm(_silu(_mm(x, w_gate, precision)) * _mm(x, w_up, precision),
               w_down, precision)


def block(cfg: dict, kind: str, index: int, precision: str, p, x, shared):
    """One block: (its output, the values later blocks read). ``p`` holds
    its leaves as ``<part>/<leaf>`` with part one of ``ln1``, ``mix`` (the
    mixer's vertex), ``ln2``, ``ffn``; ``shared`` holds ``m`` (layer 16's
    scan output before its gate) and ``kv`` (layer 17's keys and values)
    once they are made, and is handed on whole."""
    m = dims(cfg)
    own = {k[len("mix/"):]: v for k, v in p.items() if k.startswith("mix/")}
    a = layer_norm(x, p["ln1/weight"], p["ln1/bias"], m["eps"])
    shared = dict(shared)
    if kind in ("mamba", "mamba_memory"):
        mixed, y = mamba(m, own, a, precision)
        if kind == "mamba_memory":
            shared["m"] = y
    elif kind == "gmu":
        mixed = gated_memory_unit(m, own, a, shared["m"], precision)
    elif kind == "cross":
        mixed, _ = differential_attention(m, own, a, index, None, precision,
                                          kv=shared["kv"])
    else:
        mixed, kv = differential_attention(
            m, own, a, index, m["window"] if kind == "swa" else None,
            precision)
        if kind == "full_shared":
            shared["kv"] = kv
    h = x + mixed
    f = layer_norm(h, p["ln2/weight"], p["ln2/bias"], m["eps"])
    return h + swiglu(f, p["ffn/Wgate"], p["ffn/Wup"], p["ffn/Wdown"],
                      precision), shared


def part_names(names, blk: dict) -> dict:
    """{the name ``block`` reads a leaf by: its name in the whole model},
    for those of ``names`` that are ``blk``'s."""
    n, vertex = blk["name"], blk["vertex"] + "/"
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
    return table[ids]


def head_loss(cfg: dict, precision: str, head, x, labels):
    """Mean over all positions of the cross-entropy of the next id over
    ``LN_f(x) E^T``, a block of ``TOKEN_BLOCK`` token rows at a time (each
    rematerialised). ``head`` holds ``W`` (the table), ``weight``, ``bias``."""
    x = layer_norm(x, head["weight"], head["bias"], cfg["layer_norm_eps"])
    w_head = head["W"].T
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


def _head_leaves(params) -> dict:
    return {"W": params["embed/W"], "weight": params["final_norm/weight"],
            "bias": params["final_norm/bias"]}


def hidden(cfg: dict, params, ids, precision: str = "highest"):
    """The last block's output (B, T, d), before the final norm."""
    x, shared = embed(cfg, params["embed/W"], ids), {}
    for blk in blocks(cfg):
        x, shared = block(cfg, blk["kind"], blk["index"], precision,
                          own_leaves(params, blk), x, shared)
    return x


def logits(cfg: dict, params, ids, precision: str = "highest"):
    head = _head_leaves(params)
    x = layer_norm(hidden(cfg, params, ids, precision), head["weight"],
                   head["bias"], cfg["layer_norm_eps"])
    return _mm(x, params["embed/W"].T, precision)


def loss(cfg: dict, params, ids, labels, precision: str = "highest"):
    """The model's loss as ONE function of every leaf (small sizes, and
    what ``loss_and_grads``'s pieces are held to)."""
    return head_loss(cfg, precision, _head_leaves(params),
                     hidden(cfg, params, ids, precision), labels)


# ----------------------------------------------------------------- training
def _static(fn):
    return jax.jit(fn, static_argnames=("cfg_json", "kind", "index",
                                        "precision"))


@_static
def _block_forward(p, x, shared, *, cfg_json, kind, index, precision):
    return block(json.loads(cfg_json), kind, index, precision, p, x, shared)


@_static
def _block_backward(p, x, shared, ct, *, cfg_json, kind, index, precision):
    """``ct`` is the cotangent of (the block's output, the values it hands
    on); returns those of its leaves, its input and the values it was
    handed."""
    _, pull = jax.vjp(functools.partial(block, json.loads(cfg_json), kind,
                                        index, precision), p, x, shared)
    return pull(ct)


@functools.partial(jax.jit, static_argnames=("cfg_json", "precision"))
def _head_backward(head, x, labels, *, cfg_json, precision):
    value, (g_head, ct) = jax.value_and_grad(
        functools.partial(head_loss, json.loads(cfg_json), precision),
        argnums=(0, 1))(head, x, labels)
    return value, g_head, ct


@jax.jit
def _embed_backward(g_table, ids, ct):
    """The gather's term added to the head's: one leaf, two uses."""
    return g_table.at[ids].add(ct)


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
    block's input and the values it was handed, the head, then backward
    block by block from the last, the shared values' cotangents carried
    beside x's. With ``on_grads`` (``train_steps``'s Adam), a group of
    leaves' gradient is handed over as soon as it is whole and not kept: {}
    is returned in the gradient's place."""
    kw = {"cfg_json": json.dumps(cfg, sort_keys=True), "precision": precision}
    blks = blocks(cfg)
    x, shared = embed(cfg, params["embed/W"], ids), {}
    inputs = []
    for blk in blks:
        inputs.append((x, shared))
        x, shared = _block_forward(own_leaves(params, blk), x, shared,
                                   kind=blk["kind"], index=blk["index"], **kw)
    value, g_head, ct = _head_backward(_head_leaves(params), x, labels, **kw)
    del x
    ct_shared = jax.tree_util.tree_map(jnp.zeros_like, shared)
    del shared
    grads = {}
    give = on_grads or grads.update
    give({"final_norm/weight": g_head["weight"],
          "final_norm/bias": g_head["bias"]})
    for blk in reversed(blks):
        x_in, shared_in = inputs.pop()
        g, ct, ct_shared = _block_backward(
            own_leaves(params, blk), x_in, shared_in, (ct, ct_shared),
            kind=blk["kind"], index=blk["index"], **kw)
        names = part_names(params, blk)
        give({names[k]: v for k, v in g.items()})
        del g, x_in, shared_in
    give({"embed/W": _embed_backward(g_head["W"], ids, ct)})
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

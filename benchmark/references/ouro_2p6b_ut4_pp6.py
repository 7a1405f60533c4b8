"""Plain reference of the ``ouro_2p6b_ut4_pp6`` configuration.

Ouro-2.6B (``config.json`` of the Hugging Face repository, ``model_type``
``ouro``; the equations are those of "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741, and of the model's public code) on the
training path: forward, loss, gradients and Adam in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``.

With d = ``hidden_size``, ``x_0 = E[ids]``, L the layers held, R =
``total_ut_steps``, pass r = 1..R is

    h <- x_{r-1};  for each layer l = 0..L-1, the SAME weights every pass:
        h <- h + N2_l(Attn_l(N1_l(h)))
        h <- h + N4_l(MLP_l(N3_l(h)))                (sandwich norms)
    x_r = N_f(h)       (the final norm closes every pass; pass r + 1 starts
                        from its output)

``N(x) = x / sqrt(mean(x^2) + eps) * g`` (plain weight). ``Attn``: q, k, v
= x W_q, x W_k, x W_v (no bias), ``num_attention_heads`` query and
``num_key_value_heads`` key/value heads of ``head_dim``, every width of q
and k rotated (width j paired with j + head_dim / 2, turned by
t * rope_theta^(-2j / head_dim), positions 0..T-1, no scaling), causal
softmax(q k^T / sqrt(head_dim)) v, then W_o. ``MLP``:
``(silu(x W_gate) * (x W_up)) W_down``. After every pass

    lambda_r = sigmoid(x_r w_g + b_g)         (the exit gate, a token)
    z_r = x_r W_head                           (untied head)
    p_r = lambda_r prod_{j<r} (1 - lambda_j)  for r < R,
    p_R = prod_{j<R} (1 - lambda_j)            (the exit distribution)
    loss = mean over tokens of [ sum_r p_r CE(z_r, label) - beta H(p) ],
    H(p) = -sum_r p_r log p_r,  beta = ``entropy_weight``

and the gradient flows into the gate, the head, the norms and the shared
layers. The loop is a Python ``for`` over the passes that reads the same
leaves every time (``passes``), and a layer's gradient is the sum of what
the four passes give it: autodiff's sum where ``loss`` is differentiated
whole (small sizes), an explicit ``+`` where the step is taken in pieces
(``loss_and_grads``, what ``train_steps`` runs).

Departures from the published model, each to match what the configuration
states it runs:

* ``num_hidden_layers`` layers are held, the first of six pipeline stages
  of 8: what loops here is this stage's block, and the final norm, the gate
  and the head (a deployment keeps them on the last stage) close every
  pass here;
* ``config.json`` carries ``total_ut_steps`` and ``early_exit_threshold``
  only: the sandwich norms, the gate after the norm and the objective are
  the paper's and the public code's (``assumed`` in the configuration's
  file); the later training stage that fits the gate alone against
  detached losses, and leaving early at inference, are not built;
* each layer of each pass is made again in the backward pass, the
  attention runs over blocks of queries against the whole score row, the
  feed-forward and the head over blocks of token rows, and the training
  step is the chain rule over jitted pieces (one layer, the final norm, the
  exits' loss), not one program: memory devices only.

``precision`` lowers only the operands of matrix products (``bf16``:
rounded to bfloat16; ``fp8``: scaled per tensor and rounded to
float8_e4m3fn, the control of the correctness check); products accumulate
in float32.

Nothing of ``deeplearning4j_tpu`` is imported. Parameter names follow the
zoo builder's vertex paths (``loop/l3_attn/attn/Wq``) only so that the
benchmark can hand the same seeded weights to both sides."""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256     # queries whose scores are alive together
TOKEN_BLOCK = 1024    # token rows whose logits are alive together


# ---------------------------------------------------------------- structure
def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "theta": float(cfg["rope_theta"]),
            "ff": cfg["intermediate_size"], "eps": cfg["rms_norm_eps"],
            "passes": cfg["total_ut_steps"]}


def blocks(cfg: dict) -> List[dict]:
    """Every layer held: its published index (from 0) and its kinds. Each
    is applied ``total_ut_steps`` times a step."""
    return [{"index": i, "name": f"l{i}", "attn": "rattn", "ffn": "dense"}
            for i in range(cfg["num_hidden_layers"])]


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Every leaf's shape, in a fixed order (the order seeds are folded
    in)."""
    m = dims(cfg)
    d, h, hkv, dh, ff = m["d"], m["heads"], m["kv_heads"], m["head_dim"], \
        m["ff"]
    out = {"embed/W": (m["vocab"], d)}
    for blk in blocks(cfg):
        a = f"loop/{blk['name']}_attn/"
        out[a + "pre/g"] = (d,)
        out[a + "attn/Wq"] = (d, h * dh)
        out[a + "attn/Wk"] = (d, hkv * dh)
        out[a + "attn/Wv"] = (d, hkv * dh)
        out[a + "attn/Wo"] = (h * dh, d)
        out[a + "post/g"] = (d,)
        f = f"loop/{blk['name']}_ffn/"
        out[f + "pre/g"] = (d,)
        out[f + "ffn/Wgate"] = (d, ff)
        out[f + "ffn/Wup"] = (d, ff)
        out[f + "ffn/Wdown"] = (ff, d)
        out[f + "post/g"] = (d,)
    out["loop/final_norm/g"] = (d,)
    out["head/W"] = (d, m["vocab"])
    out["head/Wg"] = (d, 1)
    out["head/bg"] = (1,)
    return out


def count_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


# ------------------------------------------------------------------- FLOPs
def layers(cfg: dict) -> List[dict]:
    """What ``harness.flops`` counts, per token of a sequence of
    ``cfg["sequence_length"]``: every product as ``kind: "dense"`` with
    ``positions``, each layer's ``total_ut_steps`` times (a pass is work
    the algorithm needs, not recomputation), the head and the gate once a
    pass. The attention's scores and values at the causal mean of
    (T + 1) / 2 keys a query. Embedding gather, norms, rotation and the
    exit distribution are not matrix products and are left out."""
    m = dims(cfg)
    t = cfg["sequence_length"]
    d, h, hkv, dh, r = m["d"], m["heads"], m["kv_heads"], m["head_dim"], \
        m["passes"]
    out = []

    def add(name, n_in, n_out, positions=1.0):
        out.append({"name": name, "kind": "dense", "n_in": n_in,
                    "n_out": n_out, "positions": positions * r})

    for blk in blocks(cfg):
        n = blk["name"]
        add(n + "_attn.q", d, h * dh)
        add(n + "_attn.kv", d, 2 * hkv * dh)
        add(n + "_attn.scores", dh, (t + 1) / 2.0, h)
        add(n + "_attn.values", (t + 1) / 2.0, dh, h)
        add(n + "_attn.o", h * dh, d)
        add(n + "_ffn", d, 3 * m["ff"])
    add("head", d, m["vocab"])
    add("head.gate", d, 1)
    return out


def rattn_attend_cost(cfg: dict, tokens: int, tile: int = 512,
                      itemsize: int = 2) -> dict:
    """Operations and bytes one attention layer's ``rattn.attend`` scope
    needs for one sequence of ``tokens`` tokens IN THE FORM THE PROGRAM
    COMPUTES IT, forward once, ONE pass of the loop: the causal triangle's
    tile pairs (``tile`` x ``tile``, diagonal tiles whole: the kernels
    compute their masked halves), two products a pair (q k^T and p v) for
    each of the query heads. Bytes: q read and the output written once, a
    key and a value tile read once a pair and query head; where the heads
    are grouped, the repeat of k and v over their group as well. A
    training step: the forward twice (rematerialised) and the backward,
    which makes five products a pair (the scores again, dv, dp, dk, dq):
    4.5 x, for every pass."""
    m = dims(cfg)
    h, hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    n = -(-tokens // tile)
    pairs = n * (n + 1) // 2
    flops = h * pairs * 2 * 2 * tile * tile * d
    nbytes = itemsize * h * (2 * tokens * d + pairs * 2 * tile * d)
    if h != hkv:
        nbytes += itemsize * 2 * (hkv + h) * tokens * d
    return {"flops": float(flops), "bytes": float(nbytes)}


# ------------------------------------------------------------------ weights
def seed_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed it and
    the rest is folded in (a plain ``jax.random.key`` takes 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _leaf_recipe(name: str, shape: tuple) -> tuple:
    """(kind, scale) of one leaf's seeded draw."""
    leaf = name.rsplit("/", 1)[1]
    if leaf == "g":
        return "one_plus", 0.1
    if leaf == "bg":
        return "normal", 0.0
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
    projections, head and gate N(0, 1/fan_in), the embedding N(0, 1), the
    norms' weights 1 + 0.1 N(0, 1), the gate's bias 0."""
    return dict(zip(param_shapes(cfg), _draw(seed_key(seed), _recipes(cfg))))


def change_norms(cfg: dict, seed: int, now: Dict[str, jax.Array]) -> dict:
    """Norm of every leaf's change since ``init_params(cfg, seed)``, the
    starting weights made again leaf by leaf inside one jitted call: with
    612M parameters and Adam's state on the chip there is no room to keep
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
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * g


def rotate(x, theta: float):
    """``x`` (B, T, H, d): width j turned with width j + d / 2 by
    t * theta^(-2j / d)."""
    d = x.shape[-1]
    half = d // 2
    t = x.shape[1]
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(m, p, pre, x, precision):
    bsz, t, _ = x.shape
    h, hkv, dh = m["heads"], m["kv_heads"], m["head_dim"]
    group = h // hkv
    q = rotate(_mm(x, p[pre + "Wq"], precision).reshape(bsz, t, h, dh),
               m["theta"])
    k = rotate(_mm(x, p[pre + "Wk"], precision).reshape(bsz, t, hkv, dh),
               m["theta"])
    v = _mm(x, p[pre + "Wv"], precision).reshape(bsz, t, hkv, dh)
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
    return _mm(o.reshape(bsz, t, h * dh), p[pre + "Wo"], precision)


def _row_blocks(fn, rows, *more):
    """``fn`` over blocks of ``TOKEN_BLOCK`` rows of ``rows`` (N, d) and
    of every array of ``more`` (N, ...), one block after another
    (``lax.map``), each rematerialised: one block's inner arrays are
    alive. Returns (N, ...)."""
    n = rows.shape[0]
    blk = min(TOKEN_BLOCK, n)
    pad = (-n) % blk
    arrays = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) if pad
              else a for a in (rows,) + more]
    out = lax.map(jax.checkpoint(lambda args: fn(*args)), tuple(
        a.reshape((-1, blk) + a.shape[1:]) for a in arrays))
    return out.reshape((-1,) + out.shape[2:])[:n]


def swiglu(x, w_gate, w_up, w_down, precision):
    def rows(xb):
        return _mm(jax.nn.silu(_mm(xb, w_gate, precision))
                   * _mm(xb, w_up, precision), w_down, precision)

    return _row_blocks(rows, x.reshape(-1, x.shape[-1])).reshape(x.shape)


def _own(params, name: str) -> dict:
    """Layer ``name``'s leaves under names of their own (``attn/attn/Wq``,
    ``ffn/pre/g``): every layer's then look alike."""
    prefix = f"loop/{name}_"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def layer(cfg: dict, precision: str, p, h):
    """One layer with its four norms; ``p`` holds this layer's leaves
    (``_own``)."""
    m = dims(cfg)
    y = attention(m, p, "attn/attn/", norm(h, p["attn/pre/g"], m["eps"]),
                  precision)
    h = h + norm(y, p["attn/post/g"], m["eps"])
    y = swiglu(norm(h, p["ffn/pre/g"], m["eps"]), p["ffn/ffn/Wgate"],
               p["ffn/ffn/Wup"], p["ffn/ffn/Wdown"], precision)
    return h + norm(y, p["ffn/post/g"], m["eps"])


def passes(cfg: dict, params, ids, precision: str = "highest") -> list:
    """``[x_1, ..., x_R]``, each (B, T, d): a plain ``for`` over the passes
    and over the layers, the same leaves every pass (each layer of each
    pass rematerialised in the backward pass)."""
    x = params["embed/W"][ids]
    out = []
    for _ in range(cfg["total_ut_steps"]):
        h = x
        for blk in blocks(cfg):
            h = jax.checkpoint(functools.partial(layer, cfg, precision))(
                _own(params, blk["name"]), h)
        x = norm(h, params["loop/final_norm/g"], cfg["rms_norm_eps"])
        out.append(x)
    return out


def exit_probabilities(gates):
    """``p`` (R, ...) from the gates ``lambda`` (R, ...), as the equations
    write it; the last pass takes what is left."""
    p, stay = [], jnp.ones_like(gates[0])
    for lam in gates[:-1]:
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(p + [stay])


def _token_losses(x, w_head, labels, precision: str):
    """Cross-entropy of every token of ``x`` (B, T, d), token rows in
    blocks of ``TOKEN_BLOCK`` so that one block's logits are alive."""
    bsz, t, d = x.shape

    def one(xb, ib):
        logp = jax.nn.log_softmax(_mm(xb, w_head, precision), axis=-1)
        return -jnp.take_along_axis(logp, ib[:, None], -1)[:, 0]

    return _row_blocks(one, x.reshape(bsz * t, d),
                       labels.reshape(bsz * t).astype(jnp.int32)
                       ).reshape(bsz, t)


def logits(cfg: dict, params, ids, precision: str = "highest"):
    """The last pass's logits (what the model answers with when it never
    leaves early)."""
    return _mm(passes(cfg, params, ids, precision)[-1], params["head/W"],
               precision)


def _gates(head: dict, xs, precision: str) -> list:
    """``lambda_r`` a token for every pass's state of ``xs``."""
    return [jax.nn.sigmoid(_mm(x, head["Wg"], precision)[..., 0]
                           + head["bg"]) for x in xs]


def exit_loss(cfg: dict, head: dict, xs, labels, precision: str):
    """The loss from the passes' states ``xs`` ([x_1..x_R]) and the leaves
    of ``head`` (``W``, ``Wg``, ``bg``)."""
    p = exit_probabilities(_gates(head, xs, precision))
    ce = jnp.stack([_token_losses(x, head["W"], labels, precision)
                    for x in xs])
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-38)),
                                 0.0), 0)
    return jnp.mean(jnp.sum(p * ce, 0) - cfg["entropy_weight"] * entropy)


def _head(params) -> dict:
    return {k[len("head/"):]: v for k, v in params.items()
            if k.startswith("head/")}


def exits(cfg: dict, params, ids, precision: str = "highest"):
    """The exit distribution a token, (R, B, T)."""
    return exit_probabilities(_gates(
        _head(params), passes(cfg, params, ids, precision), precision))


def loss(cfg: dict, params, ids, labels, precision: str = "highest"):
    """Mean over all positions of the exit-weighted cross-entropy of the
    next id less ``entropy_weight`` times the exit distribution's
    entropy: the whole computation as one function (what ``jax.grad``
    differentiates at small sizes)."""
    return exit_loss(cfg, _head(params), passes(cfg, params, ids, precision),
                     labels, precision)


# -------------------------------------------------------- gradient, in pieces
# At the timed size the whole step as ONE program does not fit beside the
# weights and Adam's state: compiled whole, each of the 32 layer
# applications keeps its own copy of the weights' gradient (and of the
# weights as split for the float32 product) until the end. So the gradient
# is the chain rule written out over small jitted pieces, each ``jax.vjp``
# of a plain function above: a layer (compiled once, called for every layer
# of every pass), the final norm, the exits' loss, the embedding. The sum
# over the passes is the explicit ``+`` below. ``loss`` above is the same
# computation as one function, and the tests hold the two together.
def _static(fn):
    return functools.partial(jax.jit, static_argnames=("cfg_json",
                                                       "precision"))(fn)


@_static
def _layer_forward(p, h, *, cfg_json, precision):
    return layer(json.loads(cfg_json), precision, p, h)


@_static
def _layer_backward(p, h, ct, *, cfg_json, precision):
    _, pull = jax.vjp(functools.partial(layer, json.loads(cfg_json),
                                        precision), p, h)
    return pull(ct)


@_static
def _norm_forward(g, h, *, cfg_json, precision):
    return norm(h, g, json.loads(cfg_json)["rms_norm_eps"])


@_static
def _norm_backward(g, h, ct, *, cfg_json, precision):
    eps = json.loads(cfg_json)["rms_norm_eps"]
    _, pull = jax.vjp(lambda g_, h_: norm(h_, g_, eps), g, h)
    return pull(ct)


@_static
def _exits_backward(head, xs, labels, *, cfg_json, precision):
    value, pull = jax.vjp(
        lambda hd, xs_: exit_loss(json.loads(cfg_json), hd, xs_, labels,
                                  precision), head, xs)
    return (value,) + pull(jnp.ones_like(value))


@jax.jit
def _embed_backward(w, ids, ct):
    return jnp.zeros_like(w).at[ids].add(ct)


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def loss_and_grads(cfg: dict, params, ids, labels,
                   precision: str = "highest"):
    """(loss, its gradient by leaf): forward layer by layer keeping every
    layer application's input, then backward pass by pass from the last,
    each layer's gradient ADDED to what the later passes gave it."""
    kw = {"cfg_json": json.dumps(cfg, sort_keys=True), "precision": precision}
    names = [blk["name"] for blk in blocks(cfg)]
    own = {n: _own(params, n) for n in names}
    g_final = params["loop/final_norm/g"]
    x = params["embed/W"][ids]
    inputs, closed, xs = [], [], []
    for _ in range(cfg["total_ut_steps"]):
        h, kept = x, []
        for n in names:
            kept.append(h)
            h = _layer_forward(own[n], h, **kw)
        x = _norm_forward(g_final, h, **kw)
        inputs.append(kept)
        closed.append(h)
        xs.append(x)
    value, g_head, ct_xs = _exits_backward(_head(params), xs, labels, **kw)
    grads = {"head/" + k: v for k, v in g_head.items()}
    by_layer, ct_next = {}, None
    for r in reversed(range(cfg["total_ut_steps"])):
        ct = ct_xs[r] if ct_next is None else ct_xs[r] + ct_next
        g, ct = _norm_backward(g_final, closed[r], ct, **kw)
        grads["loop/final_norm/g"] = (
            g if r == cfg["total_ut_steps"] - 1
            else grads["loop/final_norm/g"] + g)
        for n, h in zip(reversed(names), reversed(inputs[r])):
            g, ct = _layer_backward(own[n], h, ct, **kw)
            by_layer[n] = _add(by_layer[n], g) if n in by_layer else g
        inputs[r] = closed[r] = None
        ct_next = ct
    for n, leaves in by_layer.items():
        grads.update({f"loop/{n}_{k}": v for k, v in leaves.items()})
    grads["embed/W"] = _embed_backward(params["embed/W"], ids, ct_next)
    return value, {k: grads[k] for k in params}


# ----------------------------------------------------------------- training
@functools.partial(jax.jit, static_argnames=("upd_json",),
                   donate_argnums=(0, 2, 3))
def _adam(params, grads, m, v, t, *, upd_json):
    """One Adam step; the weights and both moments are given up to it
    (three copies of 612M float32 parameters and the gradient are what
    fits). Also the norm of every leaf of the gradient."""
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


@jax.jit
def _delta_norms(a, b):
    return {k: jnp.linalg.norm(a[k] - b[k]) for k in a}


def train_steps(cfg: dict, params, batches, precision: str = "highest",
                place=None, seed: Optional[int] = None) -> dict:
    """Follow the program's first steps from the same weights and rows.
    ``batches`` is a list of host ``(ids, labels)``. ``params`` is GIVEN UP
    (donated to the first step's update). With ``seed``, ``params`` are
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
            value, g = loss_and_grads(cfg, p, place(ids), place(labels),
                                      precision)
            p, m, v, gn = _adam(p, g, m, v, float(t), upd_json=upd_json)
            del g
            losses.append(float(value))
            if first is None:
                first = {k: float(a) for k, a in gn.items()}
        del m, v
        if seed is None:
            delta = {k: float(a) for k, a in _delta_norms(p, keep).items()}
        else:
            delta = change_norms(cfg, seed, p)
    return {"losses": losses, "grad_norms": first, "delta_norms": delta}

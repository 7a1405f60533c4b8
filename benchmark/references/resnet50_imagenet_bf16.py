"""Plain reference of the ``resnet50_imagenet_bf16`` configuration.

ResNet-50 (He et al. 2015, arXiv:1512.03385, table 1, 50-layer column) as
the DL4J zoo builds it (zoo/model/ResNet50.java): 7x7/2 stem convolution,
batch norm, ReLU, 3x3/2 max pool, four stages of bottleneck blocks
(1x1 -> 3x3 -> 1x1, each followed by batch norm, projection shortcut on a
stage's first block), global average pool, dense softmax. Departures from
the paper, to match what the configuration runs:

* the stride of a stage's first block sits on its first 1x1 convolution
  and on the projection (the zoo's layout; the "v1" form), not on the 3x3;
* convolutions have no bias (a bias in front of batch norm is redundant);
* "same" padding is the TensorFlow rule: total = max((ceil(n/s)-1)*s+k-n, 0),
  the smaller half in front;
* batch norm normalises with the batch's biased variance, eps 1e-5;
* the loss is the mean over the batch of the cross-entropy, and the
  optimiser is Adam with bias correction (the configuration's updater).

Everything is float32 under ``jax.default_matmul_precision("highest")``;
``precision`` lowers only the operands of convolutions and of the dense
layer (``bf16``: rounded to bfloat16; ``fp8``: scaled per tensor and
rounded to float8_e4m3fn, the control of the correctness check), products
accumulate in float32.

Nothing of ``deeplearning4j_tpu`` is imported. Parameter names follow the
zoo's vertex names (``res3a_2b_conv/W``) only so that the benchmark can hand
the same seeded weights to both sides."""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

BLOCK_LETTERS = "abcdefghij"
CONVS_OF_BLOCK = ("2a", "2b", "2c")


# ---------------------------------------------------------------- structure
def _same_out(n: int, stride: int) -> int:
    return -(-n // stride)


def _same_pads(n: int, k: int, stride: int):
    total = max((_same_out(n, stride) - 1) * stride + k - n, 0)
    return total // 2, total - total // 2


def blocks(cfg: dict) -> List[dict]:
    """Every bottleneck block: its name, widths, stride and whether its
    shortcut is a projection."""
    out = []
    for stage in cfg["stages"]:
        for i in range(stage["blocks"]):
            out.append({
                "name": f"res{stage['name']}{BLOCK_LETTERS[i]}",
                "filters": list(stage["filters"]),
                "stride": stage["stride"] if i == 0 else 1,
                "project": i == 0,
            })
    return out


def convs(cfg: dict) -> List[dict]:
    """Every convolution with its shapes at this configuration's input
    size, in forward order; the dense layer is ``dense(cfg)``."""
    h, w, c = cfg["input_shape"]
    stem = cfg["stem"]
    out = []

    def add(name, h_in, w_in, k, stride, cin, cout):
        spec = {"name": name, "kind": "conv2d", "kh": k, "kw": k,
                "stride": stride, "cin": cin, "cout": cout,
                "h_out": _same_out(h_in, stride),
                "w_out": _same_out(w_in, stride)}
        out.append(spec)
        return spec["h_out"], spec["w_out"]

    h, w = add("stem", h, w, stem["kernel"], stem["stride"], c,
               stem["filters"])
    h, w = _same_out(h, stem["pool_stride"]), _same_out(w, stem["pool_stride"])
    c = stem["filters"]
    for blk in blocks(cfg):
        f1, f2, f3 = blk["filters"]
        h2, w2 = add(blk["name"] + "_2a", h, w, 1, blk["stride"], c, f1)
        add(blk["name"] + "_2b", h2, w2, 3, 1, f1, f2)
        add(blk["name"] + "_2c", h2, w2, 1, 1, f2, f3)
        if blk["project"]:
            add(blk["name"] + "_1", h, w, 1, blk["stride"], c, f3)
        h, w, c = h2, w2, f3
    return out


def dense(cfg: dict) -> dict:
    return {"name": "output", "kind": "dense",
            "n_in": cfg["stages"][-1]["filters"][2],
            "n_out": cfg["num_classes"]}


def layers(cfg: dict) -> List[dict]:
    """What ``harness.flops`` counts, per image."""
    return convs(cfg) + [dense(cfg)]


# ------------------------------------------------------------------ weights
@functools.partial(jax.jit, static_argnames=("shapes",))
def _draw(key, shapes):
    out = []
    for i, (kind, shape, scale) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32) * scale
        out.append(1.0 + z if kind == "one_plus" else z)
    return out


def seed_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed it and
    the rest is folded in (a plain ``jax.random.key`` takes 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Seeded float32 weights, made on the device in one jitted call:
    convolutions N(0, 2/fan_in) (He), batch-norm gamma 1 + 0.1 N(0,1) and
    beta 0.1 N(0,1) (away from the symmetric point, so every leaf has a
    gradient), dense N(0, 1/n_in) with bias 0.01 N(0,1)."""
    names, shapes = [], []
    for cv in convs(cfg):
        fan_in = cv["kh"] * cv["kw"] * cv["cin"]
        names.append(f"{cv['name']}_conv/W")
        shapes.append(("normal", (cv["kh"], cv["kw"], cv["cin"], cv["cout"]),
                       math.sqrt(2.0 / fan_in)))
        names.append(f"{cv['name']}_bn/gamma")
        shapes.append(("one_plus", (cv["cout"],), 0.1))
        names.append(f"{cv['name']}_bn/beta")
        shapes.append(("normal", (cv["cout"],), 0.1))
    d = dense(cfg)
    names += ["output/W", "output/b"]
    shapes += [("normal", (d["n_in"], d["n_out"]), math.sqrt(1.0 / d["n_in"])),
               ("normal", (d["n_out"],), 0.01)]
    return dict(zip(names, _draw(seed_key(seed), tuple(shapes))))


# ------------------------------------------------------------------ forward
def _operand(a, precision: str):
    """``a`` as a convolution or matmul operand at ``precision``. The low
    types are plain casts, so autodiff sends the cotangent through the same
    cast: ``fp8`` scales the operand per tensor (float8_e4m3fn holds
    2**-9 .. 448) and does nothing for the cotangent, which float8 then
    largely flushes to zero. That is the float8 computation a first attempt
    would write, and what the control has to be told apart by."""
    if precision == "highest":
        return a
    if precision == "bf16":
        return a.astype(jnp.bfloat16).astype(a.dtype)
    if precision == "fp8":
        scale = lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0)
        return (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _conv(x, w, stride: int, precision: str):
    k = w.shape[0]
    pads = (_same_pads(x.shape[1], k, stride), _same_pads(x.shape[2], k, stride))
    return lax.conv_general_dilated(
        _operand(x, precision), _operand(w, precision), (stride, stride), pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _batch_norm(x, gamma, beta, eps: float):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def _conv_bn(p, name, x, stride, eps, precision, relu=True):
    z = _conv(x, p[f"{name}_conv/W"], stride, precision)
    z = _batch_norm(z, p[f"{name}_bn/gamma"], p[f"{name}_bn/beta"], eps)
    return jnp.maximum(z, 0.0) if relu else z


def _max_pool(x, k: int, stride: int):
    pads = ((0, 0), _same_pads(x.shape[1], k, stride),
            _same_pads(x.shape[2], k, stride), (0, 0))
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, stride, stride, 1), pads)


def _block(p, blk_name, stride, project, eps, precision, x):
    y = _conv_bn(p, blk_name + "_2a", x, stride, eps, precision)
    y = _conv_bn(p, blk_name + "_2b", y, 1, eps, precision)
    y = _conv_bn(p, blk_name + "_2c", y, 1, eps, precision, relu=False)
    sc = (_conv_bn(p, blk_name + "_1", x, stride, eps, precision, relu=False)
          if project else x)
    return jnp.maximum(y + sc, 0.0)


def logits(cfg: dict, params, x, precision: str = "highest"):
    """Training-mode forward (batch statistics) to the pre-softmax scores.
    Each block is rematerialised in the backward pass so that the float32
    activations of the timed batch fit beside nothing else on one chip."""
    eps = cfg["batch_norm"]["eps"]
    stem = cfg["stem"]
    x = _conv_bn(params, "stem", x.astype(params["output/W"].dtype), stem["stride"], eps,
                 precision)
    x = _max_pool(x, stem["pool_kernel"], stem["pool_stride"])
    for blk in blocks(cfg):
        own = {k: v for k, v in params.items()
               if k.startswith(blk["name"] + "_")}

        def run(p, x, blk=blk):
            return _block(p, blk["name"], blk["stride"], blk["project"], eps,
                          precision, x)

        x = jax.checkpoint(run)(own, x)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(_operand(x, precision), _operand(params["output/W"],
                                                    precision),
                   precision=lax.Precision.HIGHEST) + params["output/b"]


def loss(cfg: dict, params, x, y, precision: str = "highest"):
    logp = jax.nn.log_softmax(logits(cfg, params, x, precision), axis=-1)
    return -jnp.mean(jnp.sum(y.astype(logp.dtype) * logp, axis=-1))


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

    @jax.jit
    def step(p, m, v, t, x, y):
        value, g = jax.value_and_grad(
            lambda q: loss(cfg, q, x, y, precision))(p)
        new, m, v = _adam(upd, p, g, m, v, t)
        return new, m, v, value, {k: jnp.linalg.norm(g[k]) for k in g}

    return step


@jax.jit
def _delta_norms(a, b):
    return {k: jnp.linalg.norm(a[k] - b[k]) for k in a}


def train_steps(cfg: dict, params, batches, precision: str = "highest",
                place=None) -> dict:
    """Follow the program's first steps from the same weights and rows.
    ``batches`` is a list of host ``(x, y)``; ``place`` puts one array on
    the device(s) (rows split over the chips of a multi-chip cell, which
    leaves the arithmetic that of one batch). Returns the loss of every
    step, the norm of every leaf of the first gradient, and the norm of
    every leaf's change after the last step."""
    place = place or jnp.asarray
    step = _step_fn(json.dumps(cfg, sort_keys=True), precision)
    with jax.default_matmul_precision("highest"):
        p = params
        m = {k: jnp.zeros_like(a) for k, a in params.items()}
        v = {k: jnp.zeros_like(a) for k, a in params.items()}
        losses, first = [], None
        for t, (x, y) in enumerate(batches, start=1):
            p, m, v, value, gn = step(p, m, v, float(t), place(x), place(y))
            losses.append(float(value))
            if first is None:
                first = {k: float(a) for k, a in gn.items()}
        delta = _delta_norms(p, params)
    return {"losses": losses, "grad_norms": first,
            "delta_norms": {k: float(a) for k, a in delta.items()}}

"""Plain reference of the ``charrnn_textgen_lstm`` configuration.

The DL4J zoo's TextGenerationLSTM (zoo/model/TextGenerationLSTM.java): two
stacked Graves LSTMs (Graves 2013, arXiv:1308.0850) of 256 units over
one-hot characters, a per-step softmax over the vocabulary, trained by
truncated back-propagation through time with RmsProp. Noted departures
from the textbook LSTM, to match what the configuration runs (DL4J's
LSTMHelpers with peepholes):

* fused gate weights in the order input, forget, cell (g), output;
* diagonal peepholes: c[t-1] into the input and forget gates, c[t] into
  the output gate; none into g;
* truncated BPTT: each window of ``tbptt_length`` steps takes one
  optimiser update; the (h, c) it ends with start the next window as
  values, with no gradient through them; each new batch starts from zeros;
* the loss is the cross-entropy summed over the vocabulary and averaged
  over batch x time;
* RmsProp as optax has it: nu = decay nu + (1 - decay) g^2 from zero,
  update = -lr g / sqrt(nu + eps).

Float32 under ``jax.default_matmul_precision("highest")``. Two lower
``precision``s exist for the control of the correctness check: ``bf16``
(weights, inputs, gates and the carried state all in bfloat16; master
weights and the loss stay float32) and ``fp8`` (matmul operands scaled per
tensor and rounded to float8_e4m3fn, everything else float32). Nothing of ``deeplearning4j_tpu`` is imported; leaves are named
``<layer index>/<param>``."""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax


def layers(cfg: dict) -> List[dict]:
    """What ``harness.flops`` counts, per character."""
    v, n = cfg["vocab_size"], cfg["units"]
    out, n_in = [], v
    for _ in range(cfg["lstm_layers"]):
        out.append({"kind": "lstm", "n_in": n_in, "units": n})
        n_in = n
    out.append({"kind": "dense", "n_in": n, "n_out": v})
    return out


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf_shapes(cfg: dict) -> Dict[str, tuple]:
    v, n = cfg["vocab_size"], cfg["units"]
    out, n_in = {}, v
    for i in range(cfg["lstm_layers"]):
        out.update({f"{i}/W": (n_in, 4 * n), f"{i}/U": (n, 4 * n),
                    f"{i}/b": (4 * n,), f"{i}/p_i": (n,), f"{i}/p_f": (n,),
                    f"{i}/p_o": (n,)})
        n_in = n
    i = cfg["lstm_layers"]
    out.update({f"{i}/W": (n, v), f"{i}/b": (v,)})
    return out


@functools.partial(jax.jit, static_argnames=("spec",))
def _draw(key, spec):
    out = []
    for i, (shape, scale, shift_from, shift_to) in enumerate(spec):
        z = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * scale
        if shift_to > shift_from:        # the forget gate's bias of 1
            z = z.at[shift_from:shift_to].add(1.0)
        out.append(z)
    return out


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Seeded float32 weights in one jitted call: matrices Xavier-normal
    (variance 2 / (fan_in + fan_out) with the zoo's fan_out = units),
    biases 0.1 N(0,1) with +1 on the forget gate, peepholes 0.1 N(0,1)."""
    n = cfg["units"]
    names, spec = [], []
    for name, shape in leaf_shapes(cfg).items():
        names.append(name)
        if len(shape) == 2:
            spec.append((shape, math.sqrt(2.0 / (shape[0] + min(shape[1], n))),
                         0, 0))
        elif name.endswith("/b") and shape[0] == 4 * n:
            spec.append((shape, 0.1, n, 2 * n))
        else:
            spec.append((shape, 0.1, 0, 0))
    return dict(zip(names, _draw(seed_key(seed), tuple(spec))))


# ------------------------------------------------------------------ forward
def _operand(a, precision: str):
    """``a`` as a matmul operand: at ``fp8`` scaled per tensor and rounded
    to float8_e4m3fn forward, the cotangent passed through untouched."""
    if precision != "fp8":
        return a
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale
    return a + lax.stop_gradient(q - a)


def _lstm(p, i: int, n: int, carry, x, precision: str):
    """One Graves LSTM over a (batch, time, n_in) window from ``carry``."""
    w, u, b = p[f"{i}/W"], p[f"{i}/U"], p[f"{i}/b"]
    w, u = _operand(w, precision), _operand(u, precision)
    x = _operand(x, precision)
    p_i, p_f, p_o = p[f"{i}/p_i"], p[f"{i}/p_f"], p[f"{i}/p_o"]

    def step(hc, x_t):
        h, c = hc
        z = x_t @ w + b + _operand(h, precision) @ u
        gate_i = jax.nn.sigmoid(z[:, 0 * n:1 * n] + c * p_i)
        gate_f = jax.nn.sigmoid(z[:, 1 * n:2 * n] + c * p_f)
        g = jnp.tanh(z[:, 2 * n:3 * n])
        c_new = gate_f * c + gate_i * g
        gate_o = jax.nn.sigmoid(z[:, 3 * n:4 * n] + c_new * p_o)
        h_new = gate_o * jnp.tanh(c_new)
        return (h_new, c_new), h_new

    carry, hs = lax.scan(step, carry, jnp.swapaxes(x, 0, 1))
    return carry, jnp.swapaxes(hs, 0, 1)


def window_loss(cfg: dict, params, carries, x, y, precision: str = "highest"):
    """(loss of one window, the carries it ends with)."""
    n, depth = cfg["units"], cfg["lstm_layers"]
    if precision == "bf16":
        low = jnp.bfloat16
        params = {k: a.astype(low) for k, a in params.items()}
        carries = [(h.astype(low), c.astype(low)) for h, c in carries]
        x = x.astype(low)
    elif precision not in ("highest", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    new = []
    for i in range(depth):
        carry, x = _lstm(params, i, n, carries[i], x, precision)
        new.append(carry)
    z = (_operand(x, precision) @ _operand(params[f"{depth}/W"], precision)
         + params[f"{depth}/b"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(z, axis=-1)
    value = -jnp.mean(jnp.sum(y.astype(jnp.float32) * logp, axis=-1))
    new = [(h.astype(jnp.float32), c.astype(jnp.float32)) for h, c in new]
    return value, new


def zero_carries(cfg: dict, batch: int):
    z = jnp.zeros((batch, cfg["units"]), jnp.float32)
    return [(z, z) for _ in range(cfg["lstm_layers"])]


# ----------------------------------------------------------------- training
@functools.lru_cache(maxsize=None)
def _dispatch_fn(cfg_json: str, precision: str):
    cfg = json.loads(cfg_json)
    upd, length = cfg["updater"], cfg["tbptt_length"]

    @jax.jit
    def dispatch(params, nu, x, y):
        """All windows of one (batch, T, vocab) batch: one update each."""
        b, t = x.shape[0], x.shape[1]
        xw = jnp.moveaxis(x.reshape(b, t // length, length, -1), 1, 0)
        yw = jnp.moveaxis(y.reshape(b, t // length, length, -1), 1, 0)

        def body(state, xy):
            p, nu, carries = state
            (value, carries), g = jax.value_and_grad(
                lambda q: window_loss(cfg, q, carries, xy[0], xy[1],
                                      precision), has_aux=True)(p)
            carries = lax.stop_gradient(carries)
            nu = {k: upd["rms_decay"] * nu[k]
                  + (1 - upd["rms_decay"]) * jnp.square(g[k]) for k in p}
            p = {k: p[k] - upd["learning_rate"] * g[k]
                 * lax.rsqrt(nu[k] + upd["epsilon"]) for k in p}
            return (p, nu, carries), value

        (params, nu, _), losses = lax.scan(
            body, (params, nu, zero_carries(cfg, b)), (xw, yw))
        return params, nu, losses

    return dispatch


@jax.jit
def _norms(tree):
    return {k: jnp.linalg.norm(a) for k, a in tree.items()}


def train_steps(cfg: dict, params, batches, precision: str = "highest",
                place=None) -> dict:
    """Follow the program's first dispatches from the same weights and
    rows. A "step" of this configuration's timed path is one fused dispatch
    of T / tbptt_length updates, so the numbers are: the loss of each
    dispatch's last window; per leaf, the norm of sqrt(nu) after the first
    dispatch (the gradient as RmsProp has it, a decayed mean of squares
    over that dispatch's windows); per leaf, the norm of the parameters'
    change after the last dispatch."""
    place = place or jnp.asarray
    dispatch = _dispatch_fn(json.dumps(cfg, sort_keys=True), precision)
    with jax.default_matmul_precision("highest"):
        p = params
        nu = {k: jnp.zeros_like(a) for k, a in params.items()}
        losses, first = [], None
        for x, y in batches:
            p, nu, per_window = dispatch(p, nu, place(x), place(y))
            losses.append(float(per_window[-1]))
            if first is None:
                first = {k: float(a) for k, a in _norms(
                    {k: jnp.sqrt(a) for k, a in nu.items()}).items()}
        delta = _norms({k: p[k] - params[k] for k in p})
    return {"losses": losses, "grad_norms": first,
            "delta_norms": {k: float(a) for k, a in delta.items()}}

"""Loss functions.

Parity surface: ND4J ``org.nd4j.linalg.lossfunctions.LossFunctions`` (external
dependency of the reference; used by every output layer config, e.g.
deeplearning4j-nn/.../nn/conf/layers/OutputLayer.java). Losses are computed from
the *pre-activation* output plus the activation name so that softmax+MCXENT and
sigmoid+XENT use numerically-stable fused forms; the backward pass is autodiff.

Conventions:
- ``labels``/``preout`` are (batch, n_out) or (batch, time, n_out) for RNNs;
  ``sparse_mcxent`` alone takes integer class ids, (batch,) or (batch, time).
- ``mask`` is optional (batch,) or (batch, time); masked scores are excluded
  from the average (reference: per-example score arrays + mask handling in
  BaseOutputLayer/LossFunction scoreArray implementations).
- ``weights`` is an optional per-output weight vector (ND4J loss weights).
- Each loss returns the per-example score array; ``score_from_array`` reduces
  to the mean the way DL4J's computeScore does (sum over outputs, mean over
  examples/timesteps).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.activations import get_activation

_EPS = 1e-7


def _apply_act(preout, activation):
    return get_activation(activation)(preout)


def _weighted(arr, weights):
    if weights is None:
        return arr
    return arr * jnp.asarray(weights, arr.dtype)


def _score_mse(labels, preout, activation, weights):
    d = _apply_act(preout, activation) - labels
    return _weighted(d * d, weights)


def _score_l2(labels, preout, activation, weights):
    return _score_mse(labels, preout, activation, weights)


def _score_l1(labels, preout, activation, weights):
    return _weighted(jnp.abs(_apply_act(preout, activation) - labels), weights)


def _score_mcxent(labels, preout, activation, weights):
    act = str(activation).lower()
    if act == "softmax":
        logp = jax.nn.log_softmax(preout, axis=-1)
    else:
        out = jnp.clip(_apply_act(preout, activation), _EPS, 1.0 - _EPS)
        logp = jnp.log(out)
    return _weighted(-labels * logp, weights)


def _score_xent(labels, preout, activation, weights):
    # Binary cross-entropy, stable for sigmoid activation.
    act = str(activation).lower()
    if act == "sigmoid":
        # log(sigmoid(x)) = -softplus(-x); log(1-sigmoid(x)) = -softplus(x)
        s = -(labels * -jax.nn.softplus(-preout) + (1.0 - labels) * -jax.nn.softplus(preout))
    else:
        out = jnp.clip(_apply_act(preout, activation), _EPS, 1.0 - _EPS)
        s = -(labels * jnp.log(out) + (1.0 - labels) * jnp.log(1.0 - out))
    return _weighted(s, weights)


def _score_sparse_mcxent(labels, preout, activation, weights):
    """Multi-class cross-entropy over INTEGER class ids: ``labels`` has
    ``preout``'s shape without its last axis. The score lands in the
    label's own column, so that ``score_array``'s sum over the output axis
    and ``score``'s mean over unmasked steps are ``mcxent``'s."""
    ids = labels.astype(jnp.int32)
    if ids.ndim == preout.ndim:            # (batch, time, 1)
        ids = ids[..., 0]
    onehot = jax.nn.one_hot(ids, preout.shape[-1], dtype=preout.dtype)
    return _score_mcxent(onehot, preout, activation, weights)


def blocked_sparse_mcxent(x, w, b, ids, mask=None, block: int = 1024):
    """``score("sparse_mcxent", ids, x @ w + b, "softmax", mask)`` without
    the logits of the whole sequence: the time axis goes through in blocks
    of ``block`` steps under ``jax.checkpoint``, so that forward and
    backward hold one block's float32 logits and their gradient, never the
    (batch, time, classes) array. ``x`` (batch, time, n_in), ``w`` (n_in,
    classes), ``b`` None or (classes,), ``ids`` (batch, time) integers,
    ``mask`` None or (batch, time). Mean over unmasked steps."""
    bsz, t, _ = x.shape
    ids = ids.astype(jnp.int32)
    if ids.ndim == 3:
        ids = ids[..., 0]
    m = (jnp.ones((bsz, t), jnp.float32) if mask is None
         else mask.astype(jnp.float32))
    block = min(block, t)
    pad = (-t) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        ids = jnp.pad(ids, ((0, 0), (0, pad)))
        m = jnp.pad(m, ((0, 0), (0, pad)))
    n = (t + pad) // block

    def split(a):
        return jnp.moveaxis(a.reshape((bsz, n, block) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one(xb, ib, mb):
        z = (xb @ w).astype(jnp.float32)
        if b is not None:
            z = z + b
        lse = jax.nn.logsumexp(z, axis=-1)
        picked = jnp.take_along_axis(z, ib[..., None], -1)[..., 0]
        return jnp.sum((lse - picked) * mb)

    def step(total, blk):
        return total + one(*blk), None

    with jax.named_scope("loss.blocked"):
        total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32),
                                (split(x), split(ids), split(m)))
    return total / jnp.maximum(jnp.sum(m), 1.0)


def exit_distribution(gate_logits):
    """The exit distribution of a looped model over its R passes from the
    gates' logits ``gate_logits`` (R, ...), in log space: with
    ``lambda_r = sigmoid(gate_logits[r])``,
    ``p_r = lambda_r prod_{j<r} (1 - lambda_j)`` for r < R and
    ``p_R = prod_{j<R} (1 - lambda_j)`` (the last pass takes what is left,
    so its own gate is not read and the p_r sum to one). Returns
    ``log p`` (R, ...), float32."""
    g = gate_logits.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)        # sum_{j<=r}
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], 0)
    return jnp.concatenate([jax.nn.log_sigmoid(g[:-1]) + before[:-1],
                            before[-1:]], 0)


def blocked_exit_weighted_mcxent(x, w, b, wg, bg, ids, mask=None,
                                 block: int = 1024,
                                 entropy_weight: float = 0.0):
    """The training loss of a looped model with an exit gate after every
    pass, over ``blocked_sparse_mcxent``'s blocks: ``x`` (R, batch, time,
    n_in) holds the R passes' states, every pass is scored by the one head
    ``w`` (n_in, classes) (``b`` None or (classes,)) and gated by
    ``sigmoid(x_r @ wg + bg)`` (``wg`` (n_in, 1), ``bg`` (1,)), and a
    token's loss is

        sum_r p_r CE(x_r w + b, id) - entropy_weight * H(p),
        H(p) = -sum_r p_r log p_r,   p = ``exit_distribution``

    One (pass, time block) pair's float32 logits are alive at a time,
    forward and backward (each pair under ``jax.checkpoint``); the gates,
    the distribution and the mixing are whole-sequence arrays of R numbers
    a token. ``ids`` (batch, time) integers, ``mask`` None or (batch,
    time). Returns the mean over unmasked steps."""
    passes, bsz, t, _ = x.shape
    ids = ids.astype(jnp.int32)
    if ids.ndim == 3:
        ids = ids[..., 0]
    m = (jnp.ones((bsz, t), jnp.float32) if mask is None
         else mask.astype(jnp.float32))
    with jax.named_scope("loop.exit_gate"):
        # an elementwise product and a sum: float32 whatever the backend's
        # default matmul precision is
        logp = exit_distribution(
            jnp.sum(x.astype(jnp.float32) * wg[:, 0].astype(jnp.float32),
                    -1) + bg)
        p = jnp.exp(logp)
    block = min(block, t)
    pad = (-t) % block
    xp, idp = x, ids
    if pad:
        xp = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        idp = jnp.pad(ids, ((0, 0), (0, pad)))
    n = (t + pad) // block

    @jax.checkpoint
    def one(xb, ib):
        z = (xb @ w).astype(jnp.float32)
        if b is not None:
            z = z + b
        lse = jax.nn.logsumexp(z, axis=-1)
        return lse - jnp.take_along_axis(z, ib[..., None], -1)[..., 0]

    with jax.named_scope("loop.exit_head"):
        # (pass, block) pairs, one after another
        xs = jnp.moveaxis(xp.reshape(passes, bsz, n, block, -1), 2, 1)
        ib = jnp.moveaxis(idp.reshape(bsz, n, block), 1, 0)
        _, ce = jax.lax.scan(
            lambda _, pair: (None, one(*pair)), None,
            (xs.reshape((passes * n, bsz, block) + xs.shape[4:]),
             jnp.tile(ib, (passes, 1, 1))))
        ce = jnp.moveaxis(ce.reshape(passes, n, bsz, block), 1, 2) \
            .reshape(passes, bsz, n * block)[..., :t]
    with jax.named_scope("loss.exit_weighted"):
        token = jnp.sum(p * ce, 0)
        if entropy_weight:
            token = token + entropy_weight * jnp.sum(p * logp, 0)
        return jnp.sum(token * m) / jnp.maximum(jnp.sum(m), 1.0)


def _score_nll(labels, preout, activation, weights):
    return _score_mcxent(labels, preout, activation, weights)


def _score_kld(labels, preout, activation, weights):
    out = jnp.clip(_apply_act(preout, activation), _EPS, 1.0)
    lab = jnp.clip(labels, _EPS, 1.0)
    return _weighted(labels * (jnp.log(lab) - jnp.log(out)), weights)


def _score_poisson(labels, preout, activation, weights):
    out = jnp.clip(_apply_act(preout, activation), _EPS, None)
    return _weighted(out - labels * jnp.log(out), weights)


def _score_cosine(labels, preout, activation, weights):
    out = _apply_act(preout, activation)
    dot = jnp.sum(out * labels, axis=-1, keepdims=True)
    no = jnp.maximum(jnp.linalg.norm(out, axis=-1, keepdims=True), _EPS)
    nl = jnp.maximum(jnp.linalg.norm(labels, axis=-1, keepdims=True), _EPS)
    sim = dot / (no * nl)
    # per-example score spread across one column (sum-over-outputs reduces it back)
    return _weighted(jnp.broadcast_to((1.0 - sim) / labels.shape[-1], labels.shape), weights)


def _score_hinge(labels, preout, activation, weights):
    # labels in {-1, +1} (or {0,1} mapped)
    y = jnp.where(labels > 0, 1.0, -1.0)
    out = _apply_act(preout, activation)
    return _weighted(jnp.maximum(0.0, 1.0 - y * out), weights)


def _score_squared_hinge(labels, preout, activation, weights):
    h = _score_hinge(labels, preout, activation, None)
    return _weighted(h * h, weights)


def _score_mape(labels, preout, activation, weights):
    out = _apply_act(preout, activation)
    return _weighted(100.0 * jnp.abs((labels - out) / jnp.clip(jnp.abs(labels), _EPS, None)), weights)


def _score_msle(labels, preout, activation, weights):
    out = _apply_act(preout, activation)
    d = jnp.log1p(jnp.clip(out, -1 + _EPS, None)) - jnp.log1p(jnp.clip(labels, -1 + _EPS, None))
    return _weighted(d * d, weights)


LOSSES = {
    "mse": _score_mse,
    "l2": _score_l2,
    "l1": _score_l1,
    "mae": _score_l1,
    "mcxent": _score_mcxent,
    "sparse_mcxent": _score_sparse_mcxent,
    "xent": _score_xent,
    "negativeloglikelihood": _score_nll,
    "nll": _score_nll,
    "kl_divergence": _score_kld,
    "kld": _score_kld,
    "reconstruction_crossentropy": _score_xent,
    "poisson": _score_poisson,
    "cosine_proximity": _score_cosine,
    "hinge": _score_hinge,
    "squared_hinge": _score_squared_hinge,
    "mean_absolute_percentage_error": _score_mape,
    "mape": _score_mape,
    "mean_squared_logarithmic_error": _score_msle,
    "msle": _score_msle,
}


def get_loss(name):
    if callable(name):
        return name
    key = str(name).lower()
    if key not in LOSSES:
        raise ValueError(f"Unknown loss '{name}'. Known: {sorted(LOSSES)}")
    return LOSSES[key]


def score_array(loss, labels, preout, activation="identity", mask=None, weights=None):
    """Per-example score: sum over the output dim, masked.

    Returns shape (batch,) or (batch, time).
    """
    fn = get_loss(loss)
    s = fn(labels, preout, activation, weights)
    s = jnp.sum(s, axis=-1)
    if mask is not None:
        s = s * mask
    return s


def score(loss, labels, preout, activation="identity", mask=None, weights=None):
    """Scalar score: mean over (unmasked) examples/timesteps.

    Matches DL4J computeScore: sum of per-example scores / number of counted
    examples (mask-aware).
    """
    s = score_array(loss, labels, preout, activation, mask, weights)
    if mask is not None:
        denom = jnp.maximum(jnp.sum(mask), 1.0)
    else:
        denom = float(s.size) / float(s.shape[0]) * s.shape[0]  # == s.size
        denom = jnp.asarray(denom, s.dtype)
    return jnp.sum(s) / denom

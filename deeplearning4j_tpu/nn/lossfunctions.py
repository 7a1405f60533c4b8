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


def _block_logits(xb, w, b):
    """One block's float32 logits: the product at the default matmul
    precision, accumulated in float32."""
    z = jnp.matmul(xb, w, preferred_element_type=jnp.float32)
    return z if b is None else z + b.astype(jnp.float32)


def _block_ce(z, ib):
    """(logsumexp, cross-entropy) a token of one block's float32 logits."""
    lse = jax.nn.logsumexp(z, axis=-1)
    return lse, lse - jnp.take_along_axis(z, ib[..., None], -1)[..., 0]


@jax.custom_vjp
def _blocked_ce(x, w, b, ids, coef):
    """``sum(coef * CE(x w + b, ids))`` over blocks of tokens, one after
    another: ``x`` (blocks, batch, block, n_in), ``w`` (n_in, classes),
    ``b`` None or (classes,), ``ids`` (blocks, batch, block) int32,
    ``coef`` (blocks, batch, block) float32, one number a token (the
    mask over the count; for a looped model times a pass's exit
    probability, with the passes' blocks laid end to end; for a model with
    prediction modules a state's weight, the states' blocks laid end to
    end). The one block loop under ``blocked_sparse_mcxent``,
    ``blocked_exit_weighted_mcxent`` and ``blocked_multi_token_mcxent``.

    Called without differentiation (``score``, evaluation) this body runs:
    one product a block, no gradient. Under ``jax.grad`` the rule below
    runs instead (``_blocked_ce_fwd``): ONE loop that yields the loss and
    its gradients, three products a block. There is no forward-mode rule
    (``jax.jvp`` / ``jacfwd`` / ``hessian`` of it raise)."""
    from deeplearning4j_tpu.perf.compile_watch import bump_active

    bump_active("loss.blocked_forward_only")

    def step(total, blk):
        xb, ib, cb = blk
        _, ce = _block_ce(_block_logits(xb, w, b), ib)
        return total + jnp.sum(ce * cb), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (x, ids, coef))
    return total


def _blocked_ce_fwd(x, w, b, ids, coef):
    """Loss and gradients in one pass over the blocks. A block's logits are
    formed once (first product); from them, in float32, the logsumexp, the
    token's cross-entropy (kept whole: it is ``coef``'s cotangent, through
    which a looped model's gates learn) and ``coef`` times the softmax less
    the one-hot, which gives the block's rows of ``x``'s gradient (second
    product, written once) and its part of ``w``'s and ``b``'s (third
    product, summed in float32 in the loop's carry). Alive at a time: one
    block's logits and their gradient. Kept for the backward pass, which
    only scales them by the scalar that arrives: the three gradients (the
    size of ``x``, ``w``, ``b``) and a cross-entropy a token."""
    from deeplearning4j_tpu.perf.compile_watch import bump_active

    bump_active("loss.blocked_one_pass")
    classes = w.shape[-1]

    def step(carry, blk):
        dw, db = carry
        xb, ib, cb = blk
        z = _block_logits(xb, w, b)
        lse, ce = _block_ce(z, ib)
        hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1) \
            == ib[..., None]
        p = jnp.exp(z - lse[..., None])
        g = jnp.where(hit, p - 1.0, p) * cb[..., None]
        dxb = jnp.matmul(g, w.T, preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("...d,...v->dv", xb, g,
                             preferred_element_type=jnp.float32)
        if b is not None:
            db = db + jnp.sum(g.reshape(-1, classes), 0)
        return (dw, db), (ce, dxb.astype(x.dtype))

    # b may be None: an empty subtree to tree_map, here and below
    zero = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, jnp.float32),
                                  (w, b))
    (dw, db), (ce, dx) = jax.lax.scan(step, zero, (x, ids, coef))
    dw, db = jax.tree_util.tree_map(lambda g, a: g.astype(a.dtype), (dw, db),
                                    (w, b))
    return jnp.sum(ce * coef), (dx, dw, db, ce)


def _blocked_ce_bwd(kept, ct):
    *grads, ce = kept
    dx, dw, db = jax.tree_util.tree_map(lambda g: (ct * g).astype(g.dtype),
                                        grads)
    return dx, dw, db, None, ct * ce


_blocked_ce.defvjp(_blocked_ce_fwd, _blocked_ce_bwd)


def _time_blocks(t, block):
    """(block length, padding, number of blocks) for ``t`` steps."""
    block = min(block, t)
    pad = (-t) % block
    return block, pad, (t + pad) // block


def _split_time(a, axis, n, pad):
    """``a``'s time axis ``axis`` zero-padded by ``pad`` and cut into ``n``
    blocks, the block index in front: (n, ..., block, ...)."""
    if pad:
        a = jnp.pad(a, [(0, pad if i == axis else 0) for i in range(a.ndim)])
    a = a.reshape(a.shape[:axis] + (n, -1) + a.shape[axis + 1:])
    return jnp.moveaxis(a, axis, 0)


def _stacked_blocks(a, n, pad):
    """``a`` (stacked, batch, time, ...) as (stacked x n, batch, block, ...):
    each stacked entry's ``n`` time blocks, one entry after another (the
    passes of a looped model, the states of one with prediction modules)."""
    a = _split_time(a, 2, n, pad)                      # (n, stacked, ...)
    return jnp.moveaxis(a, 0, 1).reshape((-1,) + a.shape[2:])


def _ids_and_mask(ids, mask, bsz, t):
    ids = ids.astype(jnp.int32)
    if ids.ndim == 3:
        ids = ids[..., 0]
    m = (jnp.ones((bsz, t), jnp.float32) if mask is None
         else mask.astype(jnp.float32))
    return ids, m


def blocked_sparse_mcxent(x, w, b, ids, mask=None, block: int = 1024):
    """``score("sparse_mcxent", ids, x @ w + b, "softmax", mask)`` without
    the logits of the whole sequence: the time axis goes through in blocks
    of ``block`` steps (``_blocked_ce``), so that one block's float32
    logits and their gradient are alive at a time, never the (batch, time,
    classes) array. Under ``jax.grad`` the loss and its gradients come out
    of ONE loop over the blocks (three products a block: logits, ``x``'s
    gradient, ``w``'s); kept between the forward and the backward pass are
    those gradients (the size of ``x``, ``w``, ``b``) and a cross-entropy
    a token, which the backward pass scales. Not differentiated (``score``)
    it is one product a block. No forward-mode rule (``jax.jvp``,
    ``jacfwd``, ``hessian`` raise). ``x`` (batch, time, n_in), ``w`` (n_in,
    classes), ``b`` None or (classes,), ``ids`` (batch, time) integers,
    ``mask`` None or (batch, time). Mean over unmasked steps."""
    bsz, t, _ = x.shape
    ids, m = _ids_and_mask(ids, mask, bsz, t)
    block, pad, n = _time_blocks(t, block)
    with jax.named_scope("loss.blocked"):
        coef = m / jnp.maximum(jnp.sum(m), 1.0)
        return _blocked_ce(_split_time(x, 1, n, pad), w, b,
                           _split_time(ids, 1, n, pad),
                           _split_time(coef, 1, n, pad))


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
    pass, over ``blocked_sparse_mcxent``'s block loop (``_blocked_ce``):
    ``x`` (R, batch, time, n_in) holds the R passes' states, every pass is
    scored by the one head ``w`` (n_in, classes) (``b`` None or
    (classes,)) and gated by ``sigmoid(x_r @ wg + bg)`` (``wg`` (n_in, 1),
    ``bg`` (1,)), and a token's loss is

        sum_r p_r CE(x_r w + b, id) - entropy_weight * H(p),
        H(p) = -sum_r p_r log p_r,   p = ``exit_distribution``

    One (pass, time block) pair's float32 logits and their gradient are
    alive at a time; under ``jax.grad`` loss and gradients come out of ONE
    loop over the pairs (three products a pair), which keeps for the
    backward pass the gradients of ``x``, ``w`` and ``b`` and the passes'
    cross-entropy a token (the gates learn through it); the gates, the
    distribution and the mixing are whole-sequence arrays of R numbers a
    token under plain autodiff. No forward-mode rule (``jax.jvp``,
    ``jacfwd``, ``hessian`` raise). ``ids`` (batch, time) integers,
    ``mask`` None or (batch, time). Returns the mean over unmasked steps."""
    passes, bsz, t, _ = x.shape
    ids, m = _ids_and_mask(ids, mask, bsz, t)
    with jax.named_scope("loop.exit_gate"):
        # an elementwise product and a sum: float32 whatever the backend's
        # default matmul precision is
        logp = exit_distribution(
            jnp.sum(x.astype(jnp.float32) * wg[:, 0].astype(jnp.float32),
                    -1) + bg)
        p = jnp.exp(logp)
    block, pad, n = _time_blocks(t, block)
    with jax.named_scope("loss.exit_weighted"):
        scale = m / jnp.maximum(jnp.sum(m), 1.0)
        coef = p * scale
    with jax.named_scope("loop.exit_head"):
        # (pass, block) pairs, one after another
        total = _blocked_ce(_stacked_blocks(x, n, pad), w, b,
                            jnp.tile(_split_time(ids, 1, n, pad),
                                     (passes, 1, 1)),
                            _stacked_blocks(coef, n, pad))
    if not entropy_weight:
        return total
    with jax.named_scope("loss.exit_weighted"):
        return total + entropy_weight * jnp.sum(jnp.sum(p * logp, 0) * scale)


def blocked_multi_token_mcxent(x, w, b, ids, mask=None, block: int = 1024,
                              module_weight: float = 0.3):
    """The training loss of a model with D multi-token prediction modules
    (DeepSeek-V3, arXiv:2412.19437 section 2.2), over
    ``blocked_sparse_mcxent``'s block loop (``_blocked_ce``): ``x``
    (1 + D, batch, time, n_in) holds the trunk's state and the D modules',
    every state is scored by the one head ``w`` (n_in, classes) (``b`` None
    or (classes,)), state k against the ids k steps further on (``ids``
    (batch, time) are the trunk's labels, the ids one step on; module k's
    are ``ids`` shifted by k, its last k positions having none and being
    masked), and the loss is

        L_main + module_weight / D * sum_k L_k,
        L_k = (1 / count) sum_i m_i m_{i+k} CE(x_k[i] w + b, ids[i + k])

    with ``count`` the trunk's unmasked steps for every term (the report's
    1 / T). ONE loop over the (state, time block) pairs, so that under
    ``jax.grad`` the head's gradient is summed in one carry (three products
    a pair). No forward-mode rule. Returns a scalar."""
    states, bsz, t, _ = x.shape
    modules = states - 1
    ids, m = _ids_and_mask(ids, mask, bsz, t)
    block, pad, n = _time_blocks(t, block)
    with jax.named_scope("loss.multi_token"):
        def ahead(a, k):            # position i gets a[i + k]; zeros behind
            return jnp.pad(a[:, k:], ((0, 0), (0, k)))

        scale = 1.0 / jnp.maximum(jnp.sum(m), 1.0)
        want = jnp.stack([ahead(ids, k) for k in range(states)])
        coef = jnp.stack([m * scale] + [
            m * ahead(m, k) * (scale * module_weight / modules)
            for k in range(1, states)])
    with jax.named_scope("loss.blocked"):
        # (state, block) pairs, one after another
        return _blocked_ce(_stacked_blocks(x, n, pad), w, b,
                           _stacked_blocks(want, n, pad),
                           _stacked_blocks(coef, n, pad))


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

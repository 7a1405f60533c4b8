"""Jitted embedding-training kernels.

Parity surface: reference ``models/embeddings/learning/impl/elements/
SkipGram.java:156-283`` (learnSequence -> batched native sg op) and
``CBOW.java`` — there the math lives in libnd4j's custom sg/cbow CUDA/C++
kernels; here each step is ONE XLA program: gathers, closed-form SGNS/HS
gradients, and scatter-adds (``.at[].add``) that XLA lowers to efficient TPU
scatters. Duplicate indices within a batch accumulate, matching the
sequential semantics of the reference's hogwild updates in expectation.

All steps donate the embedding tables: no copies in the hot loop, HBM-bandwidth
friendly.

Stability note: the reference applies pair updates *sequentially* (hogwild
host threads), so each touch of a row moves it by at most ~lr. A naive
batched scatter-ADD instead sums the gradients of every duplicate index in
the batch — with a small vocab (or very frequent words) that multiplies the
effective step by the duplicate count and diverges. The TPU-native answer
here is a count-normalized scatter (scatter-mean per destination row): each
row moves by lr times the *average* gradient of the pairs touching it, which
matches the sequential semantics in expectation and is unconditionally
stable."""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

_EPS = 1e-7

# TPU scatters serialize row-by-row (profiled ~13x slower than expressing
# the same segment-sum as a one-hot matmul on the MXU). The matmul path
# materializes a transient (N, V) bf16 one-hot, so it is gated on memory;
# above the budget (huge vocab x batch) the scatter path remains.
_ONEHOT_BYTES_LIMIT = int(os.environ.get("DL4J_TPU_ONEHOT_SCATTER_BYTES",
                                         2 * 1024**3))


def _scatter_mean_update(table, idx, grads, weights, lr):
    """table += lr * segment_mean(grads over idx).

    idx (N,) int32 destination rows, grads (N, D), weights (N,) 0/1 validity.
    Rows untouched in this batch keep count 0 and receive no update. The
    count vector is a cheap scalar scatter; the (V, D) accumulation uses the
    one-hot-matmul MXU path when the transient one-hot fits the budget."""
    V = table.shape[0]
    n = idx.shape[0]
    # the matmul rewrite only pays where scatters are slow (TPU); CPU keeps
    # the exact fp32 scatter (cheap there, and no bf16 rounding)
    if jax.default_backend() == "tpu":
        if n * V * 2 <= _ONEHOT_BYTES_LIMIT:
            oh = jax.nn.one_hot(idx, V, dtype=jnp.bfloat16)
            # counts ride the SAME matmul as a trailing all-ones column
            # (a scalar .at[].add count scatter serializes row-by-row on
            # TPU and dominated this step's profile); f32 accumulator
            # output is free on the MXU and avoids rounding the (V, D)
            # update to bf16 before it lands in the f32 table
            rhs = jnp.concatenate(
                [(grads * weights[:, None]).astype(jnp.bfloat16),
                 weights[:, None].astype(jnp.bfloat16)], axis=1)
            acc = jnp.matmul(oh.T, rhs, preferred_element_type=jnp.float32)
            upd = acc[:, :-1] / jnp.maximum(acc[:, -1:], 1.0)
            return table + lr * upd.astype(table.dtype)
    cnt = jnp.zeros((V,), table.dtype).at[idx].add(weights)
    scale = (weights / jnp.maximum(cnt, 1.0)[idx])[:, None]
    if jax.default_backend() == "tpu":
        from deeplearning4j_tpu.nlp import pallas_scatter
        if pallas_scatter.fits_vmem(table):
            # above the one-hot gate but table fits VMEM: the Pallas kernel
            # (~1.6x XLA scatter), exact fp32
            return pallas_scatter.scatter_add_pallas(table, idx,
                                                     lr * grads * scale)
    return table.at[idx].add(lr * grads * scale)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def sgns_step(syn0, syn1neg, centers, contexts, negs, wmask, lr):
    """Skip-gram negative sampling.

    syn0 (V, D) input vectors; syn1neg (V, D) output vectors;
    centers/contexts (B,) int32; negs (B, K) int32; wmask (B,) 1/0 padding
    mask (ragged final batches pad to the compiled batch size); lr scalar.

    word2vec convention (and the reference's SkipGram op): the *context*
    word's input vector is trained against the *center* word's output path.
    Callers pass (centers, contexts) as generated; the symmetric pairing
    means either orientation converges identically.
    """
    v = syn0[contexts]                                   # (B, D)
    u_pos = syn1neg[centers]                             # (B, D)
    u_neg = syn1neg[negs]                                # (B, K, D)
    s_pos = jax.nn.sigmoid(jnp.sum(v * u_pos, axis=-1))  # (B,)
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", v, u_neg))
    g_pos = (1.0 - s_pos) * wmask                        # label 1
    g_neg = -s_neg * wmask[:, None]                      # label 0
    dv = g_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    du_pos = g_pos[:, None] * v
    du_neg = g_neg[..., None] * v[:, None, :]
    B, K = negs.shape
    D = v.shape[-1]
    syn0 = _scatter_mean_update(syn0, contexts, dv, wmask, lr)
    # centers and negatives both land in syn1neg: one joint normalized scatter
    out_idx = jnp.concatenate([centers, negs.reshape(-1)])
    out_grads = jnp.concatenate([du_pos, du_neg.reshape(B * K, D)])
    out_w = jnp.concatenate([wmask, jnp.repeat(wmask, K)])
    syn1neg = _scatter_mean_update(syn1neg, out_idx, out_grads, out_w, lr)
    nll = -(jnp.log(s_pos + _EPS) + jnp.sum(jnp.log(1.0 - s_neg + _EPS), axis=-1))
    loss = jnp.sum(nll * wmask) / jnp.maximum(jnp.sum(wmask), 1.0)
    return syn0, syn1neg, loss


@functools.partial(jax.jit, donate_argnums=(0, 1))
def hs_step(syn0, syn1, contexts, codes, points, lengths, lr):
    """Skip-gram hierarchical softmax.

    codes/points (B, L) per-pair Huffman path of the center word, lengths (B,)
    valid path length. The ragged walk of the reference
    (SkipGram.java inner loop over vocabWord.getPoints()) becomes a masked
    dense (B, L, D) computation."""
    v = syn0[contexts]                                   # (B, D)
    u = syn1[points]                                     # (B, L, D)
    B, L = codes.shape
    # padding rows carry lengths=0, so the path mask doubles as batch mask
    mask = (jnp.arange(L)[None, :] < lengths[:, None]).astype(v.dtype)
    s = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", v, u))   # (B, L)
    g = (1.0 - codes.astype(v.dtype) - s) * mask         # word2vec: 1 - code - sigma
    dv = jnp.einsum("bl,bld->bd", g, u)
    du = g[..., None] * v[:, None, :]
    D = v.shape[-1]
    valid = (lengths > 0).astype(v.dtype)
    syn0 = _scatter_mean_update(syn0, contexts, dv, valid, lr)
    syn1 = _scatter_mean_update(syn1, points.reshape(-1),
                                du.reshape(B * L, D), mask.reshape(-1), lr)
    # masked binary cross-entropy along the path
    target = 1.0 - codes.astype(v.dtype)
    bce = -(target * jnp.log(s + _EPS) + (1.0 - target) * jnp.log(1.0 - s + _EPS))
    loss = jnp.sum(bce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return syn0, syn1, loss


@functools.partial(jax.jit, donate_argnums=(0, 1))
def cbow_step(syn0, syn1neg, centers, context_bags, bag_mask, negs, wmask, lr):
    """CBOW with negative sampling (reference CBOW.java).

    context_bags (B, W) int32 context indices (padded), bag_mask (B, W) 1/0,
    centers (B,), negs (B, K), wmask (B,) batch padding mask. The bag mean
    predicts the center."""
    bags = syn0[context_bags]                             # (B, W, D)
    m = bag_mask[..., None]
    denom = jnp.maximum(jnp.sum(bag_mask, axis=-1, keepdims=True), 1.0)
    h = jnp.sum(bags * m, axis=1) / denom                 # (B, D) bag mean
    u_pos = syn1neg[centers]
    u_neg = syn1neg[negs]
    s_pos = jax.nn.sigmoid(jnp.sum(h * u_pos, axis=-1))
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, u_neg))
    g_pos = (1.0 - s_pos) * wmask
    g_neg = -s_neg * wmask[:, None]
    dh = g_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    du_pos = g_pos[:, None] * h
    du_neg = g_neg[..., None] * h[:, None, :]
    B, K = negs.shape
    D = h.shape[-1]
    W = context_bags.shape[1]
    # distribute the bag gradient equally to members (mean => /count)
    dbag = (dh[:, None, :] * m) / denom[..., None]        # (B, W, D)
    bag_w = (bag_mask * wmask[:, None]).reshape(-1)
    syn0 = _scatter_mean_update(syn0, context_bags.reshape(-1),
                                dbag.reshape(B * W, D), bag_w, lr)
    out_idx = jnp.concatenate([centers, negs.reshape(-1)])
    out_grads = jnp.concatenate([du_pos, du_neg.reshape(B * K, D)])
    out_w = jnp.concatenate([wmask, jnp.repeat(wmask, K)])
    syn1neg = _scatter_mean_update(syn1neg, out_idx, out_grads, out_w, lr)
    nll = -(jnp.log(s_pos + _EPS) + jnp.sum(jnp.log(1.0 - s_neg + _EPS), axis=-1))
    loss = jnp.sum(nll * wmask) / jnp.maximum(jnp.sum(wmask), 1.0)
    return syn0, syn1neg, loss


@functools.partial(jax.jit, donate_argnums=(0, 1))
def cbow_hs_step(syn0, syn1, centers_codes, centers_points, centers_lengths,
                 context_bags, bag_mask, lr):
    """CBOW with hierarchical softmax (reference CBOW.java's HS branch):
    the context-bag mean walks the *center* word's Huffman path.

    centers_codes/points (B, L), centers_lengths (B,) — padded batch rows
    carry lengths=0 so the path mask doubles as the batch mask (as in
    hs_step). context_bags (B, W) int32, bag_mask (B, W)."""
    bags = syn0[context_bags]                             # (B, W, D)
    m = bag_mask[..., None]
    denom = jnp.maximum(jnp.sum(bag_mask, axis=-1, keepdims=True), 1.0)
    h = jnp.sum(bags * m, axis=1) / denom                 # (B, D)
    u = syn1[centers_points]                              # (B, L, D)
    B, L = centers_codes.shape
    mask = (jnp.arange(L)[None, :] < centers_lengths[:, None]).astype(h.dtype)
    s = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", h, u))
    g = (1.0 - centers_codes.astype(h.dtype) - s) * mask
    dh = jnp.einsum("bl,bld->bd", g, u)
    du = g[..., None] * h[:, None, :]
    D = h.shape[-1]
    W = context_bags.shape[1]
    dbag = (dh[:, None, :] * m) / denom[..., None]
    valid = (centers_lengths > 0).astype(h.dtype)
    bag_w = (bag_mask * valid[:, None]).reshape(-1)
    syn0 = _scatter_mean_update(syn0, context_bags.reshape(-1),
                                dbag.reshape(B * W, D), bag_w, lr)
    syn1 = _scatter_mean_update(syn1, centers_points.reshape(-1),
                                du.reshape(B * L, D), mask.reshape(-1), lr)
    target = 1.0 - centers_codes.astype(h.dtype)
    bce = -(target * jnp.log(s + _EPS) + (1.0 - target) * jnp.log(1.0 - s + _EPS))
    loss = jnp.sum(bce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return syn0, syn1, loss


@functools.partial(jax.jit, donate_argnums=(0,))
def sgns_infer_step(docvec, syn1neg, centers, negs, wmask, lr):
    """DBOW inference step (reference ParagraphVectors.inferVector): a single
    frozen-everything-else SGNS pass where only the document vector trains.

    docvec (D,); centers (B,) words of the document; negs (B, K)."""
    u_pos = syn1neg[centers]                              # (B, D)
    u_neg = syn1neg[negs]                                 # (B, K, D)
    s_pos = jax.nn.sigmoid(u_pos @ docvec)                # (B,)
    s_neg = jax.nn.sigmoid(jnp.einsum("bkd,d->bk", u_neg, docvec))
    g_pos = (1.0 - s_pos) * wmask
    g_neg = -s_neg * wmask[:, None]
    dv = jnp.einsum("b,bd->d", g_pos, u_pos) + \
        jnp.einsum("bk,bkd->d", g_neg, u_neg)
    docvec = docvec + lr * dv / jnp.maximum(jnp.sum(wmask), 1.0)
    nll = -(jnp.log(s_pos + _EPS) + jnp.sum(jnp.log(1.0 - s_neg + _EPS), axis=-1))
    loss = jnp.sum(nll * wmask) / jnp.maximum(jnp.sum(wmask), 1.0)
    return docvec, loss


@functools.partial(jax.jit, donate_argnums=(0,))
def cbow_infer_step(docvec, syn0, syn1neg, centers, context_bags, bag_mask,
                    negs, wmask, lr):
    """DM inference step: the doc vector joins each context bag (frozen word
    vectors), gradient flows to the doc vector only."""
    bags = syn0[context_bags]                             # (B, W, D)
    m = bag_mask[..., None]
    count = jnp.sum(bag_mask, axis=-1, keepdims=True) + 1.0   # + doc vector
    h = (jnp.sum(bags * m, axis=1) + docvec[None, :]) / count
    u_pos = syn1neg[centers]
    u_neg = syn1neg[negs]
    s_pos = jax.nn.sigmoid(jnp.sum(h * u_pos, axis=-1))
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, u_neg))
    g_pos = (1.0 - s_pos) * wmask
    g_neg = -s_neg * wmask[:, None]
    dh = g_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    dv = jnp.sum(dh / count, axis=0)                      # doc's share of each bag
    docvec = docvec + lr * dv / jnp.maximum(jnp.sum(wmask), 1.0)
    nll = -(jnp.log(s_pos + _EPS) + jnp.sum(jnp.log(1.0 - s_neg + _EPS), axis=-1))
    loss = jnp.sum(nll * wmask) / jnp.maximum(jnp.sum(wmask), 1.0)
    return docvec, loss


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def glove_step(w, wc, b, bc, gw, gwc, gb, gbc, rows, cols, logx, weight, lr):
    """AdaGrad step on the GloVe objective (reference glove/Glove.java +
    legacy GloVe.java AdaGrad math): f(x) * (w_i·wc_j + b_i + bc_j - log x)^2.

    w/wc (V, D) main/context vectors, b/bc (V,) biases, g* AdaGrad
    accumulators, rows/cols (B,) co-occurrence pair indices, logx (B,)
    log co-occurrence, weight (B,) f(x)."""
    wi = w[rows]
    wj = wc[cols]
    diff = jnp.sum(wi * wj, axis=-1) + b[rows] + bc[cols] - logx   # (B,)
    fdiff = weight * diff
    loss = 0.5 * jnp.mean(fdiff * diff)
    dwi = fdiff[:, None] * wj
    dwj = fdiff[:, None] * wi
    # AdaGrad: accumulate squared grads, scale updates
    gw = gw.at[rows].add(dwi * dwi)
    gwc = gwc.at[cols].add(dwj * dwj)
    gb = gb.at[rows].add(fdiff * fdiff)
    gbc = gbc.at[cols].add(fdiff * fdiff)
    w = w.at[rows].add(-lr * dwi / jnp.sqrt(gw[rows] + _EPS))
    wc = wc.at[cols].add(-lr * dwj / jnp.sqrt(gwc[cols] + _EPS))
    b = b.at[rows].add(-lr * fdiff / jnp.sqrt(gb[rows] + _EPS))
    bc = bc.at[cols].add(-lr * fdiff / jnp.sqrt(gbc[cols] + _EPS))
    return w, wc, b, bc, gw, gwc, gb, gbc, loss


# ---------------------------------------------------------------------------
# Whole-chunk scanned steps: ONE dispatch for a stack of (num_batches, B)
# slices. NOT used by the SequenceVectors training loops — per-batch
# dispatch overlaps host pair/negative prep with device compute, while the
# scan serializes them. Kept as a parity-tested alternative for
# environments where dispatch latency dominates (precomputed batches). The
# underlying (unjitted) step bodies are reused via .__wrapped__ so the math
# stays defined once.

def _scanned(step_fn, num_tables=2):
    def scan_fn(*args):
        tables = args[:num_tables]
        batches = args[num_tables:-1]
        lr = args[-1]

        def body(carry, inp):
            out = step_fn(*carry, *inp, lr)
            return out[:num_tables], out[num_tables]

        tables, losses = jax.lax.scan(body, tables, batches)
        return (*tables, losses)

    return functools.partial(jax.jit, donate_argnums=tuple(range(num_tables)))(scan_fn)


sgns_scan = _scanned(sgns_step.__wrapped__)
hs_scan = _scanned(hs_step.__wrapped__)
cbow_scan = _scanned(cbow_step.__wrapped__)
cbow_hs_scan = _scanned(cbow_hs_step.__wrapped__)


# ---------------------------------------------------------------------------
# Macro-dispatch SGNS: one XLA program trains a whole (NB, B) stack of pair
# batches with negatives drawn ON DEVICE from the unigram table: shipping
# (B, K) negatives per batch and dispatching per batch is transfer- and
# dispatch-bound. Here the host ships only the packed pair indices (int16
# when the vocab allows) and the device does the rest: ~7x less H2D
# traffic and NB fewer dispatches.

_sgns_macro_cache = {}


def sgns_macro_step(K: int):
    """Returns the jitted macro step for K negatives (cached per K)."""
    fn = _sgns_macro_cache.get(K)
    if fn is not None:
        return fn

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(syn0, syn1neg, neg_table, centers, contexts, key, lr):
        centers = centers.astype(jnp.int32)
        contexts = contexts.astype(jnp.int32)
        B = centers.shape[1]
        wm = jnp.ones((B,), syn0.dtype)
        T = neg_table.shape[0]

        def body(carry, inp):
            s0, s1, k = carry
            ce, ct = inp
            k, k2 = jax.random.split(k)
            negs = neg_table[jax.random.randint(k2, (B, K), 0, T)]
            s0, s1, loss = sgns_step.__wrapped__(s0, s1, ce, ct, negs, wm, lr)
            return (s0, s1, k), loss

        (syn0, syn1neg, _), losses = jax.lax.scan(
            body, (syn0, syn1neg, key), (centers, contexts))
        return syn0, syn1neg, losses

    _sgns_macro_cache[K] = run
    return run


# ---------------------------------------------------------------------------
# Corpus-resident SGNS: the encoded corpus lives in HBM and the device
# generates (center, context) pairs AND negatives itself — per macro-step the
# host ships only a PRNG key and the lr scalar, so throughput is completely
# independent of host->device bandwidth (the r4 path still shipped int16
# pair batches).
#
# Pair distribution matches the host enumeration exactly: the reference
# (SkipGram.java:156) visits every position with a dynamic radius
# r ~ U[1, w] and trains all offsets d <= r on both sides, so offset d
# occurs with probability (w - d + 1)/w per side per position. Here each
# sampled pair draws (position ~ U[corpus], side ~ ±1, d ~ P(d) ∝ w-d+1)
# — the same joint distribution, sampled i.i.d. instead of enumerated; an
# epoch processes T*(w+1) pairs, the enumeration's expected pair count.
#
# Negatives are SHARED per micro-batch (K rows serve all B pairs): their
# accumulation then becomes a dense (K, B) x (B, D) matmul instead of a
# B*K-row scatter, which removes ~85% of the scatter-matmul FLOPs. Sharing
# negatives across a minibatch is the standard batched-word2vec design
# (Ji et al. 2016, "Parallelizing Word2Vec in Shared and Distributed
# Memory"); with count-normalized updates it matches the per-pair-negative
# path on every embedding-quality test in tests/test_nlp.py.

_sgns_corpus_cache = {}


def sgns_corpus_macro_step(K: int, W: int, B: int, NB: int):
    """Jitted macro step: NB on-device-generated batches of B pairs, K
    shared negatives per batch, window w=W. Cached per static config.
    The corpus operand may be sentinel-padded (sid=-1) to a canonical
    length; the true token count and the active-batch quota arrive as
    device scalars (``true_t``, ``n_active``), so one compiled program
    serves every segment length up to the padding budget."""
    key_ = (K, W, B, NB)
    fn = _sgns_corpus_cache.get(key_)
    if fn is not None:
        return fn

    import numpy as np
    # inverse-CDF table for P(d) ∝ (W - d + 1), d in 1..W
    wts = np.arange(W, 0, -1, dtype=np.int64)
    cum = np.cumsum(wts)
    total = int(cum[-1])
    dist_cdf = jnp.asarray(cum, jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(syn0, syn1neg, corpus, sid, neg_table, keep, key, lr, true_t,
            n_active):
        # corpus/sid may be PADDED to the segment budget so every segment
        # length compiles the same program; ``true_t`` (device scalar) is
        # the real token count — position sampling and validity use it, so
        # the sentinel padding (sid = -1) is never sampled or paired.
        # ``n_active`` (device scalar) masks trailing batches beyond the
        # segment's pair quota: NB stays static (one compiled scan) while
        # the trained pair count still tracks the true T.
        Tpad = corpus.shape[0]
        TT = neg_table.shape[0]
        true_t = jnp.asarray(true_t, jnp.int32)
        n_active = jnp.asarray(n_active, jnp.int32)

        def body(carry, inp):
            s0, s1 = carry
            k, bi = inp
            kp, kd, kside, kneg, kkeep = jax.random.split(k, 5)
            pos = jax.random.randint(kp, (B,), 0, true_t)
            d = 1 + jnp.searchsorted(
                dist_cdf, jax.random.randint(kd, (B,), 0, total),
                side="right").astype(jnp.int32)
            side = jnp.where(jax.random.bernoulli(kside, 0.5, (B,)), 1, -1)
            cpos = pos + side * d
            valid = (cpos >= 0) & (cpos < true_t) & (bi < n_active)
            cposc = jnp.clip(cpos, 0, Tpad - 1)
            valid &= sid[pos] == sid[cposc]
            # corpus/sid may ship int16 (halved upload); index math in
            # int32
            centers = corpus[pos].astype(jnp.int32)
            contexts = corpus[cposc].astype(jnp.int32)
            if keep is not None:
                # APPROXIMATE subsampling: drops pairs whose endpoints fail
                # the keep draw. The host path removes words from the
                # stream BEFORE pairing (windows then reach across dropped
                # words) — reference semantics. Close in expectation, not
                # identical; the auto gate in SequenceVectors.fit therefore
                # keeps sampling>0 configs on the host path unless
                # device_corpus=True is explicit.
                k1, k2 = jax.random.split(kkeep)
                valid &= jax.random.bernoulli(k1, keep[centers])
                valid &= jax.random.bernoulli(k2, keep[contexts])
            wmask = valid.astype(s0.dtype)
            negs = neg_table[jax.random.randint(kneg, (K,), 0, TT)]

            # SGNS with shared negatives (same convention as sgns_step:
            # context word's input vector vs center word's output path)
            v = s0[contexts]                                  # (B, D)
            u_pos = s1[centers]                               # (B, D)
            u_neg = s1[negs]                                  # (K, D)
            s_pos = jax.nn.sigmoid(jnp.sum(v * u_pos, -1))    # (B,)
            s_neg = jax.nn.sigmoid(v @ u_neg.T)               # (B, K)
            g_pos = (1.0 - s_pos) * wmask
            g_neg = -s_neg * wmask[:, None]
            dv = g_pos[:, None] * u_pos + g_neg @ u_neg
            du_pos = g_pos[:, None] * v
            s0 = _scatter_mean_update(s0, contexts, dv, wmask, lr)
            s1 = _scatter_mean_update(s1, centers, du_pos, wmask, lr)
            # shared negatives: dense accumulation, count = #valid pairs
            npairs = jnp.maximum(jnp.sum(wmask), 1.0)
            s1 = s1.at[negs].add(lr * (g_neg.T @ v) / npairs)
            nll = -(jnp.log(s_pos + _EPS)
                    + jnp.sum(jnp.log(1.0 - s_neg + _EPS), -1))
            loss = jnp.sum(nll * wmask) / npairs
            return (s0, s1), loss

        keys = jax.random.split(key, NB)
        (syn0, syn1neg), losses = jax.lax.scan(
            body, (syn0, syn1neg), (keys, jnp.arange(NB, dtype=jnp.int32)))
        return syn0, syn1neg, losses

    _sgns_corpus_cache[key_] = run
    return run

"""Pallas TPU kernels for the chunked scan of Kimi Delta Attention.

The boundary is ``chunked_kda`` (nn/conf/linear_attention.py): the gated
delta rule from a zero state, chunk by chunk. In plain ``jax.numpy`` a
chunk's state-free terms (decayed scores, the triangular solve) are some
hundreds of small XLA operations whose operands cross HBM between fusions;
here a chunk of one head is taken up once (q, k, v in the compute type, g
in float32), everything about it is made on VMEM values, and only o (and,
for the backward pass, the state at the chunk's entry, the chunk's solved
u and its decayed scores) is written back.

What is computed is ``chunked_kda``'s algorithm, not a cheaper one: chunks
of 64 steps; the diagonal blocks of 8 x 8 written out channel by channel
(``exp(G_r - G_i)`` itself, exact at any decay); blocks below the diagonal
around the block's first row, where both factors are at most 1; the
unit-triangular system by forward substitution over blocks of 8; the
carried state, the running sum of g and every product of the solve in
float32. The products ``chunked_kda`` leaves at the default precision (the
scores below the diagonal, the four through the state) take the MXU's
bfloat16 operands here too, as XLA's default does on a TPU, unless the
ambient ``jax.default_matmul_precision`` asks for float32 or the kernel is
interpreted. Three orders differ from the ``jax.numpy`` form, none in what
is rounded how:

* the solve is run once, for ``U = T b (V - (K exp G) S_0)``, where the
  ``jax.numpy`` form solves for its two state-free halves (its scan hoists
  them out of the state's chain; a kernel has the state at hand);
* the solve's 8-row products are sums of eight rank-one terms on the VPU,
  exact float32, not MXU passes: at ``Precision.HIGHEST`` an 8-row product
  is six passes that each load a whole weight tile;
* a diagonal block's inverse is applied by substitution inside the block, a
  row at a time, and never formed: its columns come out of the lane
  reductions already spread over the lanes, where the Neumann products of
  ``_unit_lower_inverse`` cost four float32 MXU products of 64 x 64 a chunk
  (a third of the kernel's bundles, counted in the compiler's schedule).

Grid (batch, heads / heads-a-step, chunk): the chunk axis is sequential,
the heads of a step share a (heads, V, K) float32 scratch that carries
their states (kept transposed: the decay then scales lanes). The kernels
take (batch, heads, time, K) arrays, so a (64, 128) tile of one head is a
``BlockSpec`` window; XLA makes the transposes on the way in and out
(windows of (batch, time, heads x K) arrays read 7 ms a layer slower on
the chip, strided windows of the arrays as they are the same).

Backward: the same grid with the chunk index reversed and dS carried in
the scratch. A chunk's gradients need its own q, k, v, g, b, its entry
state, dO and the dS that arrives, and two things the forward kernel made
of them and wrote out, which this kernel READS and does not make again
(PERF.md §6, PR 49: of the 6,550 bundles a pair of heads that the kernel
was when it recomputed the chunk's terms, the second forward substitution,
``_solve_lower``, was 1,234 and these scores 365): the solved u, and the
decayed scores P and ``kk_off`` (k's with itself below the diagonal blocks)
side by side as one (C, 2 C) array, 128 whole lanes. It makes again the
running sum of g and k's diagonal blocks as columns (the transposed solve,
``_solve_upper``, wants them spread over the lanes), and emits dq, dk, dv,
dg, db, each piece written by hand and held to ``jax.vjp`` of the
``jax.numpy`` form in tests/test_zz_pallas.py.

Under a layer's rematerialisation (``apply_layer``, ``remat=``) the forward
kernel's four outputs in its ``save`` form, o (heads-major, as the kernel
wrote it: its transpose fuses into whoever reads it, in both passes; named
after the transpose it became four copies a step on the chip), the chunks'
entry states, the chunks' solved u and their scores ``[P | kk_off]``
(heads-major, float32), carry the ``checkpoint_name``s of ``KEPT``: the
delta-rule layers declare them
(``remat_keeps``), so the step runs ``kda_scan_fwd`` once a layer and the
backward pass reads what the first pass wrote, in float32 as it was made
(``kept_bytes``: 80 KB a token at 32 heads of 128: o 16, u 16, the scores
16, the states 32), where recomputing them ran the kernel a second time.
All four are residuals of the ``custom_vjp``; one left unnamed would bring
the second run back. The form without ``save`` (nothing differentiated) writes o alone.
q, k, v, g, b are residuals too and are not
named: ``kda_inputs`` makes them again (3.7 ms a step in the Kimi cell for
1.34 GB, PERF.md §7), from the projections' outputs, the input kernel's
only residuals, of which the layers DO name and keep all but KDA's ``x Wv``
(``linear_attention.PROJECTIONS_KEPT``, PR 41), so the backward pass runs
those wide products once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.perf import pallas as _pk

__all__ = ["supported", "kda_scan", "kda_scan_heads_major", "KEPT",
           "kept_bytes"]

CHUNK, SUB = 64, 8
# the forward kernel's outputs by their ``checkpoint_name``: o (batch,
# heads, time, V), the chunks' entry states that the backward kernel starts
# each chunk from, the chunks' solved u (batch, heads, time, V) and their
# decayed scores [P | kk_off] (batch, heads, time, 2 CHUNK)
KEPT = ("kda_scan.o", "kda_scan.states", "kda_scan.u", "kda_scan.scores")
_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
# heads a grid step: amortises the step's fixed cost (0.35 us)
_HEADS_A_STEP = 8
_VMEM_LIMIT = 64 * 1024 * 1024
# heads in one straight-line loop body (see _over_heads): 2 reads 6% under 1
# on the chip forward, 4 no better
_INTERLEAVE = 2


def _whole_lanes(head_dim: int) -> bool:
    return head_dim % 128 == 0 and 0 < head_dim <= 256


def supported(q, k, v, g, b, chunk: int, sub: int) -> bool:
    """Shapes the kernels take: (batch, time, heads, K) with K = V a
    multiple of 128 (a head is whole lanes), chunks of 64 and blocks of 8
    (the kernels' unrolling), q, k, v alike in bfloat16 or float32; on a
    TPU backend or in interpret mode. ``time`` is padded by the caller."""
    if chunk != CHUNK or sub != SUB:
        return False
    if q.ndim != 4 or q.shape != k.shape or q.shape != v.shape:
        return False
    if g.shape != q.shape or b.shape != q.shape[:3]:
        return False
    if not _whole_lanes(q.shape[-1]) or 0 in q.shape:
        return False
    if not (q.dtype == k.dtype == v.dtype
            and q.dtype in (jnp.bfloat16, jnp.float32)):
        return False
    return _pk.interpret() or jax.default_backend() == "tpu"


def kept_bytes(time: int, heads: int, head_dim: int, chunk: int) -> int:
    """Bytes of ``KEPT`` for one sequence of ``time`` steps: o and u (time,
    heads, V) each, the scores (time, heads, 2 CHUNK) and the (heads,
    chunks, K, K) entry states, float32, at the length the kernels run at;
    0 for a head or a chunk the kernels do not take (the ``jax.numpy`` form
    names nothing)."""
    if chunk != CHUNK or not _whole_lanes(head_dim):
        return 0
    padded = time + (-time) % CHUNK
    return 4 * heads * (head_dim * (2 * padded + padded // CHUNK * head_dim)
                        + 2 * CHUNK * padded)


def _trace_time_choices():
    """(exact, interpret): do the default-precision products run in
    float32 (in interpret mode, where the CPU's are float32 anyway, and
    where the ambient default precision asks for it), and is the kernel
    interpreted. Static arguments of the jitted calls below, so a cached
    trace is never served to another choice."""
    interpret = _pk.interpret()
    ambient = jax.config.jax_default_matmul_precision
    return interpret or ambient in ("highest", "float32"), interpret


# ------------------------------------------------------------ chunk algebra
# Everything below works on VALUES of one chunk of one head: (C, K) float32
# arrays q, k, v, g, a (C, 1) column b and the (V, K) transposed state.
# The same functions are traced into the kernels and called as plain
# jax.numpy by the tests. A chunk's forward and backward are unrolled
# Python (8 blocks, 8 rows): jitted, a kernel's interleaved heads, the
# forward with and without saved states and every layer of a model at
# these shapes share one trace (four layers of the Kimi Linear share
# traced theirs in 4 x 8.4 s on the chip's host before).
_NN = (((1,), (0,)), ((), ()))        # x @ y
_NT = (((1,), (1,)), ((), ()))        # x @ y^T
_TN = (((0,), (0,)), ((), ()))        # x^T @ y


def _dot_hi(x, y, dims=_NN):
    """A product ``chunked_kda`` makes at ``Precision.HIGHEST``."""
    return lax.dot_general(x, y, dims, precision=_HI,
                           preferred_element_type=_F32)


def _dot(x, y, exact: bool, dims=_NN):
    """A product ``chunked_kda`` makes at the default precision."""
    if exact:
        return _dot_hi(x, y, dims)
    return lax.dot_general(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                           dims, preferred_element_type=_F32)


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _running_sum(g):
    """cumsum along the chunk's rows, as a product with a lower-triangular
    ones matrix in float32."""
    c = g.shape[0]
    tri = (_iota((c, c), 0) >= _iota((c, c), 1)).astype(_F32)
    return _dot_hi(tri, g)


def _reverse_running_sum(dg):
    c = dg.shape[0]
    tri = (_iota((c, c), 0) <= _iota((c, c), 1)).astype(_F32)
    return _dot_hi(tri, dg)


def _block_row(x, i: int):
    """Row ``i`` of every block of SUB rows, repeated over the block."""
    c, kd = x.shape
    x3 = x.reshape(c // SUB, SUB, kd)
    return jnp.broadcast_to(x3[:, i:i + 1, :], x3.shape).reshape(c, kd)


def _block_masks(c: int):
    """(C, C) column index less the row's block start, and the row's index
    inside its block (C, 1)."""
    row = _iota((c, c), 0)
    return _iota((c, c), 1) - (row // SUB) * SUB, _iota((c, 1), 0) % SUB


def _diag_decay(g_cum, i: int, row_in_block):
    """exp(G_r - G_i) for the rows r >= i of i's block, 0 before."""
    dg = g_cum - _block_row(g_cum, i)
    return jnp.exp(jnp.where(row_in_block >= i, dg, -jnp.inf))


def _diag_columns(xs, k, g_cum):
    """For each x of ``xs`` the diagonal blocks of its decayed scores with
    k, channel by channel, as SUB columns (C, 1): column i holds
    M[r, i of r's block] = sum_c x[r, c] k[i, c] exp(G[r, c] - G[i, c]) for
    the rows r >= i of each block and 0 before. A lane reduction leaves
    its result on every lane, so a column costs nothing to spread again."""
    _, row_in_block = _block_masks(k.shape[0])
    cols = [[] for _ in xs]
    for i in range(SUB):
        kd = _block_row(k, i) * _diag_decay(g_cum, i, row_in_block)
        for n, x in enumerate(xs):
            cols[n].append(jnp.sum(x * kd, axis=1, keepdims=True))
    return cols


def _placed(cols):
    """The block-diagonal (C, C) matrix of SUB columns (C, 1)."""
    c = cols[0].shape[0]
    col_in_block, _ = _block_masks(c)
    out = jnp.zeros((c, c), _F32)
    for i, col in enumerate(cols):
        out = out + jnp.where(col_in_block == i, col, 0.0)
    return out


def _below_factors(k, g_cum, s: int):
    """The keys before block ``s`` scaled to its first row R:
    k_i exp(R - G_i), both factors of the split at most 1; zero rows from
    the block on, so the product needs no mask."""
    c, kd = k.shape
    n = s * SUB
    ref = g_cum[n:n + 1, :]
    scaled = k[:n] * jnp.exp(jnp.minimum(ref - g_cum[:n], 0.0))
    return jnp.concatenate([scaled, jnp.zeros((c - n, kd), _F32)], 0)


def _below_blocks(xs, k, g_cum, exact: bool):
    """For each x the blocks below the diagonal ones: rows of block s
    against every key before the block, through the MXU around the
    block's first row; zero in and above the diagonal blocks."""
    c = k.shape[0]
    ns = c // SUB
    scale = jnp.exp(g_cum - _block_row(g_cum, 0))
    xd = [x * scale for x in xs]
    rows = [[jnp.zeros((SUB, c), _F32)] for _ in xs]
    for s in range(1, ns):
        lhs = jnp.concatenate([x[s * SUB:(s + 1) * SUB] for x in xd], 0)
        off = _dot(lhs, _below_factors(k, g_cum, s), exact, _NT)
        for n in range(len(xs)):
            rows[n].append(off[n * SUB:(n + 1) * SUB])
    return [jnp.concatenate(r, 0) for r in rows]


def _columns_product(m, x, col0: int):
    """m[:, col0:col0 + SUB] @ x for x of SUB rows, as SUB rank-one terms
    on the VPU: exact float32, no MXU pass for an 8-row operand. A column
    of m is spread over the lanes once for all of m's rows."""
    terms = [m[:, col0 + j:col0 + j + 1] * x[j:j + 1, :] for j in range(SUB)]
    while len(terms) > 1:
        terms = [a + b for a, b in zip(terms[::2], terms[1::2])]
    return terms[0]


def _solve_lower(a_off, a_cols, rhs):
    """x with (I + a) x = rhs for the strictly lower a = ``a_off`` (its
    blocks below the diagonal ones) + ``a_cols`` (its diagonal blocks, as
    columns): forward substitution over blocks of SUB rows,
    x_s = (I + a_ss)^-1 (rhs_s - a[s, :s] x[:s]). A solved block is taken
    off every row below at once; (I + a_ss)^-1 is applied by substitution
    inside the block, a row at a time (the recurrence itself: the inverse
    is never formed)."""
    ns = a_off.shape[0] // SUB
    rest, out = rhs, []
    for s in range(ns):
        x = rest[:SUB]
        for i in range(SUB - 1):          # rows <= i of column i are 0
            x = x - a_cols[i][s * SUB:(s + 1) * SUB] * x[i:i + 1, :]
        out.append(x)
        if s + 1 < ns:
            rest = rest[SUB:] - _columns_product(
                a_off[(s + 1) * SUB:], x, s * SUB)
    return jnp.concatenate(out, 0)


def _solve_upper(a_off, a_cols, rhs):
    """x with (I + a)^T x = rhs: the transposed system of
    ``_solve_lower``, blocks from the last one up; inside a block row i
    follows from the rows after it, x_i = rhs_i - sum_r a[r, i] x_r."""
    ns = a_off.shape[0] // SUB
    a_t = a_off.T
    row = _iota((SUB, 1), 0)
    rest, out = rhs, [None] * ns
    for s in reversed(range(ns)):
        x = rest[s * SUB:]
        for i in reversed(range(SUB - 1)):
            known = jnp.sum(a_cols[i][s * SUB:(s + 1) * SUB] * x, axis=0,
                            keepdims=True)
            x = x - jnp.where(row == i, known, 0.0)
        out[s] = x
        if s:
            rest = rest[:s * SUB] - _columns_product(
                a_t[:s * SUB], x, s * SUB)
    return jnp.concatenate(out, 0)


def _strictly_lower(c: int):
    return _iota((c, c), 0) > _iota((c, c), 1)


def _strictly_below(cols):
    """Diagonal blocks' columns with the diagonal zeroed too (i < r)."""
    _, row_in_block = _block_masks(cols[0].shape[0])
    return [jnp.where(row_in_block > i, col, 0.0)
            for i, col in enumerate(cols)]


def _chunk_terms(q, k, g, exact: bool):
    """What a chunk needs before its state: the running sum G, the decayed
    scores P (i <= r) of q with k as a matrix, and those of k with itself
    (i < r) as the part below the diagonal blocks (a matrix) and the
    diagonal blocks (columns)."""
    g_cum = _running_sum(g)
    p_cols, kk_cols = _diag_columns([q, k], k, g_cum)
    p_off, kk_off = _below_blocks([q, k], k, g_cum, exact)
    return g_cum, p_off + _placed(p_cols), kk_off, _strictly_below(kk_cols)


@functools.partial(jax.jit, static_argnames="exact")
def chunk_forward(q, k, v, g, b, st, exact: bool):
    """One chunk of one head from its entry state ``st`` (V, K): the
    chunk's output (C, V), the exit state, and what the backward pass
    reads: the solved u (C, V) and the decayed scores [P | kk_off] (C, 2 C)."""
    g_cum, p, kk_off, kk_cols = _chunk_terms(q, k, g, exact)
    gamma = jnp.exp(g_cum)
    g_end = g_cum[-1:, :]
    rhs = b * (v - _dot(k * gamma, st, exact, _NT))
    u = _solve_lower(kk_off * b, [col * b for col in kk_cols], rhs)
    o = _dot(q * gamma, st, exact, _NT) + _dot(p, u, exact)
    k_end = k * jnp.exp(g_end - g_cum)
    st = st * jnp.exp(g_end) + _dot(u, k_end, exact, _TN)
    return o, st, u, jnp.concatenate([p, kk_off], 1)


@functools.partial(jax.jit, static_argnames="exact")
def chunk_backward(q, k, v, g, b, st, u, scores, do, dst, exact: bool):
    """The chunk's gradients from dO (C, V) and the gradient ``dst`` of
    its exit state: (dq, dk, dv, dg, db, d entry state). The solved ``u``
    and ``scores`` = [P | kk_off] are ``chunk_forward``'s (neither the
    forward substitution nor the products below the diagonal blocks are run
    again); k's diagonal blocks are made again, as columns."""
    c = k.shape[0]
    g_cum = _running_sum(g)
    p, kk_off = scores[:, :c], scores[:, c:]
    kk_cols = _strictly_below(_diag_columns([k], k, g_cum)[0])
    a_off, a_cols = kk_off * b, [col * b for col in kk_cols]
    gamma = jnp.exp(g_cum)
    g_end = g_cum[-1:, :]
    tail = jnp.exp(g_end - g_cum)
    decay = jnp.exp(g_end)
    kg, qg, k_end = k * gamma, q * gamma, k * tail
    resid = v - _dot(kg, st, exact, _NT)

    # o = qg S + P u;  S' = decay S + k_end^T u   (S = st^T)
    du = _dot(p, do, exact, _TN) + _dot(k_end, dst, exact, _NT)
    dp = jnp.where(_iota((c, c), 0) >= _iota((c, c), 1),
                   _dot(do, u, exact, _NT), 0.0)
    dqg = _dot(do, st, exact)
    dk_end = _dot(u, dst, exact)
    d_st = dst * decay + _dot(do, qg, exact, _TN)
    d_g_end = jnp.sum(dst * st, axis=0, keepdims=True) * decay
    # (I + A) u = b resid, A = b_r kk
    drhs = _solve_upper(a_off, a_cols, du)
    da = jnp.where(_strictly_lower(c), -_dot_hi(drhs, u, _NT), 0.0)
    db = (jnp.sum(drhs * resid, axis=1, keepdims=True)
          + jnp.sum(da * (kk_off + _placed(kk_cols)), axis=1, keepdims=True))
    dresid = drhs * b
    dkg = -_dot(dresid, st, exact)
    d_st = d_st - _dot(dresid, kg, exact, _TN)
    # the decayed scores, P = scores(q, k) and kk = scores(k, k)
    dq_s, dkx_s, dk_s = _scores_backward(q, k, g_cum, dp, da * b, exact)
    dq = dq_s + dqg * gamma
    dk = dkx_s + dk_s + dkg * gamma + dk_end * tail
    # G enters through gamma, tail, decay and the scores
    d_tail = dk_end * k_end
    d_gcum = (dqg * qg + dkg * kg - d_tail
              + q * dq_s + k * dkx_s - k * dk_s)
    d_g_last = d_g_end + jnp.sum(d_tail, axis=0, keepdims=True)
    d_gcum = d_gcum + jnp.where(_iota((c, 1), 0) == c - 1, d_g_last, 0.0)
    return dq, dk, dresid, _reverse_running_sum(d_gcum), db, d_st


def _scores_backward(q, k, g_cum, dp, dkk, exact: bool):
    """Through M_x[r, i] = sum_c x[r, c] k[i, c] exp(G[r, c] - G[i, c])
    for x = q (cotangent ``dp``, i <= r) and x = k (``dkk``, i < r): the
    gradients of q, of k as
    the rows' factor and of k as the columns' factor, with the forward's
    split into diagonal blocks (channel by channel) and blocks below
    (around the block's first row). The gradient of G is then
    x . dx - k . dk (the caller's)."""
    c, kd = k.shape
    ns = c // SUB
    col_in_block, row_in_block = _block_masks(c)
    # diagonal blocks
    dq = jnp.zeros((c, kd), _F32)
    dkx = jnp.zeros((c, kd), _F32)
    dk_rows = []
    for i in range(SUB):
        e = _diag_decay(g_cum, i, row_in_block)
        fac = _block_row(k, i) * e
        here = col_in_block == i
        dp_i = jnp.sum(jnp.where(here, dp, 0.0), axis=1, keepdims=True)
        dkk_i = jnp.sum(jnp.where(here, dkk, 0.0), axis=1, keepdims=True)
        dq = dq + dp_i * fac
        dkx = dkx + dkk_i * fac
        # sum over the block's rows r of dM[r, i] x[r] exp(G_r - G_i)
        y = (dp_i * q + dkk_i * k) * e
        dk_rows.append(jnp.sum(y.reshape(ns, SUB, kd), axis=1,
                               keepdims=True))
    dk = jnp.concatenate(dk_rows, 1).reshape(c, kd)
    # blocks below the diagonal ones
    scale = jnp.exp(g_cum - _block_row(g_cum, 0))
    qd, kx = q * scale, k * scale
    dq_rows = [jnp.zeros((2 * SUB, kd), _F32)]
    for s in range(1, ns):
        fac = _below_factors(k, g_cum, s)
        rows = slice(s * SUB, (s + 1) * SUB)
        dm = jnp.concatenate([dp[rows], dkk[rows]], 0)       # (2 SUB, C)
        dq_rows.append(_dot(dm, fac, exact))
        lhs = jnp.concatenate([qd[rows], kx[rows]], 0)       # (2 SUB, K)
        n = s * SUB
        grad_fac = _dot(dm, lhs, exact, _TN)                 # (C, K)
        scaled = jnp.exp(jnp.minimum(g_cum[n:n + 1, :] - g_cum[:n], 0.0))
        dk = dk + jnp.concatenate(
            [grad_fac[:n] * scaled, jnp.zeros((c - n, kd), _F32)], 0)
    both = jnp.stack(dq_rows).reshape(ns, 2, SUB, kd)
    dq = dq + both[:, 0].reshape(c, kd) * scale
    dkx = dkx + both[:, 1].reshape(c, kd) * scale
    return dq, dkx, dk


# ------------------------------------------------------------------ kernels
def _head_column(tile, h):
    """Column ``h`` of a (C, heads) tile as (C, 1)."""
    return jnp.sum(jnp.where(_iota(tile.shape, 1) == h, tile, 0.0),
                   axis=1, keepdims=True)


def _over_heads(hb: int, head):
    """``head(h)`` for the step's heads, ``_INTERLEAVE`` of them in one
    straight-line loop body: their chains are independent, so the
    scheduler fills one's waits (an MXU result, the solve's dependent
    steps) with the other's work."""
    u = _INTERLEAVE if hb % _INTERLEAVE == 0 else 1

    def body(i, carry):
        for j in range(u):
            head(i * u + j)
        return carry

    lax.fori_loop(0, hb // u, body, 0)


def _fwd_kernel(hb, exact, save, q_ref, k_ref, v_ref, g_ref, b_ref,
                o_ref, *rest):
    from jax.experimental import pallas as pl
    st_ref = rest[-1]
    # ``save``: the windows of what the backward kernel reads
    states_ref, u_ref, scores_ref = rest[:-1] if save else (None,) * 3

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros(st_ref.shape, _F32)

    b_all = b_ref[0, 0]

    def head(h):
        st = st_ref[h]
        if save:
            states_ref[0, h, 0] = st
        o, st, u, scores = chunk_forward(
            q_ref[0, h].astype(_F32), k_ref[0, h].astype(_F32),
            v_ref[0, h].astype(_F32), g_ref[0, h],
            _head_column(b_all, h), st, exact)
        o_ref[0, h] = o
        st_ref[h] = st
        if save:
            u_ref[0, h] = u
            scores_ref[0, h] = scores

    _over_heads(hb, head)


def _bwd_kernel(hb, exact, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, u_ref,
                sc_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                dst_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros(dst_ref.shape, _F32)

    b_all = b_ref[0, 0]

    db_ref[0, 0] = jnp.zeros(b_all.shape, _F32)

    def head(h):
        dq, dk, dv, dg, db, dst = chunk_backward(
            q_ref[0, h].astype(_F32), k_ref[0, h].astype(_F32),
            v_ref[0, h].astype(_F32), g_ref[0, h],
            _head_column(b_all, h), s_ref[0, h, 0], u_ref[0, h],
            sc_ref[0, h], do_ref[0, h], dst_ref[h], exact)
        dq_ref[0, h] = dq.astype(dq_ref.dtype)
        dk_ref[0, h] = dk.astype(dk_ref.dtype)
        dv_ref[0, h] = dv.astype(dv_ref.dtype)
        dg_ref[0, h] = dg
        dst_ref[h] = dst
        db_ref[0, 0] += jnp.where(_iota(b_all.shape, 1) == h, db, 0.0)

    _over_heads(hb, head)


def _heads_a_step(h: int) -> int:
    return max(d for d in range(1, _HEADS_A_STEP + 1) if h % d == 0)


def _by_head_group(b, hb: int):
    """(B, T, H) -> (B, H / hb, T, hb): a step's heads as the minor axis
    of a small array (a window's last axis has to be whole)."""
    bsz, t, h = b.shape
    return jnp.swapaxes(b.reshape(bsz, t, h // hb, hb), 1, 2)


def _call(name, kernel, interpret, grid, in_specs, out_specs, out_shape,
          scratch):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _specs(shape, hb: int, reverse: bool):
    """Windows of the (B, H, T, K) arrays, of b by head group, of the
    (B, H, N, V, K) entry states and of the (B, H, T, 2 CHUNK) scores for
    grid (batch, head group, chunk)."""
    from jax.experimental import pallas as pl
    bsz, t, h, kd = shape
    n = t // CHUNK

    def at(c):
        return n - 1 - c if reverse else c

    wide = pl.BlockSpec((1, hb, CHUNK, kd), lambda i, j, c: (i, j, at(c), 0))
    col = pl.BlockSpec((1, 1, CHUNK, hb), lambda i, j, c: (i, j, at(c), 0))
    state = pl.BlockSpec((1, hb, 1, kd, kd),
                         lambda i, j, c: (i, j, at(c), 0, 0))
    pair = pl.BlockSpec((1, hb, CHUNK, 2 * CHUNK),
                        lambda i, j, c: (i, j, at(c), 0))
    return (bsz, h // hb, n), wide, col, state, pair


def _windows(q, k, v, g, heads_major: bool):
    """q, k, v, g as the kernels read them, (B, H, T, K), and that shape's
    (B, T, H, K): arrays that arrive time-major are transposed here, by
    XLA; arrays that arrive heads-major (``kda_inputs`` writes them so) are
    taken as they are."""
    if heads_major:
        bsz, h, t, kd = q.shape
        return [q, k, v, g], (bsz, t, h, kd)
    return [jnp.swapaxes(a, 1, 2) for a in (q, k, v, g)], q.shape


@functools.partial(jax.jit, static_argnames=("save", "exact", "interpret",
                                             "heads_major"))
def _forward(q, k, v, g, b, save: bool, exact: bool, interpret: bool,
             heads_major: bool = False):
    from jax.experimental.pallas import tpu as pltpu
    flat, shape = _windows(q, k, v, g, heads_major)
    bsz, t, h, kd = shape
    hb = _heads_a_step(h)
    grid, wide, col, state, pair = _specs(shape, hb, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((bsz, h, t, kd), _F32)]
    out_specs = [wide]
    if save:
        out_shape += [jax.ShapeDtypeStruct((bsz, h, t // CHUNK, kd, kd), _F32),
                      out_shape[0],
                      jax.ShapeDtypeStruct((bsz, h, t, 2 * CHUNK), _F32)]
        out_specs += [state, wide, pair]
    outs = _call(
        "kda_scan_fwd", functools.partial(_fwd_kernel, hb, exact, save),
        interpret, grid, [wide] * 4 + [col], out_specs, out_shape,
        [pltpu.VMEM((hb, kd, kd), _F32)])(*flat, _by_head_group(b, hb))
    if not save:
        return jnp.swapaxes(outs[0], 1, 2)
    # named as the kernel wrote them (the module's last paragraph)
    o, *saved = map(checkpoint_name, outs, KEPT)
    return jnp.swapaxes(o, 1, 2), *saved


@functools.partial(jax.jit, static_argnames=("exact", "interpret",
                                             "heads_major"))
def _backward(q, k, v, g, b, states, u, scores, do, exact: bool,
              interpret: bool, heads_major: bool = False):
    from jax.experimental.pallas import tpu as pltpu
    flat, shape = _windows(q, k, v, g, heads_major)
    bsz, t, h, kd = shape
    hb = _heads_a_step(h)
    grid, wide, col, state, pair = _specs(shape, hb, reverse=True)
    like = jax.ShapeDtypeStruct((bsz, h, t, kd), q.dtype)
    out_shape = [like, like, like,
                 jax.ShapeDtypeStruct((bsz, h, t, kd), _F32),
                 jax.ShapeDtypeStruct((bsz, h // hb, t, hb), _F32)]
    dq, dk, dv, dg, db = _call(
        "kda_scan_bwd", functools.partial(_bwd_kernel, hb, exact),
        interpret, grid, [wide] * 4 + [col, state, wide, pair, wide],
        [wide] * 4 + [col], out_shape, [pltpu.VMEM((hb, kd, kd), _F32)])(
            *flat, _by_head_group(b, hb), states, u, scores,
            jnp.swapaxes(do.astype(_F32), 1, 2))
    if not heads_major:
        dq, dk, dv, dg = (jnp.swapaxes(a, 1, 2) for a in (dq, dk, dv, dg))
    return dq, dk, dv, dg, jnp.swapaxes(db, 1, 2).reshape(bsz, t, h)


def _scan(heads_major: bool, doc: str):
    @jax.custom_vjp
    def scan(q, k, v, g, b):
        return _forward(q, k, v, g, b, False, *_trace_time_choices(),
                        heads_major=heads_major)

    def fwd(q, k, v, g, b):
        o, *saved = _forward(q, k, v, g, b, True, *_trace_time_choices(),
                             heads_major=heads_major)
        return o, (q, k, v, g, b, *saved)

    def bwd(res, do):
        return _backward(*res, do, *_trace_time_choices(),
                         heads_major=heads_major)

    scan.defvjp(fwd, bwd)
    scan.__doc__ = doc
    return scan


kda_scan = _scan(False, """``chunked_kda`` at chunk 64, block 8 for inputs
``supported`` takes, ``time`` a multiple of 64: o (batch, time, heads, V) in
float32.""")
kda_scan_heads_major = _scan(True, """``kda_scan`` for q, k, v, g that arrive
as the kernels read them, (batch, heads, time, K) (``kda_inputs`` writes them
so), with b (batch, time, heads): the same o (batch, time, heads, V); dq, dk,
dv, dg leave heads-major too. No transpose of the four runs, in or out.""")

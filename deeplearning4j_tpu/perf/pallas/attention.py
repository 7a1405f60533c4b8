"""Pallas TPU kernels for blocked causal attention with q/k heads and v
heads of different widths (latent attention) or alike (gated
grouped-query attention, its k and v repeated to the query heads).

The boundary is ``blocked_causal_attention`` (nn/conf/attention.py): causal
softmax(q k^T / sqrt(d_q)) v in tiles, online softmax forward, the
probabilities made again from the saved log-sum-exp backward. In plain
``jax.numpy`` a tile pair is five products with elementwise passes between
them, which XLA does not fuse into one another: a pair's scores,
probabilities and their cotangents (32 heads x 512 x 512 in float32 and in
the compute type, 100 MB at the Kimi Linear's shape) cross HBM between the
fusions, 136 pairs a pass. Here a pair's tiles are made and used in VMEM:
the kernels read q, k, v (and dO, the log-sum-exp, delta) and write O (and
dq, dk, dv), nothing else.

What is computed is ``blocked_causal_attention``'s algorithm at its
precision: scores from the operands as they arrive, scaled in float32;
the running maximum, the running sum, the log-sum-exp and every
accumulator in float32; ``p`` cast to ``v``'s type and ``ds`` to ``q``'s
for the MXU; key tiles after the query tile never visited, the diagonal
tile masked with -inf. What differs is the order of the float32 sums: the
key tiles are folded in from the first to the diagonal one (the
``jax.numpy`` form starts with the diagonal), and a tile here is 512 steps
(or 256, or 128: the largest that divides the padded length) whatever the
caller's ``block``.

Two kernels behind one ``custom_vjp``. Each runs a grid (batch, head
group, tile pair): the pairs of one causal triangle are listed on the host
(scalar-prefetched index tables), so no grid step is spent on a pair the
mask would skip, and consecutive steps that share a tile do not fetch it
again. A grid step takes several heads (its fixed cost is a third of one
head's pair), unrolled, so one head's products overlap the next one's
exponentials.

* ``mla_attend_fwd``: pairs by query tile; running maximum, sum and
  accumulator in scratch; writes O and (for the backward pass) the
  log-sum-exp as a row over the queries.
* ``mla_attend_bwd``: pairs by key tile, scores TRANSPOSED (key rows,
  query lanes): ``p^T dO`` and ``ds^T q`` are then plain products, the
  log-sum-exp and delta are rows that spread over sublanes, and dk, dv of
  the key tile accumulate in scratch. dq needs ``ds`` itself: the one
  (tile, tile) transpose a pair pays (the XLU has room: 14% of the
  schedule's bundles), and its sums run over key tiles, so the head
  group's whole dq stays in a float32 scratch (time x d_q) until the
  group's last pair. Five products a pair, each made once; the usual two
  backward kernels make the scores and ``dp`` twice (seven) and measured
  17.0 ms against 12.7 at the Kimi Linear's shape (PERF.md §6, PR 29).

With a ``window`` w (query t sees key u where t - w < u <= t) the tables
list the BAND of tile pairs that hold a kept position and nothing else: a
query tile's diagonal tile and the ``ceil((w - 1) / tile)`` before it. The
kernels then read where a query tile's pairs start and where a key tile's
end from the tables' neighbouring entries, not from ``j == 0`` /
``i == n - 1``; the forward folds the DIAGONAL tile first (every row has
its own key there, so the running maximum is finite before a tile in
which a row sees nothing: ``exp(-inf - -inf)`` never arises); the band's
far edge (``u > t - w``) is masked in the pairs that cross it, traced
apart from the pairs that need no mask, as the diagonal is. dq's scratch
stays the whole time axis. ``window=None`` is the triangle: its tables,
grid and kernel bodies are what they were.

Under a layer's rematerialisation (``apply_layer``, ``remat=``) the forward
kernel's two outputs carry the ``checkpoint_name``s of ``KEPT``, as the
kernel wrote them: O heads-major (batch, heads, time, d_v) in ``v``'s type
(its transpose and the cut of the padding fuse into whoever reads them) and
the log-sum-exp (batch, heads, 1, time) in float32. They are the two
residuals of the backward rule that only the forward kernel can make again.
A layer type that declares the names (``remat_keeps``:
``MultiHeadLatentAttention``) runs ``mla_attend_fwd`` once a layer and step,
the backward pass reading what the first pass wrote, for ``kept_bytes`` a
layer (260 bytes a token and head of 128 in bfloat16: 68 MB at 8192 tokens
and 32 heads). q, k, v are the rule's other three residuals: the kernels'
wrapper does not name them (``RotaryAttention`` and ``GatedAttention`` share
it), the latent layer does, where it makes them
(``nn/conf/attention.py::OPERANDS_KEPT``, PR 45), and declares those names
too, so its backward pass reads the operands the first pass handed to
``mla_attend_fwd`` and makes none of ``W_qb`` / ``W_q``, ``W_kvb``, the
rotation, the concatenations or the transposes again; ``W_qa x``, ``q_norm``,
``W_kva x`` and ``kv_norm`` it still makes again (the up-projections' weight
gradients and the norms' backward passes read them). That is 1,024 bytes
more a token and head of 192 / 128 in bfloat16, 1,284 in all: 268 MB a
layer at 8192 tokens and 32 heads as values, 335 MB on the chip, which
holds q's and k's 192 lanes as 256. A type that declares nothing
(``RotaryAttention``, ``GatedAttention``) holds no policy that knows the
names: the ``name`` equations lower to their operands and its compiled
program is what it was, two forward kernels a layer (its lowered text too,
but for the numbers MLIR's symbol table gives private functions: an
equation's out-of-line lowering takes one even when it is inlined away).
``remat="nothing_saveable"`` keeps nothing for any type.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.perf import pallas as _pk

__all__ = ["supported", "blocked_attention", "KEPT", "kept_bytes"]

# the forward pass's outputs by their ``checkpoint_name``: O (batch, heads,
# time, d_v) and the log-sum-exp the backward pass makes the probabilities
# from; ``blocked_causal_attention``'s ``jax.numpy`` execution gives its own
# the same names
KEPT = ("blocked_attention.o", "blocked_attention.lse")

_F32 = jnp.float32
_LANES = 128
_NN = (((1,), (0,)), ((), ()))        # x @ y
_NT = (((1,), (1,)), ((), ()))        # x @ y^T
_VMEM_LIMIT = 100 * 1024 * 1024
# Chosen on the chip at (1, 32, 8192, 192 | 128) bfloat16 by the layer's
# time (PERF.md §6, PR 29). Square tiles, the largest that divides the
# padded length: 512 (1024 reads 4 ms a layer slower, the diagonal tiles'
# masked halves; 256 6 ms). Heads a grid step, forward: 8 (1 / 2 / 4 / 8:
# 6.2 / 5.7 / 5.3 / 5.1 ms; 16 no better). Backward: 4 (1 / 2 / 4: 13.8 /
# 13.1 / 12.7 ms), fewer where the group's dq would not fit _DQ_VMEM.
_TILES = (512, 256, 128)
_HEADS_A_STEP = 8
_HEADS_A_STEP_BWD = 4
# a head group's dq in VMEM: the float32 scratch and the output's two
# buffers, (4 + 2 x itemsize) bytes an element at whole lanes
_DQ_VMEM = 72 * 1024 * 1024


def _tile(t: int):
    return next((c for c in _TILES if t % c == 0), None)


def _dq_bytes_a_head(t: int, dq: int, dtype) -> int:
    return t * -(-dq // _LANES) * _LANES * (4 + 2 * jnp.dtype(dtype).itemsize)


def supported(q, k, v, block: int, window=None) -> bool:
    """Shapes the kernels take: q, k (batch, heads, time, d_q) and v
    (batch, heads, time, d_v) with the SAME head count, any number of
    heads (grouped-query heads reach the kernels with k and v repeated
    over their group by the caller: ``GatedAttention``), alike in
    bfloat16 or float32, ``time``
    (padded by the caller) more than one ``block`` and a multiple of 128,
    head widths multiples of 64 up to 256, one head's dq within the
    backward kernel's VMEM (32768 steps of 192 in bfloat16); a ``window``
    of at least one key (None: the causal triangle); on a TPU backend or
    in interpret mode."""
    if window is not None and window < 1:
        return False
    if q.ndim != 4 or q.shape != k.shape or v.shape[:3] != q.shape[:3]:
        return False
    if 0 in q.shape or 0 in v.shape:
        return False
    t = q.shape[2]
    if t <= block or _tile(t) is None:
        return False
    if any(d % 64 or d > 256 for d in (q.shape[-1], v.shape[-1])):
        return False
    if not (q.dtype == k.dtype == v.dtype
            and q.dtype in (jnp.bfloat16, jnp.float32)):
        return False
    if _dq_bytes_a_head(t, q.shape[-1], q.dtype) > _DQ_VMEM:
        return False
    return _pk.interpret() or jax.default_backend() == "tpu"


def kept_bytes(time: int, heads: int, v_dim: int, block: int, dtype) -> int:
    """Bytes of ``KEPT`` for one sequence of ``time`` steps: O (heads, time,
    d_v) in ``dtype`` and the log-sum-exp (heads, time) in float32, at the
    length ``blocked_causal_attention`` pads to (whole tiles of ``block``);
    the same in both executions."""
    padded = time + (-time) % min(block, time)
    return heads * padded * (v_dim * jnp.dtype(dtype).itemsize + 4)


def _heads_a_step(h: int, most: int) -> int:
    return max(d for d in range(1, most + 1) if h % d == 0)


def _band(window, tile: int):
    """(key tiles before the diagonal one that hold a kept position, the
    tile distance from which a pair crosses the window's far edge) for a
    window of ``window`` keys in tiles of ``tile``; None for the
    triangle."""
    if window is None:
        return None
    return -((1 - window) // tile), window // tile


def _pairs(n: int, by_query: bool, window_tiles=None):
    """The (query tile, key tile) pairs of a causal triangle of ``n`` x
    ``n`` tiles as two int32 tables, grouped by query tile (its key tiles
    from the first to the diagonal) or by key tile (its query tiles from
    the diagonal to the last). With ``window_tiles`` the band alone: a
    query tile's diagonal tile FIRST, then the ``window_tiles`` before it
    from the oldest on; a key tile's query tiles from the diagonal to the
    ``window_tiles``-th after it."""
    if window_tiles is not None:
        if by_query:
            pairs = [(i, j) for i in range(n) for j in
                     [i, *range(max(i - window_tiles, 0), i)]]
        else:
            pairs = [(i, j) for j in range(n)
                     for i in range(j, min(j + window_tiles, n - 1) + 1)]
    elif by_query:
        pairs = [(i, j) for i in range(n) for j in range(i + 1)]
    else:
        pairs = [(i, j) for j in range(n) for i in range(j, n)]
    return (jnp.asarray([i for i, _ in pairs], jnp.int32),
            jnp.asarray([j for _, j in pairs], jnp.int32))


def _spread(col, width: int):
    """A (rows, 128) array whose lanes are alike, at ``width`` lanes."""
    reps, rem = divmod(width, _LANES)
    if rem:
        return jnp.tile(col, (1, reps + 1))[:, :width]
    return col if reps == 1 else jnp.tile(col, (1, reps))


def _scores(x, y, scale, diagonal: bool, queries_in_rows: bool, far=None):
    """x y^T * scale in float32; on a diagonal tile -inf where the key lies
    after the query; with ``far`` (the pair's tile distance x tile less
    the window, a scalar) -inf where the key lies ``window`` or more
    before the query: kept is key - query > ``far`` inside the pair."""
    s = lax.dot_general(x, y, _NT, preferred_element_type=_F32) * scale
    if not diagonal and far is None:
        return s
    rows, cols = (lax.broadcasted_iota(jnp.int32, s.shape, d) for d in (0, 1))
    query, key = (rows, cols) if queries_in_rows else (cols, rows)
    keep = key <= query if diagonal else None
    if far is not None:
        inside = key - query > far
        keep = inside if keep is None else keep & inside
    return jnp.where(keep, s, -jnp.inf)


def _on_and_off_the_diagonal(pl, i, j, pair):
    """Trace ``pair(diagonal)`` twice, each under its own condition: only
    the diagonal tile pays for the mask."""
    pl.when(i == j)(functools.partial(pair, True))
    pl.when(i != j)(functools.partial(pair, False))


def _by_mask(pl, i, j, band, tile: int, window: int, pair):
    """``_on_and_off_the_diagonal`` for a band: ``pair(diagonal, far)``
    traced once a kind of mask that the band holds, each under its own
    condition, so that only the pairs across the window's far edge pay for
    its mask (``far`` as ``_scores`` takes it, None elsewhere)."""
    if band is None:
        return _on_and_off_the_diagonal(pl, i, j, pair)
    back, far_from = band
    apart = i - j
    edge = apart * tile - window
    if far_from == 0:            # a window under one tile: every pair
        pl.when(apart == 0)(lambda: pair(True, edge))
        if back:
            pl.when(apart != 0)(lambda: pair(False, edge))
        return None
    pl.when(apart == 0)(lambda: pair(True, None))
    if far_from > 1:
        pl.when((apart > 0) & (apart < far_from))(lambda: pair(False, None))
    if back >= far_from:
        pl.when(apart >= far_from)(lambda: pair(False, edge))
    return None


# ------------------------------------------------------------------ kernels
def _last_of_its_group(pl, table_ref, step, own):
    """Whether the pair at ``step`` is the last of its query tile (key
    tile): the tables' next entry names another, or there is none."""
    last = pl.num_programs(2) - 1
    return (step == last) | (table_ref[jnp.minimum(step + 1, last)] != own)


def _fwd_kernel(hb, scale, save, band, window, qi_ref, kj_ref, q_ref, k_ref,
                v_ref, o_ref, *rest):
    from jax.experimental import pallas as pl
    lse_ref = rest[0] if save else None
    m_ref, l_ref, acc_ref = rest[-3:]
    step = pl.program_id(2)
    i, j = qi_ref[step], kj_ref[step]

    # the triangle starts a query tile at key tile 0, a band at the
    # diagonal tile (listed first)
    @pl.when(j == 0 if band is None else j == i)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, _F32)
        l_ref[...] = jnp.zeros(l_ref.shape, _F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def pair(diagonal, far=None):
        # the first key tile holds key 0, which every query sees (a
        # band's first is the diagonal one, where every query sees its own
        # key): the running maximum is finite from the first fold on
        for h in range(hb):
            v = v_ref[h]
            s = _scores(q_ref[h], k_ref[h], scale, diagonal, True, far)
            m_prev = m_ref[h]
            m_next = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - _spread(m_next, s.shape[1]))
            fix = jnp.exp(m_prev - m_next)
            l_ref[h] = l_ref[h] * fix + jnp.sum(p, -1, keepdims=True)
            m_ref[h] = m_next
            acc_ref[h] = (acc_ref[h] * _spread(fix, v.shape[1])
                          + lax.dot_general(p.astype(v.dtype), v, _NN,
                                            preferred_element_type=_F32))

    _by_mask(pl, i, j, band, q_ref.shape[1], window, pair)

    @pl.when(j == i if band is None
             else _last_of_its_group(pl, qi_ref, step, i))
    def _():
        for h in range(hb):
            l = l_ref[h]
            o_ref[h] = (acc_ref[h] / _spread(l, o_ref.shape[-1])).astype(
                o_ref.dtype)
            if save:    # a row over the queries, as the backward reads it
                lse_ref[h] = (m_ref[h] + jnp.log(l)).T[:1]


def _bwd_kernel(hb, scale, n, band, window, qi_ref, kj_ref, q_ref, k_ref,
                v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dq_ref,
                dk_acc, dv_acc, dq_acc):
    from jax.experimental import pallas as pl
    step = pl.program_id(2)
    i, j = qi_ref[step], kj_ref[step]

    @pl.when(step == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, _F32)

    @pl.when(i == j)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, _F32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, _F32)

    def pair(diagonal, far=None):
        for h in range(hb):
            q, k, v, do = q_ref[h], k_ref[h], v_ref[h], do_ref[h]
            s_t = _scores(k, q, scale, diagonal, False, far)  # (keys, queries)
            p_t = jnp.exp(s_t - lse_ref[h])
            dv_acc[h] = dv_acc[h] + lax.dot_general(
                p_t.astype(do.dtype), do, _NN, preferred_element_type=_F32)
            dp_t = lax.dot_general(v, do, _NT, preferred_element_type=_F32)
            ds_t = p_t * (dp_t - delta_ref[h]) * scale
            dk_acc[h] = dk_acc[h] + lax.dot_general(
                ds_t.astype(q.dtype), q, _NN, preferred_element_type=_F32)
            dq_acc[h, i] = dq_acc[h, i] + lax.dot_general(
                ds_t.T.astype(q.dtype), k, _NN, preferred_element_type=_F32)

    _by_mask(pl, i, j, band, q_ref.shape[1], window, pair)

    @pl.when(i == n - 1 if band is None
             else _last_of_its_group(pl, kj_ref, step, j))
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(step == (n * (n + 1) // 2 if band is None
                      else pl.num_programs(2)) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


# ----------------------------------------------------------------- wrappers
def _call(name, kernel, interpret, grid, in_specs, out_specs, out_shape,
          scratch):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _windows(hb: int, tile: int):
    """BlockSpecs over (batch, heads, time, width) arrays for a grid
    (batch, head group, pair): a head group's tile at the pair's query
    tile or key tile, ``width`` lanes wide; and a row of ``tile`` steps of
    a (batch, heads, 1, time) array at the query tile."""
    from jax.experimental import pallas as pl

    def at_query(width):
        return pl.BlockSpec((None, hb, tile, width),
                            lambda b, g, p, qi, kj: (b, g, qi[p], 0))

    def at_key(width):
        return pl.BlockSpec((None, hb, tile, width),
                            lambda b, g, p, qi, kj: (b, g, kj[p], 0))

    row = pl.BlockSpec((None, hb, 1, tile),
                       lambda b, g, p, qi, kj: (b, g, 0, qi[p]))
    return at_query, at_key, row


@functools.partial(jax.jit, static_argnames=("save", "interpret", "window"))
def _forward(q, k, v, save: bool, interpret: bool, window=None):
    from jax.experimental.pallas import tpu as pltpu
    bsz, h, t, dq = q.shape
    dv = v.shape[-1]
    tile, hb = _tile(t), _heads_a_step(h, _HEADS_A_STEP)
    at_query, at_key, row = _windows(hb, tile)
    band = _band(window, tile)
    qi, kj = _pairs(t // tile, True, band and band[0])
    out_shape = [jax.ShapeDtypeStruct((bsz, h, t, dv), v.dtype)]
    out_specs = [at_query(dv)]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((bsz, h, 1, t), _F32))
        out_specs.append(row)
    outs = _call(
        "mla_attend_fwd",
        functools.partial(_fwd_kernel, hb, 1.0 / (dq ** 0.5), save, band,
                          window),
        interpret, (bsz, h // hb, qi.shape[0]),
        [at_query(dq), at_key(dq), at_key(dv)], out_specs, out_shape,
        [pltpu.VMEM((hb, tile, _LANES), _F32),
         pltpu.VMEM((hb, tile, _LANES), _F32),
         pltpu.VMEM((hb, tile, dv), _F32)])(qi, kj, q, k, v)
    # named as the kernel wrote them (the module's last paragraph)
    return tuple(map(checkpoint_name, outs, KEPT)) if save else outs[0]


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def _backward(q, k, v, out, lse, dout, interpret: bool, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bsz, h, t, dq = q.shape
    dv = v.shape[-1]
    tile = _tile(t)
    n = t // tile
    hb = _heads_a_step(h, min(    # supported(): one head's dq does fit
        _HEADS_A_STEP_BWD, _DQ_VMEM // _dq_bytes_a_head(t, dq, q.dtype)))
    at_query, at_key, row = _windows(hb, tile)
    band = _band(window, tile)
    qi, kj = _pairs(n, False, band and band[0])
    delta = jnp.sum(out.astype(_F32) * dout.astype(_F32), -1)
    whole = pl.BlockSpec((None, hb, n, tile, dq),
                         lambda b, g, p, qi, kj: (b, g, 0, 0, 0))
    d_k, d_v, d_q = _call(
        "mla_attend_bwd",
        functools.partial(_bwd_kernel, hb, 1.0 / (dq ** 0.5), n, band,
                          window), interpret,
        (bsz, h // hb, qi.shape[0]),
        [at_query(dq), at_key(dq), at_key(dv), at_query(dv), row, row],
        [at_key(dq), at_key(dv), whole],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((bsz, h, n, tile, dq), q.dtype)],
        [pltpu.VMEM((hb, tile, dq), _F32), pltpu.VMEM((hb, tile, dv), _F32),
         pltpu.VMEM((hb, n, tile, dq), _F32)])(
             qi, kj, q, k, v, dout, lse, delta[:, :, None, :])
    return d_q.reshape(q.shape), d_k, d_v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def blocked_attention(q, k, v, window=None):
    """``blocked_causal_attention`` for inputs ``supported`` takes, ``time``
    already padded: (batch, heads, time, d_v) in ``v``'s type."""
    return _forward(q, k, v, False, _pk.interpret(), window)


def _blocked_attention_fwd(q, k, v, window):
    out, lse = _forward(q, k, v, True, _pk.interpret(), window)
    return out, (q, k, v, out, lse)


def _blocked_attention_bwd(window, res, dout):
    return _backward(*res, dout, _pk.interpret(), window)


blocked_attention.defvjp(_blocked_attention_fwd, _blocked_attention_bwd)

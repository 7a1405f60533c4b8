"""Pallas TPU kernels for the chunked scan of a Mamba-2 state-space mixer.

The boundary is ``chunked_ssd`` (nn/conf/state_space.py): the recurrence
``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``, ``y_t = S_t^T C_t`` from a
zero state, chunk by chunk. In plain ``jax.numpy`` a chunk's (heads, L, L)
float32 decay factors ``exp(c_r - c_i)`` and their masked product with
``C B^T`` and dt cross HBM several times between XLA's fusions, three
forward passes and one backward a step (PERF.md §5-6, PR 46: 99 us a chunk
where the four products are 7 at the peak). Here a chunk is taken up once,
time-major as the layer's convolution writes it (x (L, heads x P) in the
compute type, B and C (L, N), dt (L, heads) float32), a head's (L, L)
factor is made in VMEM from the running sum, cast, multiplied into the
head's x and never written out; only y (and, for the backward pass, the
state at the chunk's entry) is written back.

What is computed is ``_ssd_chunk``'s algorithm, not a cheaper one: decays,
sums and states float32; every exponent a difference of two running sums;
the four products on operands of x's type with float32 accumulation (a
float32 operand rounded to bfloat16 as XLA's default does on a TPU, unless
the ambient ``jax.default_matmul_precision`` asks for float32 or the kernel
is interpreted); the running sum a product with the triangle of ones at
``Precision.HIGHEST``. Two orders differ, neither in what is rounded how:

* a (128, 128) block of the factor above the diagonal is all zero and is
  skipped, exponentials and products both;
* heads narrower than a lane tile (P = 64) go two at a time: a pair's x is
  one (L, 128) window and each head's product reads it with the other
  head's lanes zeroed, a full-width MXU pass either way, so no 64-lane
  slice is ever cut.

Grid (batch, chunk), the chunk axis sequential; the states (N, heads x P)
float32 live in VMEM scratch and are written to a (chunks, N, heads x P)
output at each chunk's entry when the call is differentiated. One group
only: with one shared B and C the carried product ``C S_0`` and the state's
update ``B^T (w o X)`` read the same (L, N) operand for every head. More
groups stay with the ``jax.numpy`` form (``supported``).

Backward: the same grid with the chunk index reversed and dS carried in the
scratch. One kernel makes the chunk's factors again in VMEM (this is the
chunk's own rematerialisation: ``chunked_ssd``'s ``jax.checkpoint`` body ran
a third forward for it) and emits dx, dB, dC (summed over the heads) and,
a head, three vectors over the chunk's steps from which ``_finish`` (plain
``jax.numpy`` over (time, heads) arrays) makes ddt and dA: the row sums and
the column sums of the factor's cotangent and the state terms' sums over a
head's lanes. Each piece is written by hand and held to ``jax.vjp`` of the
``jax.numpy`` form in tests/test_zz_pallas.py.

Under a layer's rematerialisation the forward kernel runs twice a step (the
step, the layer's second forward) and the backward once; the entry states
(4 N heads P bytes a chunk) are residuals of the custom-VJP, alive for one
layer's backward, and carry no ``checkpoint_name`` (PERF.md §7, PR 47, sizes
keeping them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.perf import pallas as _pk
from deeplearning4j_tpu.perf.pallas.kda import (_F32, _HI, _NN, _NT, _TN,
                                                _dot_hi, _head_column, _iota,
                                                _trace_time_choices)

__all__ = ["supported", "ssd_scan"]

LANES = 128                    # a lane tile, and the factor's block
_VMEM_LIMIT = 64 * 1024 * 1024
_NEG = -jnp.inf


def _heads_a_tile(head_dim: int) -> int:
    """Heads in one 128-lane window: 2 of 64, 1 of 128; 0 = not taken."""
    return {64: 2, 128: 1}.get(head_dim, 0)


def _vmem_bytes(chunk: int, heads: int, head_dim: int, state: int,
                itemsize: int) -> int:
    """The backward kernel's windows (the larger of the two), each twice
    for the pipeline, its scratch and a chunk's float32 values."""
    wide = chunk * heads * head_dim
    windows = wide * (2 * itemsize + 4) + state * heads * head_dim * 4
    small = chunk * (4 * state + 6 * heads + 3 * chunk) * 4
    return 2 * windows + state * heads * head_dim * 4 + small + (8 << 20)


def supported(x, dt, a_rate, bm, cm, chunk: int) -> bool:
    """Shapes the kernels take: x (batch, time, heads, P) with P 64 or 128
    and heads x P whole lane tiles, dt (batch, time, heads), A (heads,),
    ONE group of B and C (batch, time, 1, N), N and ``chunk`` multiples of
    128, ``time`` a multiple of ``chunk``; x, B, C alike in bfloat16 or
    float32; the windows inside the VMEM limit; on a TPU backend or in
    interpret mode. Anything else is ``chunked_ssd``'s ``jax.numpy`` form:
    more groups (each would want its own state products), a ragged length
    (the caller's padding), an odd head width."""
    if x.ndim != 4 or bm.ndim != 4 or bm.shape != cm.shape or 0 in x.shape:
        return False
    bsz, t, h, p = x.shape
    per = _heads_a_tile(p)
    if not per or h % per or dt.shape != (bsz, t, h) or a_rate.shape != (h,):
        return False
    n = bm.shape[3]
    if bm.shape[:3] != (bsz, t, 1) or n % LANES or n == 0:
        return False
    if chunk < LANES or chunk % LANES or t % chunk:
        return False
    if not (x.dtype == bm.dtype == cm.dtype
            and x.dtype in (jnp.bfloat16, jnp.float32)):
        return False
    if _vmem_bytes(chunk, h, p, n, x.dtype.itemsize) > _VMEM_LIMIT:
        return False
    return _pk.interpret() or jax.default_backend() == "tpu"


# ------------------------------------------------------------ chunk algebra
# Values of one chunk: dt, c, w (L, heads) float32; B, C (L, N) and a tile's
# x (L, 128) in the compute type; the state's tile (N, 128) float32.
def _dot(x, y, low, exact: bool, dims=_NN):
    """A product of ``_ssd_chunk``: operands in x's type ``low``, float32
    accumulation; float32 operands in one bfloat16 pass unless ``exact``."""
    if low == jnp.float32 and not exact:
        low = jnp.bfloat16
    precision = _HI if low == jnp.float32 else None
    return lax.dot_general(x.astype(low), y.astype(low), dims,
                           precision=precision, preferred_element_type=_F32)


def _chunk_sums(dt, a_row):
    """From dt (L, heads) and A (1, heads): the running sum c of dt A (a
    product with the triangle of ones, float32), what a step adds to the
    state decayed to the chunk's end, w = dt exp(c_L - c), and c and dt
    transposed (heads, L) (a product with the identity at ``HIGHEST``:
    exact), so that a head's row is a sublane slice."""
    length, heads = dt.shape
    tril = (_iota((length, length), 0) >= _iota((length, length), 1))
    c = _dot_hi(tril.astype(_F32), dt * a_row)
    w = dt * jnp.exp(c[length - 1:length, :] - c)
    eye = (_iota((heads, heads), 0) == _iota((heads, heads), 1)).astype(_F32)
    return c, w, _dot_hi(eye, c, _NT), _dot_hi(eye, dt, _NT)


def _set_columns(ref, rows, heads, cols):
    """Columns ``heads`` of rows ``rows`` of a (L, heads) window := ``cols``
    (128, 1) each: one read and one write for the tile's heads."""
    tile = ref[0, rows]
    lane = _iota(tile.shape, 1)
    for k in range(len(heads)):
        tile = jnp.where(lane == heads[k], cols[k], tile)
    ref[0, rows] = tile


def _half(k: int, per: int):
    """(1, 128) mask of head ``k``'s lanes in a tile of ``per`` heads; None
    for a head that fills the tile."""
    if per == 1:
        return None
    return _iota((1, LANES), 1) // (LANES // per) == k


def _only(mask, value):
    return value if mask is None else jnp.where(mask, value, 0.0)


def _spread(masks, values):
    """A tile's heads' values (rows, 1), each over its head's lanes:
    (rows, 128)."""
    out = jnp.broadcast_to(values[0], (values[0].shape[0], LANES))
    for k in range(1, len(values)):
        out = jnp.where(masks[k], values[k], out)
    return out


def _decay(c_col, c_row, s: int):
    """Rows [128 s, 128 s + 128) of a head's decay factor exp(c_r - c_i),
    i <= r, as far as the strip's diagonal block (what lies right of it is
    zero and is never made): (128, 128 (s + 1)) float32; only the diagonal
    block is masked."""
    keep = _iota((LANES, LANES), 0) >= _iota((LANES, LANES), 1)
    diff = c_col - c_row
    diag = jnp.where(keep, diff[:, s * LANES:], _NEG)
    if s:
        diag = jnp.concatenate([diff[:, :s * LANES], diag], 1)
    return jnp.exp(diag)


def _padded(rows, length: int, axis: int = 0):
    """``rows`` with zeros appended along ``axis`` up to ``length``."""
    short = length - rows.shape[axis]
    if not short:
        return rows
    shape = list(rows.shape)
    shape[axis] = short
    return jnp.concatenate([rows, jnp.zeros(shape, rows.dtype)], axis)


# ------------------------------------------------------------------ kernels
# A grid step is a chunk; inside it a loop over the lane tiles of heads x P
# (one head of 128 or two of 64), and inside that, straight-line, the
# chunk's strips of 128 rows with the tile's heads innermost: a strip's
# values are (128, 128) or (128, width) float32, a quarter of the register
# file, where a whole chunk's (256, 128) pieces of x, dy, C S_0 and B dS
# held at once were spilled and filled all through the loop.
def _fwd_kernel(per, exact, save, x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref,
                *rest):
    from jax.experimental import pallas as pl
    st_ref, ct_ref, dtt_ref = rest[-3:]

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros(st_ref.shape, _F32)

    if save:
        rest[0][0, 0] = st_ref[...]
    low = x_ref.dtype
    dot = functools.partial(_dot, low=low, exact=exact)
    bm, cm = b_ref[0], c_ref[0]
    c, w, ct_ref[...], dtt_ref[...] = _chunk_sums(dt_ref[0], a_ref[...])
    length = c.shape[0]
    scores = dot(cm, bm, dims=_NT)                   # C B^T, (L, L)
    masks = [_half(k, per) for k in range(per)]

    def tile(j, carry):
        lanes = pl.ds(pl.multiple_of(j * LANES, LANES), LANES)
        heads = [j * per + k for k in range(per)]
        c_cols = [_head_column(c, h) for h in heads]
        w_cols = [_head_column(w, h) for h in heads]
        state = st_ref[:, lanes]
        keep = _spread(masks, [jnp.exp(col[length - 1:]) for col in c_cols])
        added = jnp.zeros(state.shape, _F32)
        for s in range(length // LANES):
            rows, width = slice(s * LANES, (s + 1) * LANES), (s + 1) * LANES
            grow = _spread(masks, [jnp.exp(col[rows]) for col in c_cols])
            y = grow * dot(cm[rows], state)
            x_before = x_ref[0, :width, lanes].astype(_F32)
            for k in range(per):
                row = pl.ds(heads[k], 1)
                decay = _decay(c_cols[k][rows], ct_ref[row, :][:, :width], s)
                mixed = (decay * scores[rows, :width]
                         * dtt_ref[row, :][:, :width])
                y = y + dot(mixed, _only(masks[k], x_before))
            y_ref[0, rows, lanes] = y
            add = _spread(masks, [col[rows] for col in w_cols])
            added = added + dot(bm[rows], x_before[s * LANES:] * add,
                                dims=_TN)
        st_ref[:, lanes] = keep * state + added
        return carry

    lax.fori_loop(0, x_ref.shape[2] // LANES, tile, 0)


def _bwd_kernel(per, exact, x_ref, dt_ref, a_ref, b_ref, c_ref, s_ref,
                dy_ref, dx_ref, db_ref, dc_ref, rows_ref, adds_ref, cols_ref,
                ends_ref, dst_ref, ct_ref, dtt_ref, ds_ref):
    """Outputs beside dx, dB, dC, a head h and a step l: ``rows`` (L, heads)
    what the running sum c_l gathers as a ROW of the factor and through
    exp(c_l) of the carried term; ``adds`` (L, heads) the cotangent of
    w_l = dt_l exp(c_L - c_l); ``cols`` (heads, L) the cotangent of dt_l as
    a COLUMN of the factor (times -dt_l: c_l's); ``ends`` (1, heads x P)
    the sum over N of dS' o S_0 (the cotangent of exp(c_L), a head's
    lanes summed outside)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_ref[...] = jnp.zeros(dst_ref.shape, _F32)

    low = x_ref.dtype
    dot = functools.partial(_dot, low=low, exact=exact)
    bm, cm = b_ref[0], c_ref[0]
    c, w, ct_ref[...], dtt_ref[...] = _chunk_sums(dt_ref[0], a_ref[...])
    length = c.shape[0]
    strips = length // LANES
    scores = dot(cm, bm, dims=_NT)
    ds_ref[...] = jnp.zeros(ds_ref.shape, _F32)      # d scores, over heads
    db_ref[0] = jnp.zeros(db_ref.shape[1:], _F32)
    dc_ref[0] = jnp.zeros(dc_ref.shape[1:], _F32)
    masks = [_half(k, per) for k in range(per)]

    def tile(j, carry):
        lanes = pl.ds(pl.multiple_of(j * LANES, LANES), LANES)
        heads = [j * per + k for k in range(per)]
        c_cols = [_head_column(c, h) for h in heads]
        w_cols = [_head_column(w, h) for h in heads]
        state, dstate = s_ref[0, 0, :, lanes], dst_ref[:, lanes]
        ends_ref[0, 0, :, lanes] = jnp.sum(dstate * state, axis=0,
                                           keepdims=True)
        keep = _spread(masks, [jnp.exp(col[length - 1:]) for col in c_cols])
        d_state = keep * dstate
        col_sums = [jnp.zeros((1, length), _F32) for _ in heads]
        dx_below = [None] * strips                   # dx's blocks, so far
        # from the last strip up: a strip adds to dx's blocks at and before
        # it, so block s is whole once strip s is through
        for s in range(strips - 1, -1, -1):
            rows, width = slice(s * LANES, (s + 1) * LANES), (s + 1) * LANES
            here = slice(0, width)
            x_before = x_ref[0, here, lanes]
            xf = x_before[s * LANES:].astype(_F32)
            dy = dy_ref[0, rows, lanes]
            grows = [jnp.exp(col[rows]) for col in c_cols]
            add = _spread(masks, [col[rows] for col in w_cols])
            carried = dot(cm[rows], state)
            d_added = dot(bm[rows], dstate)          # d (w o x)
            d_carried = dy * _spread(masks, grows)
            dc_ref[0, rows] += dot(d_carried, state, dims=_NT)
            db_ref[0, rows] += dot(xf * add, dstate, dims=_NT)
            d_state = d_state + dot(cm[rows], d_carried, dims=_TN)
            via_sum, via_w = dy * carried, d_added * xf
            dx = d_added * add
            row_sums, add_sums = [], []
            for k in range(per):
                mask, row = masks[k], pl.ds(heads[k], 1)
                dy_h = _only(mask, dy)           # the other head's drop out
                dt_row = dtt_ref[row, :][:, here]
                decay = _decay(c_cols[k][rows], ct_ref[row, :][:, here], s)
                d_mixed = dot(dy_h, x_before, dims=_NT)
                masked = decay * scores[rows, here]
                mixed = masked * dt_row
                ds_ref[rows, here] += d_mixed * decay * dt_row
                below = dot(mixed, dy_h, dims=_TN)   # (width, 128)
                for b in range(s + 1):
                    piece = below[b * LANES:(b + 1) * LANES]
                    dx_below[b] = (piece if dx_below[b] is None
                                   else dx_below[b] + piece)
                row_sums.append(
                    jnp.sum(d_mixed * mixed, axis=1, keepdims=True)
                    + grows[k] * jnp.sum(_only(mask, via_sum), axis=1,
                                         keepdims=True))
                add_sums.append(jnp.sum(_only(mask, via_w), axis=1,
                                        keepdims=True))
                col_sums[k] = col_sums[k] + _padded(
                    jnp.sum(d_mixed * masked, axis=0, keepdims=True),
                    length, 1)
            _set_columns(rows_ref, rows, heads, row_sums)
            _set_columns(adds_ref, rows, heads, add_sums)
            dx_ref[0, rows, lanes] = (dx + dx_below[s]).astype(dx_ref.dtype)
        for k in range(per):
            cols_ref[0, pl.ds(heads[k], 1), :] = col_sums[k]
        dst_ref[:, lanes] = d_state
        return carry

    lax.fori_loop(0, x_ref.shape[2] // LANES, tile, 0)
    d_scores = ds_ref[...]
    dc_ref[0] += dot(d_scores, bm)
    db_ref[0] += dot(d_scores, cm, dims=_TN)


def _call(name, kernel, interpret, grid, in_specs, out_specs, out_shape,
          scratch):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _specs(bsz, t, h, p, n, chunk, reverse: bool):
    """Windows for grid (batch, chunk): time-major (L, width) pieces of the
    (batch, time, width) arrays, the heads-major (heads, L) piece of a
    (batch, heads, time) array, A whole, a chunk's (N, heads x P) state and
    its (1, heads x P) row."""
    from jax.experimental import pallas as pl
    count = t // chunk

    def at(c):
        return count - 1 - c if reverse else c

    def rows(width):
        return pl.BlockSpec((1, chunk, width), lambda i, c: (i, at(c), 0))

    by_head = pl.BlockSpec((1, h, chunk), lambda i, c: (i, 0, at(c)))
    rate = pl.BlockSpec((1, h), lambda i, c: (0, 0))
    state = pl.BlockSpec((1, 1, n, h * p), lambda i, c: (i, at(c), 0, 0))
    end = pl.BlockSpec((1, 1, 1, h * p), lambda i, c: (i, at(c), 0, 0))
    return (bsz, count), rows, by_head, rate, state, end


@functools.partial(jax.jit, static_argnames=("chunk", "save", "exact",
                                             "interpret"))
def _forward(x, dt, a_rate, bm, cm, chunk: int, save: bool, exact: bool,
             interpret: bool):
    """x (batch, time, heads x P), dt (batch, time, heads) float32, A
    (1, heads) float32, B and C (batch, time, N): y like x in float32 and,
    where ``save``, the chunks' entry states (batch, chunks, N, heads x P)."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, t, h = dt.shape
    p, n = x.shape[2] // h, bm.shape[2]
    grid, rows, _, rate, state, _ = _specs(bsz, t, h, p, n, chunk, False)
    out_shape = [jax.ShapeDtypeStruct((bsz, t, h * p), _F32)]
    out_specs = [rows(h * p)]
    if save:
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, t // chunk, n, h * p), _F32))
        out_specs.append(state)
    outs = _call(
        "ssd_scan_fwd",
        functools.partial(_fwd_kernel, _heads_a_tile(p), exact, save),
        interpret, grid, [rows(h * p), rows(h), rate, rows(n), rows(n)],
        out_specs, out_shape,
        [pltpu.VMEM((n, h * p), _F32), pltpu.VMEM((h, chunk), _F32),
         pltpu.VMEM((h, chunk), _F32)])(x, dt, a_rate, bm, cm)
    return tuple(outs) if save else outs[0]


def _finish(dt, a_rate, chunk: int, rows, adds, cols, ends):
    """ddt (batch, time, heads) and dA (1, heads) from the backward kernel's
    vectors (``_bwd_kernel``): through c = cumsum(dt A) inside a chunk,
    w = dt exp(c_L - c) and exp(c_L)."""
    bsz, t, h = dt.shape
    count = t // chunk
    shape = (bsz, count, chunk, h)
    dt = dt.reshape(shape)
    rate = a_rate[0]
    tril = jnp.tril(jnp.ones((chunk, chunk), _F32))
    c = jnp.einsum("bkih,li->bklh", dt * rate, tril, precision=_HI)
    tail = jnp.exp(c[:, :, -1:] - c)
    w = dt * tail
    rows, adds = rows.reshape(shape), adds.reshape(shape)
    cols = jnp.swapaxes(cols, 1, 2).reshape(shape)
    d_end = ends.reshape(bsz, count, 1, h, -1).sum(-1)
    dc = rows - cols * dt - adds * w
    dc_end = (jnp.sum(adds * w, axis=2, keepdims=True)
              + d_end * jnp.exp(c[:, :, -1:]))
    dc = dc + dc_end * (jnp.arange(chunk) == chunk - 1)[:, None]
    # the running sum's transpose: every later step of the chunk
    d_rate = jnp.einsum("bklh,li->bkih", dc, tril, precision=_HI)
    ddt = cols + adds * tail + d_rate * rate
    return ddt.reshape(bsz, t, h), jnp.sum(d_rate * dt, axis=(0, 1, 2))[None]


@functools.partial(jax.jit, static_argnames=("chunk", "exact", "interpret"))
def _backward(x, dt, a_rate, bm, cm, states, dy, chunk: int, exact: bool,
              interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    bsz, t, h = dt.shape
    p, n = x.shape[2] // h, bm.shape[2]
    grid, rows, by_head, rate, state, end = _specs(bsz, t, h, p, n, chunk,
                                                   True)
    small = jax.ShapeDtypeStruct((bsz, t, h), _F32)
    group = jax.ShapeDtypeStruct((bsz, t, n), _F32)
    out_shape = [jax.ShapeDtypeStruct((bsz, t, h * p), x.dtype), group,
                 group, small, small,
                 jax.ShapeDtypeStruct((bsz, h, t), _F32),
                 jax.ShapeDtypeStruct((bsz, t // chunk, 1, h * p), _F32)]
    dx, db, dc, row_sums, adds, cols, ends = _call(
        "ssd_scan_bwd", functools.partial(_bwd_kernel, _heads_a_tile(p),
                                          exact),
        interpret, grid,
        [rows(h * p), rows(h), rate, rows(n), rows(n), state, rows(h * p)],
        [rows(h * p), rows(n), rows(n), rows(h), rows(h), by_head, end],
        out_shape,
        [pltpu.VMEM((n, h * p), _F32), pltpu.VMEM((h, chunk), _F32),
         pltpu.VMEM((h, chunk), _F32), pltpu.VMEM((chunk, chunk), _F32)])(
             x, dt, a_rate, bm, cm, states, dy)
    ddt, da = _finish(dt, a_rate, chunk, row_sums, adds, cols, ends)
    return dx, ddt, da, db.astype(bm.dtype), dc.astype(cm.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dt, a_rate, bm, cm, chunk: int):
    return _forward(x, dt, a_rate, bm, cm, chunk, False,
                    *_trace_time_choices())


def _scan_fwd(x, dt, a_rate, bm, cm, chunk: int):
    y, states = _forward(x, dt, a_rate, bm, cm, chunk, True,
                         *_trace_time_choices())
    return y, (x, dt, a_rate, bm, cm, states)


def _scan_bwd(chunk: int, res, dy):
    return _backward(*res, dy, chunk, *_trace_time_choices())


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, a_rate, bm, cm, chunk: int):
    """``chunked_ssd`` for inputs ``supported`` takes: y (batch, time,
    heads, P) in float32. The custom-VJP is over the arrays as the kernels
    read them, whole rows of heads x P columns, so that y and its cotangent
    cross it in the layout the kernels write and read."""
    bsz, t, h, p = x.shape
    y = _scan(x.reshape(bsz, t, h * p), dt.astype(_F32),
              a_rate.astype(_F32).reshape(1, h), bm.reshape(bsz, t, -1),
              cm.reshape(bsz, t, -1), chunk)
    return y.reshape(bsz, t, h, p)

"""Pallas TPU kernels for the selective scan of a Mamba-1 mixer.

The boundary is ``chunked_selective_scan`` (nn/conf/state_space.py): the
recurrence with a decay for every (channel, state) pair,

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + (dt_t x_t)[c] B_t[n]
    y_t[c] = sum_n C_t[n] S_t[n, c] + D[c] x_t[c]

from a zero state. There is no matrix product in it: a step is one pass of
the vector units over the (N, channels) state. As ``lax.scan``s (a loop over
chunks round a loop over a chunk's steps, XLA writing the backward pass) a
step of the Phi-4-mini-flash cell spent 152.8 ms in its two layers' scans,
2% of their roofline (PERF.md §5-6, PR 50): autodiff stacks three (64, N,
channels) float32 residuals a chunk, the chunk's forward runs a third time
under its ``jax.checkpoint``, and 190 thousand small operations a step run
under the mixers' backward pass. Here the state of a tile of ``TILE``
channels is a (N, TILE) float32 value that a block's ``BLOCK`` steps carry
in registers (N = 16, TILE = 512: eight registers), the states on the
sublanes and the channels on the lanes, as ``_selective_chunk`` holds them.

What is computed is ``_selective_chunk.step``'s arithmetic, not a cheaper
one: x, B and C cast to float32; ``exp(dt A)`` of a non-positive product a
step, never a quotient of running products; ``decay S + (dt x) B``; the sum
over the states in float32. Only the ORDER of that sum differs (the
sublanes' tree, not a sequence).

Grid (batch, time block, channel tile), all in order, the tile innermost:
a block's B and C are fetched once for its tiles, and the backward kernel's
dB and dC (sums over ALL channels) gather in one output window over the
tiles. x, dt, y and their cotangents cross as (BLOCK, TILE) windows of the
(batch, time, channels) arrays the layer's neighbours write and read: no
transpose. B and C reach the kernels with every value repeated over a lane
tile, (batch, time, N, 128) float32 made by one broadcast in front of the
call (134 MB a layer and pass at 8,192 tokens, written and read once): a
step takes B_t as a (N, 128) register pair for every lane tile of the
state, where a (time, N) window would want a transpose and a lane
broadcast a step. dB and dC leave the same way, (batch, time, N, 128) sums
over the channels' lane TILES whose 128 lanes plain ``jax.numpy`` adds up.

Forward: the states (tiles, N, TILE) live in VMEM scratch between blocks
and are written to a (batch, blocks, N, channels) output at each block's
ENTRY when the call is differentiated; the sum over the states is taken
eight steps at a time (``_rows``: a register of eight steps' sums and one
whole store, where a sum a step is three rotations and additions a register
and a store of one sublane). Backward: the same grid with the blocks
reversed and dS carried in scratch; a block's states and decays are made
again from its entry state into VMEM scratch (this IS the chunk's
rematerialisation: the third forward of XLA's form is gone), then the
adjoint recurrence runs backwards through the block; dA and dD gather in
output windows that stay on the chip for a whole sequence. Each piece is
written by hand and held to ``jax.vjp`` of the ``lax`` form in
tests/test_zz_pallas.py. The variants were ordered by the compiler's
schedule for the described v5e before any chip call (PERF.md §6, PR 51).

Under a layer's rematerialisation the forward kernel runs twice a step (the
step, the layer's second forward) and the backward once; the entry states
(4 N channels bytes a block) are residuals of the custom-VJP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.perf import pallas as _pk

__all__ = ["supported", "selective_scan"]

_F32 = jnp.float32
LANES = 128
BLOCK = 256                    # steps a grid step: the kernels' time block
_TILES = (512, 256, 128)       # channels a grid step: the widest that divides
_UNROLL = 8                    # steps an iteration of the loops below: the
#                                sublanes of a register (``_rows``)
_VMEM_LIMIT = 64 * 1024 * 1024


def _tile(channels: int) -> int:
    """Channels a grid step takes: 0 = not whole lane tiles."""
    return next((w for w in _TILES if channels % w == 0), 0)


def _vmem_bytes(channels: int, state: int, itemsize: int) -> int:
    """The backward kernel's windows (the larger of the two), each twice
    for the pipeline, and its scratch."""
    width = _tile(channels)
    wide = BLOCK * width
    windows = (wide * (2 * itemsize + 3 * 4)          # x, dx; dt, dy, ddt
               + 4 * BLOCK * state * LANES * 4        # B, C, dB, dC
               + 2 * state * width * 4                # A, the entry state
               + (state + 8) * channels * 4)          # dA, dD
    scratch = ((2 * BLOCK + 1) * state * width + state * channels
               + 3 * wide) * 4
    return 2 * windows + scratch + (4 << 20)


def supported(x, dt, a_rate, bm, cm, skip=None) -> bool:
    """Shapes the kernels take: x and dt (batch, time, channels) with the
    channels whole lane tiles of 128 and ``time`` a multiple of ``BLOCK``,
    dt float32; A (channels, N), B and C (batch, time, N) with N a multiple
    of 8 sublanes; D (channels,) or None; x, B, C alike in bfloat16 or
    float32; the windows inside the VMEM limit; on a TPU backend or in
    interpret mode. Anything else is ``chunked_selective_scan``'s ``lax``
    form: a ragged length (the caller's padding), a state of 4, float16."""
    if x.ndim != 3 or bm.ndim != 3 or 0 in x.shape or 0 in bm.shape:
        return False
    bsz, t, c = x.shape
    n = bm.shape[2]
    if not _tile(c) or t % BLOCK or n % 8:
        return False
    if (dt.shape != x.shape or a_rate.shape != (c, n)
            or bm.shape != (bsz, t, n) or cm.shape != bm.shape
            or (skip is not None and skip.shape != (c,))):
        return False
    if not (x.dtype == bm.dtype == cm.dtype and dt.dtype == jnp.float32
            and x.dtype in (jnp.bfloat16, jnp.float32)):
        return False
    if _vmem_bytes(c, n, x.dtype.itemsize) > _VMEM_LIMIT:
        return False
    return _pk.interpret() or jax.default_backend() == "tpu"


# ------------------------------------------------------------------ kernels
# Values of a step: the state, its cotangent and A (N, TILE) float32; a row
# of dt, dt x, dy (1, TILE) read from its window and spread over the
# sublanes; B_t and C_t (N, 128), every lane alike, set side by side for the
# tile's lane tiles.
def _wide(tile, width: int):
    """A (N, 128) value whose lanes are alike, for ``width`` lanes."""
    reps = width // LANES
    return tile if reps == 1 else jnp.concatenate([tile] * reps, axis=1)


def _fold(value):
    """The sum of a (N, width) value's lane tiles: (N, 128)."""
    out = value[:, :LANES]
    for k in range(1, value.shape[1] // LANES):
        out = out + value[:, k * LANES:(k + 1) * LANES]
    return out


def _halve(value):
    """The sum of a (N, width) value's sublane tiles: (8, width)."""
    out = value[:8]
    for k in range(1, value.shape[0] // 8):
        out = out + value[8 * k:8 * k + 8]
    return out


def _rows(parts):
    """Eight (8, width) values, one a step, each still to be summed over
    its sublanes: the (8, width) value whose row k is that sum of part k.
    Three rounds of rotate, add and select over the eight registers (34
    operations a lane tile) where a sum a step is 3 rotations and 3
    additions each (48), and one whole store where those are eight of one
    sublane."""
    from jax.experimental.pallas import tpu as pltpu
    sub = lax.broadcasted_iota(jnp.int32, parts[0].shape, 0)
    even, low_pair, low_half = (sub & 1) == 0, (sub & 2) == 0, sub < 4

    def pair(mask, x, y, shift):
        # rows the mask keeps: x's row + the row ``shift`` below it;
        # the others: y's row + the row ``shift`` above it
        return jnp.where(mask, x + pltpu.roll(x, 8 - shift, 0),
                         y + pltpu.roll(y, shift, 0))

    twos = [pair(even, parts[2 * k], parts[2 * k + 1], 1) for k in range(4)]
    fours = [pair(low_pair, twos[2 * k], twos[2 * k + 1], 2)
             for k in range(2)]
    # a rotation by 4 of 8 is its own inverse: one serves both halves
    return (jnp.where(low_half, fours[0], fours[1])
            + pltpu.roll(jnp.where(low_half, fours[1], fours[0]), 4, 0))


def _fwd_kernel(save, x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
                *rest):
    from jax.experimental import pallas as pl
    st_ref, u_ref = rest[-2:]
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[j] = jnp.zeros(st_ref.shape[1:], _F32)

    if save:
        rest[0][0, 0] = st_ref[j]
    xf = x_ref[0].astype(_F32)
    u_ref[...] = dt_ref[0] * xf
    a = a_ref[...]
    width = a.shape[1]

    def steps(i, s):
        first = pl.multiple_of(i * _UNROLL, _UNROLL)
        read = []
        for k in range(_UNROLL):
            t = first + k
            row = pl.ds(t, 1)
            decay = jnp.exp(dt_ref[0, row, :] * a)
            s = decay * s + u_ref[row, :] * _wide(b_ref[0, t], width)
            read.append(_halve(s * _wide(c_ref[0, t], width)))
        y_ref[0, pl.ds(first, _UNROLL), :] = _rows(read)
        return s

    st_ref[j] = lax.fori_loop(0, BLOCK // _UNROLL, steps, st_ref[j])
    y_ref[0] = y_ref[0] + d_ref[...] * xf


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s_ref, dy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                dst_ref, hist_ref, decay_ref, u_ref, du_ref, dz_ref):
    """Outputs: dx, ddt (BLOCK, TILE) windows; dB, dC (BLOCK, N, 128)
    windows that gather over the block's tiles (their lanes summed
    outside); dA (tiles, N, TILE) and dD (tiles, 8, TILE, its sublanes
    summed outside) windows that stay for a sequence and gather over its
    blocks. Scratch: dS (tiles, N, TILE) between blocks; the block's states
    (BLOCK + 1, N, TILE), entry first, and its decays (BLOCK, N, TILE); dt
    x, and a row a step of ``du = sum_n dS B`` and ``dz = sum_n dS decay S'
    A`` (BLOCK, TILE)."""
    from jax.experimental import pallas as pl
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_ref[j] = jnp.zeros(dst_ref.shape[1:], _F32)
        da_ref[0, j] = jnp.zeros(da_ref.shape[2:], _F32)
        dd_ref[0, j] = jnp.zeros(dd_ref.shape[2:], _F32)

    @pl.when(j == 0)
    def _():
        db_ref[0] = jnp.zeros(db_ref.shape[1:], _F32)
        dc_ref[0] = jnp.zeros(dc_ref.shape[1:], _F32)

    xf = x_ref[0].astype(_F32)
    u_ref[...] = dt_ref[0] * xf
    a = a_ref[...]
    width = a.shape[1]

    # the block's states again, from the state at its entry, and its decays
    # (kept for the steps below: an exponential a step and pair, not two)
    hist_ref[0] = s_ref[0, 0]

    def forward(i, s):
        first = pl.multiple_of(i * _UNROLL, _UNROLL)
        for k in range(_UNROLL):
            t = first + k
            row = pl.ds(t, 1)
            decay = jnp.exp(dt_ref[0, row, :] * a)
            decay_ref[t] = decay
            s = decay * s + u_ref[row, :] * _wide(b_ref[0, t], width)
            hist_ref[t + 1] = s
        return s

    lax.fori_loop(0, BLOCK // _UNROLL, forward, s_ref[0, 0])

    # the adjoint recurrence, last step first: ``ds`` enters a step as the
    # cotangent of S_t from the steps after it
    def backward(i, carry):
        ds, da, s = carry
        first = pl.multiple_of(BLOCK - _UNROLL - i * _UNROLL, _UNROLL)
        du, dz_a = [None] * _UNROLL, [None] * _UNROLL
        for k in range(_UNROLL - 1, -1, -1):
            t = first + k
            row = pl.ds(t, 1)
            dy = dy_ref[0, row, :]
            ds = ds + _wide(c_ref[0, t], width) * dy
            dc_ref[0, t] += _fold(dy * s)
            db_ref[0, t] += _fold(ds * u_ref[row, :])
            du[k] = _halve(ds * _wide(b_ref[0, t], width))
            ds = ds * decay_ref[t]               # what step t - 1 is handed
            s = hist_ref[t]                      # S_{t-1}
            dz = ds * s                          # the cotangent of dt_t A
            dz_a[k] = _halve(dz * a)
            da = da + dz * dt_ref[0, row, :]
        du_ref[pl.ds(first, _UNROLL), :] = _rows(du)
        dz_ref[pl.ds(first, _UNROLL), :] = _rows(dz_a)
        return ds, da, s

    ds, da, _ = lax.fori_loop(
        0, BLOCK // _UNROLL, backward,
        (dst_ref[j], jnp.zeros(a.shape, _F32), hist_ref[BLOCK]))
    dst_ref[j] = ds
    da_ref[0, j] += da
    dy, du = dy_ref[0], du_ref[...]
    dx_ref[0] = (du * dt_ref[0] + d_ref[...] * dy).astype(dx_ref.dtype)
    ddt_ref[0] = dz_ref[...] + du * xf
    dd_ref[0, j] += jnp.sum((dy * xf).reshape(BLOCK // 8, 8, width), axis=0)


def _call(name, kernel, interpret, grid, in_specs, out_specs, out_shape,
          scratch):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _specs(bsz, t, c, n, reverse: bool):
    """Windows for grid (batch, block, tile): a (BLOCK, TILE) piece of a
    (batch, time, channels) array, a block's (BLOCK, N, 128) piece of B or
    C, a tile's (rows, TILE) piece of A or D, the (N, TILE) state at a
    block's entry, and a sequence's (tiles, rows, TILE) sums."""
    from jax.experimental import pallas as pl
    width, blocks = _tile(c), t // BLOCK

    def at(b):
        return blocks - 1 - b if reverse else b

    rows = pl.BlockSpec((1, BLOCK, width), lambda i, b, j: (i, at(b), j))
    group = pl.BlockSpec((1, BLOCK, n, LANES),
                         lambda i, b, j: (i, at(b), 0, 0))
    state = pl.BlockSpec((1, 1, n, width), lambda i, b, j: (i, at(b), 0, j))

    def by_tile(height):
        return pl.BlockSpec((height, width), lambda i, b, j: (0, j))

    def sums(height):
        return pl.BlockSpec((1, c // width, height, width),
                            lambda i, b, j: (i, 0, 0, 0))

    return (bsz, blocks, c // width), rows, group, state, by_tile, sums


def _spread(m):
    """B or C (batch, time, N) with every value over a lane tile."""
    return jnp.broadcast_to(m.astype(_F32)[..., None], m.shape + (LANES,))


@functools.partial(jax.jit, static_argnames=("save", "interpret"))
def _forward(x, dt, a_t, bm, cm, d, save: bool, interpret: bool):
    """x (batch, time, channels), dt like it in float32, A (N, channels)
    and D (1, channels) float32, B and C (batch, time, N): y like x in
    float32 and, where ``save``, the blocks' entry states (batch, blocks, N,
    channels)."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, t, c = x.shape
    n, width = a_t.shape[0], _tile(c)
    grid, rows, group, state, by_tile, _ = _specs(bsz, t, c, n, False)
    out_shape, out_specs = [jax.ShapeDtypeStruct((bsz, t, c), _F32)], [rows]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((bsz, t // BLOCK, n, c), _F32))
        out_specs.append(state)
    outs = _call(
        "selective_scan_fwd", functools.partial(_fwd_kernel, save), interpret,
        grid, [rows, rows, by_tile(n), group, group, by_tile(1)], out_specs,
        out_shape,
        [pltpu.VMEM((c // width, n, width), _F32),
         pltpu.VMEM((BLOCK, width), _F32)])(
             x, dt, a_t, _spread(bm), _spread(cm), d)
    return tuple(outs) if save else outs[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(x, dt, a_t, bm, cm, d, states, dy, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    bsz, t, c = x.shape
    n, width = a_t.shape[0], _tile(c)
    tiles = c // width
    grid, rows, group, state, by_tile, sums = _specs(bsz, t, c, n, True)
    wide = jax.ShapeDtypeStruct((bsz, t, c), _F32)
    narrow = jax.ShapeDtypeStruct((bsz, t, n, LANES), _F32)
    dx, ddt, db, dc, da, dd = _call(
        "selective_scan_bwd", _bwd_kernel, interpret, grid,
        [rows, rows, by_tile(n), group, group, by_tile(1), state, rows],
        [rows, rows, group, group, sums(n), sums(8)],
        [jax.ShapeDtypeStruct((bsz, t, c), x.dtype), wide, narrow, narrow,
         jax.ShapeDtypeStruct((bsz, tiles, n, width), _F32),
         jax.ShapeDtypeStruct((bsz, tiles, 8, width), _F32)],
        [pltpu.VMEM((tiles, n, width), _F32),
         pltpu.VMEM((BLOCK + 1, n, width), _F32),
         pltpu.VMEM((BLOCK, n, width), _F32)]
        + [pltpu.VMEM((BLOCK, width), _F32)] * 3)(
            x, dt, a_t, _spread(bm), _spread(cm), d, states, dy)
    da = jnp.moveaxis(jnp.sum(da, axis=0), 0, 1).reshape(n, c)
    return (dx, ddt, da, jnp.sum(db, axis=-1).astype(bm.dtype),
            jnp.sum(dc, axis=-1).astype(cm.dtype),
            jnp.sum(dd, axis=(0, 2)).reshape(1, c))


@jax.custom_vjp
def _scan(x, dt, a_t, bm, cm, d):
    return _forward(x, dt, a_t, bm, cm, d, False, _pk.interpret())


def _scan_fwd(x, dt, a_t, bm, cm, d):
    y, states = _forward(x, dt, a_t, bm, cm, d, True, _pk.interpret())
    return y, (x, dt, a_t, bm, cm, d, states)


def _scan_bwd(res, dy):
    return _backward(*res, dy, _pk.interpret())


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, a_rate, bm, cm, skip=None):
    """``chunked_selective_scan`` for inputs ``supported`` takes: y (batch,
    time, channels) in float32. The custom-VJP is over the arrays as the
    kernels read them: A with the states first, D a row."""
    c = x.shape[2]
    d = (jnp.zeros((1, c), _F32) if skip is None
         else skip.astype(_F32).reshape(1, c))
    return _scan(x, dt, a_rate.astype(_F32).T, bm, cm, d)

"""Pallas TPU kernels for the input path of the delta-rule layers.

The boundary is what ``KimiDeltaAttention.apply`` and ``GatedDeltaNet.apply``
(nn/conf/linear_attention.py) do between the projections' products and the
chunked scan: a causal depthwise convolution over time, SiLU, for q and k
the per-head L2 norm (q also scaled by 1 / sqrt(d)), and for KDA the decay
``g = -exp(A_log) * softplus(f + dt_bias)``. In plain ``jax.numpy`` XLA runs
that as a chain of passes over (time, heads x K) arrays (a pad, shifted
slices, multiply-adds, a cast, a lane reduce), its transpose as pads, adds
and reductions over time, and then transposes q, k, v, g to the (batch,
heads, time, K) windows the scan's kernels read and dq, dk, dv, dg back.

Here one kernel reads a (time tile, head group x K) window of each
projection's output as the product wrote it and writes q, k, v (compute
type) and g (float32) as (batch, heads, time, K) windows: a head is one
K-lane column block of the tile, so the transpose costs nothing. One
kernel reads dq, dk, dv, dg in that layout, makes the tile's convolution,
SiLU and norm again from the projections (the only residuals besides the
small weights) and writes the projections' cotangents as (time, heads x K)
matrices for XLA's weight-gradient products; the taps', the decay rates'
and the bias's gradients are summed over the time tiles in their output
windows, eight partial rows each, and reduced by one small XLA sum.

Arithmetic is float32 from the load to the store: nothing is rounded that
the ``jax.numpy`` form does not round, and the roundings to the compute
type it makes after the convolution and the SiLU are not made. The SiLU's
sigmoid is ``0.5 tanh(x / 2) + 0.5``, one transcendental; on the chip it
reads 9e-6 of the norm off the ``jax.numpy`` form on float32 inputs
(PERF.md §6, PR 31), bfloat16's own step being 4e-3.

Grid (batch, key-head group, time tile). A step walks its heads and, a
head at a time, passes of ``_ROWS`` rows, ``_INTERLEAVE`` independent
passes to a loop body, so a pass's values stay in registers and one pass's
waits are filled with another's work. The convolution's rows before a pass
come from the same window, before a tile from a second, ``_HALO``-row
window of the same array (zeros before the first tile). Shifted rows are
read back from a small float32 scratch at a sublane offset, which the load
unit does for nothing (rotating registers and selecting between neighbours
was a quarter of the VALU work; Mosaic refuses such windows of bfloat16
arrays). The backward kernel walks time from the end: the transposed
convolution needs the cotangents of the rows after a pass, which the pass
after it (run before it) hands on, across tiles in a VMEM scratch.

Gated DeltaNet takes the same kernels: its q, k, v are three column ranges
of one projection (a stream names its array and its first head) under one
taps array, and each q/k head is written to the ``rep`` consecutive value
heads it serves (backward: their cotangents are summed); it has no decay
stream (one decay a head is spread in front of the scan).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.perf import pallas as _pk
from deeplearning4j_tpu.perf.pallas import kda as _scan

__all__ = ["Spec", "supported", "kda_inputs"]

_F32 = jnp.float32
_ROWS = 64              # rows of one pass: 8 registers an array and head
_HALO = 16              # rows read before a tile: one bfloat16 tile
_TILES = (512, 256, 128, 64)
_LANES_A_STEP = 1024    # a step's widest window, in columns
_MAX_TAPS = 4
_INTERLEAVE = 4         # passes in one straight-line loop body


class Spec(NamedTuple):
    """What a call reads, static. ``srcs``: for q, k and v the index of the
    stream's array in ``xs``, of its taps in ``ws`` and the stream's first
    head in both (heads of ``head_dim`` columns); ``decay``: the same pair
    for the decay stream, or None; ``key_heads`` q/k heads, each serving
    ``rep`` consecutive value heads."""
    srcs: Tuple[Tuple[int, int, int], ...]
    decay: Optional[Tuple[int, int]]
    key_heads: int
    rep: int
    head_dim: int
    eps: float = 1e-6


def _heads_a_step(spec: Spec) -> int:
    """Key heads a grid step takes: as many as keep the widest window at
    ``_LANES_A_STEP`` columns, dividing the head count and every stream's
    first head (a window's column index counts whole windows); 0 where a
    value stream starts inside a key head's group."""
    (_, _, q0), (_, _, k0), (_, _, v0) = spec.srcs
    wide = [v0] + ([] if spec.decay is None else [spec.decay[1]])
    most = max(1, _LANES_A_STEP // (spec.head_dim * spec.rep))
    return max((d for d in range(1, most + 1)
                if spec.key_heads % d == 0 and q0 % d == 0 and k0 % d == 0
                and all(f % (d * spec.rep) == 0 for f in wide)), default=0)


def _tile(t: int) -> int:
    return next((c for c in _TILES if t % c == 0), 0)


def supported(dtype, bsz: int, t: int, spec: Spec, taps: int) -> bool:
    """Calls the kernels take: heads of 128 or 256 columns (whole lanes),
    at most ``_MAX_TAPS`` taps (the rows before a pass are one register),
    bfloat16 or float32 projections, a length (padded by the caller) that
    a time tile divides, streams that start on a step's head group, the
    scan's kernels taking the (batch, time, value heads, K) operands these
    write; on a TPU backend or in interpret mode."""
    if spec.head_dim % 128 or spec.head_dim > 256:
        return False
    if not 1 <= taps <= _MAX_TAPS:
        return False
    if dtype not in (jnp.bfloat16, jnp.float32) or not _tile(t) or bsz < 1:
        return False
    if not _heads_a_step(spec):
        return False
    wide = jax.ShapeDtypeStruct(
        (bsz, t, spec.key_heads * spec.rep, spec.head_dim), dtype)
    if not _scan.supported(wide, wide, wide,
                           jax.ShapeDtypeStruct(wide.shape, _F32),
                           jax.ShapeDtypeStruct(wide.shape[:3], _F32),
                           _scan.CHUNK, _scan.SUB):
        return False
    return _pk.interpret() or jax.default_backend() == "tpu"


# ------------------------------------------------------------- pass algebra
# Everything below works on float32 VALUES of one pass of one head:
# ``xa`` (8 + _ROWS, K), the stream's rows from 8 before the pass on, taps
# ``w`` (taps, K). Traced into the kernels; the tests call the kernels.
def _sigmoid(x):
    """Through tanh: one transcendental, where 1 / (1 + exp(-x)) is two and
    an exact division's special cases."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _conv_silu(xa, w, shift_ref):
    """c_r = sum_j w[j] x_{r - (taps - 1) + j}, sigmoid(c) and SiLU(c).
    Rows shifted by m are a window of ``shift_ref`` (8 + rows, K) that
    starts m rows early: a load at a sublane offset, where rotating the
    registers and selecting between neighbours is two VALU operations a
    register and shift."""
    taps, rows = w.shape[0], xa.shape[0] - 8
    shift_ref[...] = xa
    c = w[taps - 1:taps] * xa[8:]
    for j in range(taps - 1):
        m = taps - 1 - j
        c = c + w[j:j + 1] * shift_ref[8 - m:8 - m + rows, :]
    sig = _sigmoid(c)
    return c, sig, c * sig


def _conv_silu_bwd(xa, w, c, sig, ds, after, shift_ref):
    """Through SiLU and the convolution: the cotangent of the stream's
    rows, eight partial rows of each tap's gradient and the first eight
    rows of dc (what the pass before this one needs as ``after``)."""
    taps, rows = w.shape[0], c.shape[0]
    dc = ds * (sig * (1.0 + c * (1.0 - sig)))
    shift_ref[...] = jnp.concatenate([dc, after], 0)
    x = xa[8:]
    dx, dws = None, []
    for j in range(taps):
        m = taps - 1 - j                   # dc_{r + m} meets x_r under w[j]
        up = dc if m == 0 else shift_ref[m:m + rows, :]
        dx = w[j:j + 1] * up if dx is None else dx + w[j:j + 1] * up
        dws.append(_sum8(up * x))
    return dx, dws, dc[:8]


def _sum8(y):
    """(rows, K) -> (8, K): whole registers added, no cross-sublane work."""
    rows, kd = y.shape
    return jnp.sum(y.reshape(rows // 8, 8, kd), axis=0)


def _unit(s, eps: float):
    """1 / |s| a row, (rows, 1)."""
    return lax.rsqrt(jnp.sum(s * s, axis=1, keepdims=True) + eps)


def _softplus(u):
    return jnp.maximum(u, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(u)))


# ------------------------------------------------------------------ kernels
def _rows_from(x_ref, halo_ref, r0, lanes, live):
    """The stream's rows from 8 before ``r0`` to the pass's end, float32:
    from the tile's window, before its first row from the halo window
    (``live``: 0.0 in the first tile, where nothing comes before)."""
    from jax.experimental import pallas as pl
    cur = x_ref[0, pl.ds(r0, _ROWS), lanes].astype(_F32)
    start = pl.multiple_of(jnp.maximum(r0 - _HALO, 0), _HALO)
    prev = x_ref[0, pl.ds(start, _HALO), lanes].astype(_F32)
    halo = halo_ref[0, :, lanes].astype(_F32) * live
    prev = jnp.where(r0 == 0, halo, prev)
    return jnp.concatenate([prev[_HALO - 8:], cur], 0)


def _lanes(head, kd: int):
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(head * kd, 128), kd)


def _over_passes(passes: int, one, carry):
    """``carry = one(i, slot, carry)`` for the tile's passes,
    ``_INTERLEAVE`` of them in one straight-line loop body, each with its
    own ``slot`` of the shift scratch: the scheduler fills one pass's
    waits (a stored window read back, a lane reduce) with the other's
    work."""
    u = max(d for d in range(1, _INTERLEAVE + 1) if passes % d == 0)

    def body(i, carry):
        for j in range(u):
            carry = one(i * u + j, j, carry)
        return carry

    return lax.fori_loop(0, passes // u, body, carry)


def _split(refs, spec: Spec, per_stream: int, per_decay: int):
    n = 3 * per_stream
    streams = [refs[i * per_stream:(i + 1) * per_stream] for i in range(3)]
    decay = None
    if spec.decay is not None:
        decay, n = refs[n:n + per_decay], n + per_decay
    return streams, decay, refs[n:]


def _fwd_kernel(spec: Spec, hkb: int, tile: int, *refs):
    from jax.experimental import pallas as pl
    streams, decay, rest = _split(refs, spec, 3, 3)
    outs, shift_ref = rest[:-1], rest[-1]
    kd, rep = spec.head_dim, spec.rep
    live = (pl.program_id(2) > 0).astype(_F32)
    scale = 1.0 / math.sqrt(kd)
    passes = tile // _ROWS

    def head(h, carry):
        def stream(s, head_in, first_out, n_out, norm):
            """Stream ``s``'s head ``head_in``, written to ``n_out`` heads
            from ``first_out`` on."""
            x_ref, halo_ref, w_ref = streams[s]
            lanes = _lanes(head_in, kd)
            w = w_ref[:, lanes]

            def rows(i, slot, c):
                r0 = pl.multiple_of(i * _ROWS, _ROWS)
                y = _conv_silu(_rows_from(x_ref, halo_ref, r0, lanes, live),
                               w, shift_ref.at[slot])[2]
                if norm:
                    y = y * (_unit(y, spec.eps) * norm)
                y = y.astype(outs[s].dtype)
                for r in range(n_out):
                    outs[s][0, first_out + r, pl.ds(r0, _ROWS), :] = y
                return c

            _over_passes(passes, rows, 0)

        def decay_head(hv):
            f_ref, a_ref, bias_ref = decay
            lanes = _lanes(hv, kd)
            a, bias = a_ref[:, lanes], bias_ref[:, lanes]

            def rows(i, slot, c):
                r0 = pl.multiple_of(i * _ROWS, _ROWS)
                u = f_ref[0, pl.ds(r0, _ROWS), lanes].astype(_F32) + bias
                outs[3][0, hv, pl.ds(r0, _ROWS), :] = a * _softplus(u)
                return c

            _over_passes(passes, rows, 0)

        stream(0, h, h * rep, rep, scale)
        stream(1, h, h * rep, rep, 1.0)
        for r in range(rep):
            stream(2, h * rep + r, h * rep + r, 1, None)
            if decay is not None:
                decay_head(h * rep + r)
        return carry

    lax.fori_loop(0, hkb, head, 0)


def _bwd_kernel(spec: Spec, hkb: int, tile: int, *refs):
    from jax.experimental import pallas as pl
    streams, decay, rest = _split(refs, spec, 4, 4)
    kd, rep = spec.head_dim, spec.rep
    n_out = 6 + (0 if decay is None else 3)
    outs, after_refs, shift_refs = rest[:n_out], rest[n_out:-2], rest[-2:]
    first = pl.program_id(2) == 0                   # the LAST tile in time
    live = (pl.program_id(2) < pl.num_programs(2) - 1).astype(_F32)
    scale = 1.0 / math.sqrt(kd)
    passes = tile // _ROWS

    @pl.when(first)
    def _():
        sums = after_refs + outs[3:6] + (outs[7:] if decay is not None
                                         else ())
        for i in range(len(sums)):
            sums[i][...] = jnp.zeros(sums[i].shape, _F32)

    def head(h, carry):
        def stream(s, head_in, first_out, n_out, norm):
            x_ref, halo_ref, w_ref, d_ref = streams[s]
            dx_ref, dw_ref, after_ref = outs[s], outs[3 + s], after_refs[s]
            lanes = _lanes(head_in, kd)
            w = w_ref[:, lanes]
            taps = w.shape[0]

            def rows(i, slot, c):
                after, dws = c
                r0 = pl.multiple_of((passes - 1 - i) * _ROWS, _ROWS)
                xa = _rows_from(x_ref, halo_ref, r0, lanes, live)
                cv, sig, y = _conv_silu(xa, w, shift_refs[0].at[slot])
                dy = d_ref[0, first_out, pl.ds(r0, _ROWS), :].astype(_F32)
                for r in range(1, n_out):   # a q/k head's value heads
                    dy = dy + d_ref[0, first_out + r, pl.ds(r0, _ROWS),
                                    :].astype(_F32)
                if norm:                    # through y / |y| * norm
                    inv = _unit(y, spec.eps)
                    dy = dy * norm
                    dy = inv * (dy - y * (inv * inv * jnp.sum(
                        dy * y, axis=1, keepdims=True)))
                dx, new, after = _conv_silu_bwd(xa, w, cv, sig, dy, after,
                                                shift_refs[1].at[slot])
                dx_ref[0, pl.ds(r0, _ROWS), lanes] = dx.astype(dx_ref.dtype)
                return after, tuple(dws[j] + new[j] for j in range(taps))

            zero = jnp.zeros((8, kd), _F32)
            after, dws = _over_passes(
                passes, rows, (after_ref[:, lanes], (zero,) * taps))
            after_ref[:, lanes] = after
            for j in range(taps):
                dw_ref[0, 8 * j:8 * j + 8, lanes] += dws[j]

        def decay_head(hv):
            f_ref, a_ref, bias_ref, dg_ref = decay
            df_ref, da_ref, dbias_ref = outs[6:9]
            lanes = _lanes(hv, kd)
            a, bias = a_ref[:, lanes], bias_ref[:, lanes]

            def rows(i, slot, c):
                da, dbias = c
                r0 = pl.multiple_of(i * _ROWS, _ROWS)
                u = f_ref[0, pl.ds(r0, _ROWS), lanes].astype(_F32) + bias
                dg = dg_ref[0, hv, pl.ds(r0, _ROWS), :]
                du = dg * a * _sigmoid(u)
                df_ref[0, pl.ds(r0, _ROWS), lanes] = du.astype(df_ref.dtype)
                return da + _sum8(dg * _softplus(u)), dbias + _sum8(du)

            zero = jnp.zeros((8, kd), _F32)
            da, dbias = _over_passes(passes, rows, (zero, zero))
            da_ref[0, :, lanes] += da
            dbias_ref[0, :, lanes] += dbias

        stream(0, h, h * rep, rep, scale)
        stream(1, h, h * rep, rep, 1.0)
        for r in range(rep):
            stream(2, h * rep + r, h * rep + r, 1, None)
            if decay is not None:
                decay_head(h * rep + r)
        return carry

    lax.fori_loop(0, hkb, head, 0)


# ----------------------------------------------------------------- wrappers
def _windows(spec: Spec, hkb: int, tile: int, nt: int, reverse: bool):
    """BlockSpec makers for grid (batch, key-head group, time tile): a
    stream's (time tile, heads x K) window, the ``_HALO`` rows before it,
    its taps, its eight-row partial sums, a row of decay constants and the
    (heads, time tile, K) windows of the scan's operands."""
    from jax.experimental import pallas as pl
    kd = spec.head_dim

    def at(t):
        return nt - 1 - t if reverse else t

    def wide(first: int, heads: int):
        off = first // heads
        return pl.BlockSpec((1, tile, heads * kd),
                            lambda i, j, t: (i, at(t), off + j))

    def halo(first: int, heads: int):
        off, per = first // heads, tile // _HALO
        return pl.BlockSpec(
            (1, _HALO, heads * kd),
            lambda i, j, t: (i, jnp.maximum(at(t) * per - 1, 0), off + j))

    def small(first: int, heads: int, rows: int):
        off = first // heads
        return pl.BlockSpec((rows, heads * kd), lambda i, j, t: (0, off + j))

    def sums(heads: int, rows: int):
        return pl.BlockSpec((1, rows, heads * kd), lambda i, j, t: (i, 0, j))

    def major(heads: int):
        return pl.BlockSpec((1, heads, tile, kd),
                            lambda i, j, t: (i, j, at(t), 0))

    return wide, halo, small, sums, major


def _stream_heads(spec: Spec, hkb: int):
    """Heads a step takes of q, k, v: (first head, heads a step)."""
    return [(first, hkb * (spec.rep if s == 2 else 1))
            for s, (_, _, first) in enumerate(spec.srcs)]


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def _forward(xs, ws, rows, spec: Spec, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    bsz, t, _ = xs[0].shape
    kd, hv = spec.head_dim, spec.key_heads * spec.rep
    hkb, tile, taps = _heads_a_step(spec), _tile(t), ws[0].shape[0]
    nt = t // tile
    wide, halo, small, _, major = _windows(spec, hkb, tile, nt, False)
    shifts = pltpu.VMEM((_INTERLEAVE, 8 + _ROWS, kd), _F32)
    args, in_specs = [], []
    for (xi, wi, _), (first, heads) in zip(spec.srcs,
                                           _stream_heads(spec, hkb)):
        args += [xs[xi], xs[xi], ws[wi]]
        in_specs += [wide(first, heads), halo(first, heads),
                     small(first, heads, taps)]
    like = jax.ShapeDtypeStruct((bsz, hv, t, kd), xs[0].dtype)
    out_shape, out_specs = [like] * 3, [major(hkb * spec.rep)] * 3
    if spec.decay is not None:
        fi, first = spec.decay
        heads = hkb * spec.rep
        args += [xs[fi], *rows]
        in_specs += [wide(first, heads), small(0, heads, 1),
                     small(0, heads, 1)]
        out_shape.append(jax.ShapeDtypeStruct((bsz, hv, t, kd), _F32))
        out_specs.append(major(heads))
    return tuple(_scan._call(
        "kda_inputs_fwd", functools.partial(_fwd_kernel, spec, hkb, tile),
        interpret, (bsz, spec.key_heads // hkb, nt), in_specs, out_specs,
        out_shape, [shifts])(*args))


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def _backward(xs, ws, rows, cts, spec: Spec, interpret: bool):
    """Per stream the cotangent of its columns (batch, time, columns) and
    of its taps (taps, columns), then df, d a-row, d bias-row."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, t, _ = xs[0].shape
    kd = spec.head_dim
    hkb, tile, taps = _heads_a_step(spec), _tile(t), ws[0].shape[0]
    nt = t // tile
    wide, halo, small, sums, major = _windows(spec, hkb, tile, nt, True)
    shifts = pltpu.VMEM((_INTERLEAVE, 8 + _ROWS, kd), _F32)
    args, in_specs, dx_shape, dx_specs, dw_shape, dw_specs, scratch = (
        [], [], [], [], [], [], [])
    heads_of = _stream_heads(spec, hkb)
    for s, ((xi, wi, _), (first, heads)) in enumerate(zip(spec.srcs,
                                                          heads_of)):
        args += [xs[xi], xs[xi], ws[wi], cts[s]]
        in_specs += [wide(first, heads), halo(first, heads),
                     small(first, heads, taps), major(hkb * spec.rep)]
        cols = (spec.key_heads * heads // hkb) * kd
        dx_shape.append(jax.ShapeDtypeStruct((bsz, t, cols), xs[xi].dtype))
        dx_specs.append(wide(0, heads))
        dw_shape.append(jax.ShapeDtypeStruct((bsz, 8 * taps, cols), _F32))
        dw_specs.append(sums(heads, 8 * taps))
        scratch.append(pltpu.VMEM((8, heads * kd), _F32))
    out_shape, out_specs = dx_shape + dw_shape, dx_specs + dw_specs
    if spec.decay is not None:
        fi, first = spec.decay
        heads = hkb * spec.rep
        cols = spec.key_heads * spec.rep * kd
        args += [xs[fi], *rows, cts[3]]
        in_specs += [wide(first, heads), small(0, heads, 1),
                     small(0, heads, 1), major(heads)]
        out_shape += [jax.ShapeDtypeStruct((bsz, t, cols), xs[fi].dtype)] + [
            jax.ShapeDtypeStruct((bsz, 8, cols), _F32)] * 2
        out_specs += [wide(0, heads), sums(heads, 8), sums(heads, 8)]
    outs = _scan._call(
        "kda_inputs_bwd", functools.partial(_bwd_kernel, spec, hkb, tile),
        interpret, (bsz, spec.key_heads // hkb, nt), in_specs, out_specs,
        out_shape, scratch + [shifts] * 2)(*args)
    dxs = list(outs[:3])
    dws = [jnp.sum(a.reshape(bsz, taps, 8, -1), axis=(0, 2))
           for a in outs[3:6]]
    tail = ()
    if spec.decay is not None:
        tail = (outs[6],) + tuple(jnp.sum(a, axis=(0, 1))[None]
                                  for a in outs[7:9])
    return dxs, dws, tail


def _assemble(pieces, like, axis: int):
    """The cotangent of an array some of whose columns the streams read:
    ``pieces`` (first column, columns' cotangent), zeros elsewhere."""
    parts, at = [], 0
    for first, piece in sorted(pieces, key=lambda p: p[0]):
        if first > at:
            shape = list(piece.shape)
            shape[axis] = first - at
            parts.append(jnp.zeros(shape, like.dtype))
        parts.append(piece.astype(like.dtype))
        at = first + piece.shape[axis]
    if at < like.shape[axis]:
        shape = list(like.shape)
        shape[axis] = like.shape[axis] - at
        parts.append(jnp.zeros(shape, like.dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def kda_inputs(xs, ws, rows, spec: Spec):
    """The scan's operands from the projections' outputs. ``xs``: the
    arrays (batch, time, columns) the streams of ``spec`` read, in the
    compute type, ``time`` a multiple of a time tile; ``ws``: their taps
    (taps, columns), float32; ``rows``: for a decay stream the float32 rows
    (1, heads x K) ``-exp(A_log)`` spread over its head's columns and
    ``dt_bias``, else (). Returns q, k, v (compute type) and, with a decay
    stream, g (float32), each (batch, value heads, time, K)."""
    return _forward(xs, ws, rows, spec, _pk.interpret())


def _kda_inputs_fwd(xs, ws, rows, spec: Spec):
    return _forward(xs, ws, rows, spec, _pk.interpret()), (xs, ws, rows)


def _kda_inputs_bwd(spec: Spec, res, cts):
    xs, ws, rows = res
    dxs, dws, tail = _backward(xs, ws, rows, cts, spec, _pk.interpret())
    kd = spec.head_dim
    x_pieces = [[] for _ in xs]
    w_pieces = [[] for _ in ws]
    for (xi, wi, first), dx, dw in zip(spec.srcs, dxs, dws):
        x_pieces[xi].append((first * kd, dx))
        w_pieces[wi].append((first * kd, dw))
    d_rows = ()
    if spec.decay is not None:
        x_pieces[spec.decay[0]].append((spec.decay[1] * kd, tail[0]))
        d_rows = tuple(tail[1:])
    return (tuple(_assemble(p, x, 2) for p, x in zip(x_pieces, xs)),
            tuple(_assemble(p, w, 1) for p, w in zip(w_pieces, ws)),
            d_rows)


kda_inputs.defvjp(_kda_inputs_fwd, _kda_inputs_bwd)

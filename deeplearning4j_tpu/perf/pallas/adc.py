"""Retrieval hot-loop Pallas kernels: ADC gather-accumulate + int4 dot.

Three kernels behind the boundaries retrieval/ already parity-tests:

- :func:`score_pq` — flat ADC for ``PQIndex``: the per-query LUT is the
  same jitted ``_adc_lut`` matmul the reference runs; the M-way
  code-table gather-accumulate (the bandwidth-bound loop — n·M byte
  reads feeding n·M LUT lookups) moves into a ``pallas_call`` gridded
  over code-table tiles, accumulating in a VMEM (b, tile) f32 block.
- :func:`score_ivf_pq` — IVF-PQ for ``IVFPQIndex``: probe, residual
  LUT build and CSR slot arithmetic stay the reference jnp (small,
  matmul-shaped); the per-slot fused (segment, code) flat-index
  gather-accumulate — the loop that touches every candidate byte —
  runs in the kernel.
- :func:`int4_matmul` / :func:`score_brute_int4` — the int4 table dot
  for ``BruteForceIndex(int4=True)`` (and the int4 ``QuantizedLayer``
  lowering): nibble unpack fused IN-KERNEL against the int8×int8→int32
  ``dot_general``, so the unpacked operand lives only as a VMEM tile.

Accumulation order matches the references step for step, so flat-ADC
distances and the int dot are BITWISE identical — top-k ids can be
asserted equal, not merely close (tests/test_zz_pallas.py). Dense-IVF
int4 variants (``IVFIndex(int4=True)``) stay on the XLA reference —
their gather-then-unpack shape is already one fused XLA op; documented
selection rule, not an oversight.

What the v5e compiler accepts shaped two of the bodies: Mosaic has no
general in-kernel gather (``jnp.take``: "Shape mismatch in input,
indices and output"), so the flat-ADC lookup is a one-hot matmul; and it
has neither int8 vector shifts ("failed to legalize operation
'arith.shli'") nor the stack+reshape nibble interleave
("infer-vector-layout: unsupported shape cast"), so the int4 dot
sign-extends in int32 and meets the even / odd query columns with the
low / high nibbles. ``score_ivf_pq`` gathers CSR rows by data-dependent
index and is NOT in the automatic TPU rule
(``perf.pallas.TPU_AUTO_FAMILIES``); it runs under explicit override
(interpret-mode parity) only, until it is reworked around DMA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.perf import pallas as _pk

__all__ = ["score_pq", "score_ivf_pq", "int4_matmul", "score_brute_int4",
           "pq_supported", "int4_supported", "brute_int4_supported"]

_NBLK = 512
# what one program's blocks and temporaries may take of Mosaic's 16 MiB
# default scoped VMEM on a v5e (tests/test_chip_compile.py compiles the
# full-width shapes)
_VMEM_BUDGET = 12 * 1024 * 1024


def _nblk(n: int) -> int:
    # table rows per program: 512-row tiles (Pallas masks the ragged last
    # one), the whole table when it is smaller than a tile
    return min(n, _NBLK)


def pq_supported(q, codebooks, codes, *_, **__) -> bool:
    """``score_pq`` keeps the whole (b, M, ksub) f32 LUT VMEM-resident
    (double-buffered) beside a (512, ksub) one-hot and the (b, 512)
    accumulator."""
    b = q.shape[0]
    m_count, ksub, _ = codebooks.shape
    nblk = _nblk(codes.shape[0])
    need = 4 * (2 * b * m_count * ksub + 2 * nblk * ksub + 3 * b * nblk)
    return need <= _VMEM_BUDGET


def int4_supported(b: int, n: int, w: int) -> bool:
    """``int4_matmul`` holds a (512, w) packed tile twice over as int8
    windows, once as int32 and as its two int8 nibble planes, beside the
    (b, w) query halves."""
    need = _nblk(n) * w * (2 + 4 + 2) + 4 * b * w + 12 * b * _nblk(n)
    return need <= _VMEM_BUDGET


def brute_int4_supported(q, packed, *_, **__) -> bool:
    """:func:`int4_supported` over ``score_brute_int4``'s arguments."""
    return int4_supported(q.shape[0], *packed.shape)


# ---------------------------------------------------------------- flat ADC
def _adc_kernel(m_count, ksub, lut_ref, codes_ref, d2_ref):
    codes = codes_ref[...].astype(jnp.int32)
    iota = lax.broadcasted_iota(jnp.int32, (codes.shape[0], ksub), 1)
    acc = jnp.zeros(d2_ref.shape, jnp.float32)
    for m in range(m_count):                       # static unroll over M
        # LUT lookup as a one-hot matmul: one 1.0 per row, so the f32 dot
        # at highest precision returns the LUT entry itself, bit for bit
        onehot = (codes[:, m:m + 1] == iota).astype(jnp.float32)
        acc = acc + lax.dot_general(lut_ref[:, m, :], onehot,
                                    (((1,), (1,)), ((), ())),
                                    precision=lax.Precision.HIGHEST,
                                    preferred_element_type=jnp.float32)
    d2_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("k",))
def score_pq(q, codebooks, codes, k: int):
    """Pallas flat ADC with ``_score_pq``'s signature and bitwise its
    distances: LUT outside (matmul), lookup-accumulate inside, top-k on
    the kernel's (b, n) output."""
    from jax.experimental import pallas as pl
    from deeplearning4j_tpu.retrieval.pq import _adc_lut

    b = q.shape[0]
    m_count, ksub, dsub = codebooks.shape
    n = codes.shape[0]
    lut = _adc_lut(q.reshape(b, m_count, dsub), codebooks)
    nblk = _nblk(n)
    d2 = pl.pallas_call(
        functools.partial(_adc_kernel, m_count, ksub),
        grid=(pl.cdiv(n, nblk),),
        in_specs=[
            pl.BlockSpec((b, m_count, ksub), lambda j: (0, 0, 0)),
            pl.BlockSpec((nblk, m_count), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((b, nblk), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=_pk.interpret(),
    )(lut, codes)
    neg, idx = lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


# ----------------------------------------------------------------- IVF-PQ
def _ivf_adc_kernel(m_count, ksub, lut_ref, codes_ref, seg_ref, pos_ref,
                    d2_ref):
    seg = seg_ref[...]
    pos = pos_ref[...]
    lut = lut_ref[...]
    codes = codes_ref[...]
    b = seg.shape[0]
    acc = jnp.zeros(seg.shape, jnp.float32)
    for m in range(m_count):                       # static unroll over M
        lut_m = lut[:, :, m, :].reshape(b, -1)     # (b, p·ksub)
        code_m = codes[pos, m].astype(seg.dtype)
        acc = acc + jnp.take_along_axis(lut_m, seg * ksub + code_m, axis=1)
    d2_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "cand_pad"))
def score_ivf_pq(q, centroids, codebooks, flat_codes, flat_ids, offsets,
                 k: int, nprobe: int, cand_pad: int):
    """Pallas IVF-PQ with ``_score_ivf_pq``'s signature: probe + per-cell
    LUT + CSR slots in jnp (matmul-shaped, already fast), the per-slot
    (segment, code) gather-accumulate in-kernel. One program over the
    (b, cand_pad) slot block — the CSR flat table is gathered by
    data-dependent row, which Mosaic does not lower: interpret-mode
    parity only, never auto-selected on a TPU (module docstring)."""
    from jax.experimental import pallas as pl
    from deeplearning4j_tpu.retrieval.index import (_centroid_d2,
                                                    _csr_slots)
    from deeplearning4j_tpu.retrieval.pq import _adc_lut

    b = q.shape[0]
    m_count, ksub, dsub = codebooks.shape
    cd2 = _centroid_d2(q, centroids)
    _, probe = lax.top_k(-cd2, nprobe)                    # (b, p)
    qc = q[:, None, :] - centroids[probe]                 # (b, p, d)
    lut = _adc_lut(qc.reshape(b * nprobe, m_count, dsub),
                   codebooks).reshape(b, nprobe, m_count, ksub)
    seg, pos, valid = _csr_slots(offsets, probe, cand_pad)
    d2 = pl.pallas_call(
        functools.partial(_ivf_adc_kernel, m_count, ksub),
        out_shape=jax.ShapeDtypeStruct((b, cand_pad), jnp.float32),
        interpret=_pk.interpret(),
    )(lut, flat_codes, seg, pos)
    d2 = jnp.where(valid, d2, jnp.inf)
    ids = jnp.where(valid, flat_ids[pos], -1)
    neg, p2 = lax.top_k(-d2, k)
    took = jnp.take_along_axis(ids, p2, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), took


# --------------------------------------------------------------- int4 dot
def _int4_dot_kernel(qe_ref, qo_ref, p_ref, out_ref):
    # unpack_nibbles without the interleave: sign-extend each nibble in
    # int32 (left 28 + arithmetic right 28 for the low one, arithmetic
    # right 4 for the high one); the two int8 planes feed the dot directly
    # and never leave VMEM
    p = p_ref[...].astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28).astype(jnp.int8)
    hi = jnp.right_shift(p, 4).astype(jnp.int8)
    dims = (((1,), (1,)), ((), ()))
    out_ref[...] = (
        lax.dot_general(qe_ref[...], lo, dims,
                        preferred_element_type=jnp.int32)
        + lax.dot_general(qo_ref[...], hi, dims,
                          preferred_element_type=jnp.int32))


def int4_matmul(qq, packed, d: int):
    """int8 queries (b, d) × packed int4 table (n, ceil(d/2)) →
    int32 (b, n): nibble unpack fused against the integer dot inside one
    ``pallas_call``, gridded over table-row tiles. Column 2j of the
    unpacked table is byte j's low nibble and column 2j+1 its high one,
    so the even query columns dot the low plane and the odd ones the
    high plane. Bit-exact (integer arithmetic end to end)."""
    from jax.experimental import pallas as pl

    b = qq.shape[0]
    n, w = packed.shape
    qe = qq[:, 0:d:2]
    qo = qq[:, 1:d:2]
    if d % 2:       # the last byte's high nibble is padding
        qo = jnp.pad(qo, ((0, 0), (0, 1)))
    nblk = _nblk(n)
    return pl.pallas_call(
        _int4_dot_kernel,
        grid=(pl.cdiv(n, nblk),),
        in_specs=[
            pl.BlockSpec((b, w), lambda j: (0, 0)),
            pl.BlockSpec((b, w), lambda j: (0, 0)),
            pl.BlockSpec((nblk, w), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((b, nblk), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        interpret=_pk.interpret(),
    )(qe, qo, packed)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def score_brute_int4(q, packed, vnorm2, scale_v, k: int, metric: str):
    """Pallas int4 brute scorer with ``_score_brute_int4``'s signature:
    per-row query quantization and the metric tail are the reference ops
    in the reference order (bitwise-identical distances); only the
    unpack+dot runs in-kernel."""
    from deeplearning4j_tpu.retrieval.index import _score_quantize_rows

    qq, scale_q = _score_quantize_rows(q)
    doti = int4_matmul(qq, packed, q.shape[1])
    dots = doti.astype(jnp.float32) * scale_q * scale_v[None, :]
    if metric == "cosine":
        cos = jnp.clip(dots, -1.0, 1.0)
        neg, idx = lax.top_k(cos, k)
        return jnp.arccos(neg), idx
    d2 = vnorm2[None, :] - 2.0 * dots + jnp.sum(q * q, axis=1, keepdims=True)
    neg, idx = lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx

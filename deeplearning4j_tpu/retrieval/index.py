"""TPU-native vector indexes: device-batched top-k over a resident corpus.

The reference serves nearest neighbors from host-side tree walks (SURVEY
§2.9: VPTree/KDTree/SpTree behind a Play server) — one CPU thread chasing
pointers per query. On an accelerator the same contract inverts: the whole
corpus lives in device memory and ONE program answers a whole query batch,

    d²(q, V) = |q|² − 2·q·Vᵀ + |V|²   (the matmul is the MXU op)
    top-k     = lax.top_k(−d², k)      (tie-stable: lower index first)

which is the ``_lloyd_step`` pattern from ``clustering/kmeans.py`` applied
to retrieval. Index types, one query contract:

- :class:`BruteForceIndex` — exact. Scores every vector; the oracle the
  host trees are tested against and the recall baseline for the rest.
- :class:`IVFIndex` — inverted-file coarse index: KMeans cells
  (``KMeansClustering``), probed ``nprobe``-nearest per query. Two cell
  layouts: ``layout="dense"`` stores one padded, device-resident
  ``(n_cells, cap, d)`` block (every cell padded to the LARGEST cell —
  skewed corpora burn ``cap − count`` slots per cell); ``layout="csr"``
  stores the corpus FLAT in cell-major order plus a ``(n_cells+1,)``
  offsets array, and the kernel gathers each query's probed ranges into
  a candidate axis padded to one pow2 rung — resident memory is exactly
  ``n`` rows regardless of skew, with identical results (parity-asserted
  in tier-1).
- int8 compression (``int8=True`` on either) — vectors quantized on the
  symmetric grid of ``quant/``'s observers (scale = amax/127, zero point
  0, memory ×4 smaller); scoring quantizes each query row onto its own
  grid and runs int8×int8→int32 dot products
  (``preferred_element_type``), exactly the PTQ lowering recipe. Gate it
  with ``gates.assert_recall_within`` like the PTQ accuracy gates.
- int4 packing (``int4=True`` on either) — the next rung down: codes on
  the symmetric [-7, 7] grid (``quant/pack.py``), TWO per resident int8
  byte, unpacked with shift/mask INSIDE the jitted scorer (never on the
  host — lint DLT014), halving the int8 table's code bytes again.
  Queries stay on the int8 grid, so the dot is int8×int4→int32.
- Product quantization (``retrieval/pq.py``) — :class:`PQIndex` /
  :class:`IVFPQIndex` score 1-byte-per-subspace codes through an ADC
  lookup table; see that module.

Shape discipline (the serving contract): queries pad to a pow2
``BucketPolicy`` ladder on the batch axis and ``k`` rounds up to a pow2
rung, so a steady-state query mix reuses a small warmed set of compiled
programs — ``warmup()`` precompiles the ladder and ``compile_watch``
proves zero compiles after it. The jitted scoring path never touches the
host (lint rules DLT013/DLT014 + the trace_check tier-1 gate keep it
that way).

``memory_bytes()`` on every index is the device-resident (HBM) footprint
— scraped as the ``retrieval_index_bytes`` gauge so index residency sits
next to the planner's HBM numbers.

Padding slots answer ``index -1`` at distance ``inf`` (only visible when
``k`` exceeds the probed candidate count).
"""

from __future__ import annotations

import functools
import json
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.clustering.kmeans import KMeansClustering
from deeplearning4j_tpu.perf.bucketing import BucketPolicy, pad_to_bucket
from deeplearning4j_tpu.perf.compile_watch import CompileWatch
from deeplearning4j_tpu.quant.observers import QMAX, observe_stream
from deeplearning4j_tpu.quant.pack import (QMAX4, quantize_int4,
                                           unpack_nibbles,
                                           unpack_nibbles_host)

__all__ = ["BruteForceIndex", "IVFIndex", "load_index"]

_METRICS = ("euclidean", "cosine")

# assignment chunk for IVF builds: bounds the (chunk, n_cells) distance
# matrix so a million-vector build never materializes n×C at once
_ASSIGN_CHUNK = 16384


def _pow2ceil(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


# --------------------------------------------------------------- kernels
# (DLT013/DLT014 scope: these run under jit — device math only, no host
# numpy, no .item()/device_get, no data-dependent Python control flow)

def _score_dots(q, vecs, precision):
    return jnp.matmul(q, vecs.T, precision=precision)


def _centroid_d2(q, centroids):
    """(b, C) squared query→centroid distances, the probe scorer."""
    return (jnp.sum(centroids * centroids, axis=1)[None, :]
            - 2.0 * _score_dots(q, centroids, "highest")
            + jnp.sum(q * q, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _score_brute(q, vecs, vnorm2, k: int, metric: str):
    if metric == "cosine":
        # vecs/q are unit vectors; angular distance = arccos(cos), the
        # same true metric the host VPTree uses for "cosine"
        cos = jnp.clip(_score_dots(q, vecs, "highest"), -1.0, 1.0)
        neg, idx = lax.top_k(cos, k)
        return jnp.arccos(neg), idx
    d2 = (vnorm2[None, :] - 2.0 * _score_dots(q, vecs, "highest")
          + jnp.sum(q * q, axis=1, keepdims=True))
    neg, idx = lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


def _score_quantize_rows(q):
    """Quantize each query ROW onto its own symmetric int8 grid. Per-row
    (not per-batch) so a request's answer never depends on which other
    requests it was coalesced with."""
    amax = jnp.maximum(jnp.max(jnp.abs(q), axis=1, keepdims=True), 1e-12)
    scale = amax / QMAX
    qq = jnp.clip(jnp.round(q / scale), -QMAX, QMAX).astype(jnp.int8)
    return qq, scale


def _brute_i8_topk(q, vecs_q, vnorm2, scale_v, k: int, metric: str):
    """Shared tail for the quantized brute kernels: ``vecs_q`` is the
    int8 table (for int4 it arrives already unpacked in-kernel).
    scale_v is PER-VECTOR (quant/'s per-output-channel weight recipe):
    dot(q, v_i) ≈ s_q·s_i·(q8·v8_i), one int8×int8→int32 matmul."""
    qq, scale_q = _score_quantize_rows(q)
    doti = lax.dot_general(qq, vecs_q, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.int32)
    dots = doti.astype(jnp.float32) * scale_q * scale_v[None, :]
    if metric == "cosine":
        cos = jnp.clip(dots, -1.0, 1.0)
        neg, idx = lax.top_k(cos, k)
        return jnp.arccos(neg), idx
    d2 = vnorm2[None, :] - 2.0 * dots + jnp.sum(q * q, axis=1, keepdims=True)
    neg, idx = lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _score_brute_int8(q, vecs_q, vnorm2, scale_v, k: int, metric: str):
    return _brute_i8_topk(q, vecs_q, vnorm2, scale_v, k, metric)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _score_brute_int4(q, packed, vnorm2, scale_v, k: int, metric: str):
    # shift/mask unpack INSIDE the program: the resident table stays two
    # codes per byte; XLA fuses the unpack into the int dot's operand
    vecs_q = unpack_nibbles(packed, q.shape[1])
    return _brute_i8_topk(q, vecs_q, vnorm2, scale_v, k, metric)


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def _score_ivf(q, centroids, cells, ids, vnorm2, k: int, nprobe: int):
    b = q.shape[0]
    qn2 = jnp.sum(q * q, axis=1, keepdims=True)
    cd2 = _centroid_d2(q, centroids)
    _, probe = lax.top_k(-cd2, nprobe)                    # (b, nprobe)
    cand = cells[probe]                                   # (b, p, cap, d)
    cand_ids = ids[probe].reshape(b, -1)                  # (b, p·cap)
    cand_n2 = vnorm2[probe].reshape(b, -1)                # +inf on pads
    dots = jnp.einsum("bd,bpcd->bpc", q, cand,
                      precision="highest").reshape(b, -1)
    d2 = cand_n2 - 2.0 * dots + qn2
    neg, pos = lax.top_k(-d2, k)
    took = jnp.take_along_axis(cand_ids, pos, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), took


def _recenter_queries(q, centroids, probe):
    """RESIDUAL recentering (the FAISS IVF encoding): per probed cell,
    quantize ``q − c`` onto its own int8 grid — the residual amax is the
    cell radius, not the embedding magnitude, so the grid is an order
    finer than whole-vector quantization."""
    qc = q[:, None, :] - centroids[probe]                 # (b, p, d)
    amax = jnp.maximum(jnp.max(jnp.abs(qc), axis=2, keepdims=True), 1e-12)
    s_qc = amax / QMAX
    qcq = jnp.clip(jnp.round(qc / s_qc), -QMAX, QMAX).astype(jnp.int8)
    return qcq, s_qc


def _ivf_residual_topk(q, cd2, probe, cand, cand_ids, cand_n2, cand_s,
                       centroids, k: int):
    """Shared tail for the dense residual-quantized IVF kernels:
    ``cand`` is int8 residual codes (b, p, cap, d) — int4 variants unpack
    before calling. Scoring recenters the query per probed cell:
    |q−v|² = |q−c|² − 2·(q−c)·r + |r|², where |q−c|² is the centroid
    distance already computed for probing."""
    b = q.shape[0]
    qcq, s_qc = _recenter_queries(q, centroids, probe)
    doti = jnp.einsum("bpd,bpcd->bpc", qcq, cand,
                      preferred_element_type=jnp.int32)
    dots = (doti.astype(jnp.float32) * s_qc * cand_s).reshape(b, -1)
    cqd2 = jnp.take_along_axis(cd2, probe, axis=1)        # |q−c|² (b, p)
    d2 = (jnp.repeat(cqd2, cand.shape[2], axis=1)
          - 2.0 * dots + cand_n2)
    neg, pos = lax.top_k(-d2, k)
    took = jnp.take_along_axis(cand_ids, pos, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), took


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def _score_ivf_int8(q, centroids, cells_q, ids, rnorm2, scales,
                    k: int, nprobe: int):
    b = q.shape[0]
    cd2 = _centroid_d2(q, centroids)
    _, probe = lax.top_k(-cd2, nprobe)                    # (b, p)
    cand = cells_q[probe]                                 # (b, p, cap, d) i8
    cand_ids = ids[probe].reshape(b, -1)
    cand_n2 = rnorm2[probe].reshape(b, -1)                # +inf on pads
    cand_s = scales[probe]                                # (b, p, cap)
    return _ivf_residual_topk(q, cd2, probe, cand, cand_ids, cand_n2,
                              cand_s, centroids, k)


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def _score_ivf_int4(q, centroids, cells_p, ids, rnorm2, scales,
                    k: int, nprobe: int):
    b = q.shape[0]
    cd2 = _centroid_d2(q, centroids)
    _, probe = lax.top_k(-cd2, nprobe)
    # gather FIRST, then shift/mask-unpack only the probed cells — the
    # resident table never exists in unpacked form
    cand = unpack_nibbles(cells_p[probe], q.shape[1])     # (b, p, cap, d)
    cand_ids = ids[probe].reshape(b, -1)
    cand_n2 = rnorm2[probe].reshape(b, -1)
    cand_s = scales[probe]
    return _ivf_residual_topk(q, cd2, probe, cand, cand_ids, cand_n2,
                              cand_s, centroids, k)


def _csr_slots(offsets, probe, cand_pad: int):
    """Segment arithmetic for the CSR layout: map each of ``cand_pad``
    candidate slots to (probe segment, flat row). The probed ranges
    concatenate in probe-major / within-cell order — the SAME relative
    order of real candidates as the dense layout (whose pads sit at each
    cell's tail at +inf), so tie-stable top-k picks identical ids.
    Returns ``(seg, pos, valid)``, each (b, cand_pad)."""
    starts = offsets[probe]                               # (b, p)
    counts = offsets[probe + 1] - starts                  # (b, p)
    ends = jnp.cumsum(counts, axis=1)                     # inclusive
    begins = ends - counts
    slot = jnp.arange(cand_pad, dtype=ends.dtype)[None, :]
    # segment of a slot = number of segment-ends <= slot (a (b,C,p)
    # compare-and-sum — C·p stays small, no vmapped searchsorted needed)
    seg = jnp.sum(ends[:, None, :] <= slot[:, :, None], axis=2)
    seg = jnp.minimum(seg, probe.shape[1] - 1)
    within = slot - jnp.take_along_axis(begins, seg, axis=1)
    pos = jnp.take_along_axis(starts, seg, axis=1) + within
    valid = slot < ends[:, -1:]
    return seg, jnp.where(valid, pos, 0), valid


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "cand_pad"))
def _score_ivf_csr(q, centroids, flat, flat_ids, flat_n2, offsets,
                   k: int, nprobe: int, cand_pad: int):
    cd2 = _centroid_d2(q, centroids)
    _, probe = lax.top_k(-cd2, nprobe)
    seg, pos, valid = _csr_slots(offsets, probe, cand_pad)
    cand = flat[pos]                                      # (b, C, d)
    cand_ids = jnp.where(valid, flat_ids[pos], -1)
    cand_n2 = jnp.where(valid, flat_n2[pos], jnp.inf)
    dots = jnp.einsum("bd,bcd->bc", q, cand, precision="highest")
    d2 = cand_n2 - 2.0 * dots + jnp.sum(q * q, axis=1, keepdims=True)
    neg, p2 = lax.top_k(-d2, k)
    took = jnp.take_along_axis(cand_ids, p2, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), took


def _csr_residual_topk(q, cd2, probe, seg, valid, cand, cand_ids,
                       cand_n2, cand_s, centroids, k: int):
    """Shared tail for the CSR residual-quantized kernels: ``cand`` is
    int8 residual codes (b, C, d), gathered (and for int4, unpacked)
    from the flat table; ``seg`` maps each slot back to its probe so the
    per-cell recentered query and |q−c|² term line up per candidate."""
    qcq, s_qc = _recenter_queries(q, centroids, probe)    # (b, p, d)
    qslot = jnp.take_along_axis(qcq, seg[..., None], axis=1)   # (b, C, d)
    sslot = jnp.take_along_axis(s_qc[..., 0], seg, axis=1)     # (b, C)
    doti = jnp.einsum("bcd,bcd->bc", qslot, cand,
                      preferred_element_type=jnp.int32)
    dots = doti.astype(jnp.float32) * sslot * cand_s
    cqd2 = jnp.take_along_axis(cd2, probe, axis=1)        # (b, p)
    cslot = jnp.take_along_axis(cqd2, seg, axis=1)        # (b, C)
    d2 = jnp.where(valid, cslot - 2.0 * dots + cand_n2, jnp.inf)
    neg, p2 = lax.top_k(-d2, k)
    took = jnp.take_along_axis(cand_ids, p2, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), took


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "cand_pad"))
def _score_ivf_csr_int8(q, centroids, flat_q, flat_ids, flat_n2, flat_s,
                        offsets, k: int, nprobe: int, cand_pad: int):
    cd2 = _centroid_d2(q, centroids)
    _, probe = lax.top_k(-cd2, nprobe)
    seg, pos, valid = _csr_slots(offsets, probe, cand_pad)
    cand = flat_q[pos]                                    # (b, C, d) i8
    cand_ids = jnp.where(valid, flat_ids[pos], -1)
    cand_n2 = flat_n2[pos]
    cand_s = flat_s[pos]
    return _csr_residual_topk(q, cd2, probe, seg, valid, cand, cand_ids,
                              cand_n2, cand_s, centroids, k)


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "cand_pad"))
def _score_ivf_csr_int4(q, centroids, flat_p, flat_ids, flat_n2, flat_s,
                        offsets, k: int, nprobe: int, cand_pad: int):
    cd2 = _centroid_d2(q, centroids)
    _, probe = lax.top_k(-cd2, nprobe)
    seg, pos, valid = _csr_slots(offsets, probe, cand_pad)
    cand = unpack_nibbles(flat_p[pos], q.shape[1])        # (b, C, d)
    cand_ids = jnp.where(valid, flat_ids[pos], -1)
    cand_n2 = flat_n2[pos]
    cand_s = flat_s[pos]
    return _csr_residual_topk(q, cd2, probe, seg, valid, cand, cand_ids,
                              cand_n2, cand_s, centroids, k)


# ----------------------------------------------------------- quantization
def _observe_stream(vecs: np.ndarray, observer: str, chunk: int = 65536):
    """Drive quant/'s observer over the table in chunks — ONE shared
    recipe (quant.observers.observe_stream) with the activation
    calibration stream and the int4 weight grid."""
    return observe_stream(vecs, observer, chunk)


def _quantize_table(vecs: np.ndarray, observer: str, chunk: int = 65536
                    ) -> Tuple[np.ndarray, np.ndarray, float]:
    """Symmetric int8 table quantization: PER-VECTOR scales (quant/'s
    per-output-channel weight recipe, ``s_i = amax_i / 127``, zero point
    always 0), with the table-level clipping ceiling calibrated through
    quant/'s observer machinery — the observer aggregates per-chunk
    ``(min, max, pct|x|)`` stats exactly like the activation-calibration
    stream, and a ``percentile`` observer then CLIPS outlier rows to the
    bulk's amax (finer grid everywhere else, the heavy-tail PTQ story;
    the default ``minmax`` ceiling never clips). Returns
    ``(int8 table, per-row scales, table-level wire scale)`` — the last
    is the grid int8 wire-format queries are decoded on."""
    obs = _observe_stream(vecs, observer, chunk)
    ceiling = max(float(obs.amax()), 1e-12)
    row_amax = np.abs(vecs).max(axis=1) if len(vecs) else np.zeros(0)
    amax = np.clip(row_amax, 1e-12, ceiling)
    scales = (amax / QMAX).astype(np.float32)
    q = np.clip(np.rint(vecs / scales[:, None]), -QMAX, QMAX
                ).astype(np.int8)
    return q, scales, float(obs.scale())


def _train_cells(v: np.ndarray, n_cells: int, train_size: int,
                 max_iterations: int, seed: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """KMeans cells on a seeded subsample + full-corpus assignment —
    the coarse-quantizer recipe shared by the IVF family (index.py +
    pq.py). Returns ``(centroids (C, d), assign (n,))``."""
    rng = np.random.default_rng(seed)
    if len(v) > train_size:
        sample = v[rng.choice(len(v), train_size, replace=False)]
    else:
        sample = v
    km = KMeansClustering(n_cells, max_iterations=max_iterations,
                          seed=seed)
    km.apply_to(sample)
    centroids = km.centroids.astype(np.float32)
    return centroids, _assign_all(v, centroids)


def _assign_all(v: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """Nearest-centroid assignment for a whole corpus, chunked so the
    (chunk, n_cells) distance matrix stays bounded; the final ragged
    chunk pads to the chunk size so a build compiles at most two
    programs. Shared by the IVF family (index.py + pq.py)."""
    c = jnp.asarray(centroids)
    out = np.empty(len(v), np.int64)
    for lo in range(0, len(v), _ASSIGN_CHUNK):
        chunk = v[lo:lo + _ASSIGN_CHUNK]
        n = len(chunk)
        if n < _ASSIGN_CHUNK and lo > 0:
            chunk = pad_to_bucket(chunk, _ASSIGN_CHUNK)
        out[lo:lo + n] = np.asarray(
            _assign_chunk(jnp.asarray(chunk), c))[:n]
    return out


@jax.jit
def _assign_chunk(points, centroids):
    return jnp.argmin(_centroid_d2(points, centroids), axis=1)


def _rerank_exact(table: np.ndarray, q: np.ndarray, ids: np.ndarray,
                  k: int):
    """Host-side exact re-rank of compressed-index candidates against
    the fp32 table: tie-stable ((d², id) lexicographic, the tree/oracle
    contract), pads (id −1) keep answering inf. Runs AFTER the device
    program returned — never inside the jitted scoring path."""
    safe = np.maximum(ids, 0)
    cand = table[safe]                                    # (b, rk, d)
    diff = cand - q[:, None, :]
    d2 = np.einsum("brd,brd->br", diff, diff)
    d2 = np.where(ids < 0, np.inf, d2)
    order = np.lexsort((ids, d2), axis=-1)[:, :k]
    top = np.take_along_axis(ids, order, axis=1).astype(np.int32)
    dd = np.sqrt(np.maximum(
        np.take_along_axis(d2, order, axis=1), 0.0)).astype(np.float32)
    dd[top < 0] = np.inf
    return top, dd


# ------------------------------------------------------------------ base
class _DeviceIndex:
    """Shared host-side surface: query-batch bucketing, the pow2 k
    ladder, warmup, CompileWatch accounting, npz persistence and the
    opt-in exact re-rank.

    ``rerank=r`` (any compressed index, euclidean only): the device
    program answers the top ``r·k`` approximate candidates and a host
    pass re-scores them exactly against the original fp32 vectors — kept
    on the HOST (the FAISS deployment shape: codes in HBM, full
    precision in host RAM), so ``memory_bytes()`` stays the compressed
    device footprint and recall gates stay satisfiable at high
    compression."""

    kind = "base"

    def __init__(self, vectors, *, metric: str = "euclidean",
                 int8: bool = False, int4: bool = False,
                 rerank: int = 0, observer: str = "minmax",
                 labels: Optional[Sequence[str]] = None,
                 query_policy: Optional[BucketPolicy] = None):
        v = np.asarray(vectors, np.float32)
        if v.ndim != 2 or v.shape[0] < 1:
            raise ValueError(
                f"index needs a (n, d) vector matrix; got shape {v.shape}")
        if not np.isfinite(v).all():
            raise ValueError("index vectors contain non-finite values")
        if metric not in _METRICS:
            raise ValueError(f"unsupported metric {metric!r} "
                             f"(supported: {list(_METRICS)})")
        if int8 and int4:
            raise ValueError("int8 and int4 are one codec knob — pick one")
        if rerank < 0:
            raise ValueError(f"rerank must be >= 0; got {rerank}")
        if rerank and metric != "euclidean":
            raise ValueError("rerank re-scores euclidean d² on the host "
                             "— cosine tables don't compose with it")
        if labels is not None and len(labels) != len(v):
            raise ValueError(
                f"labels length {len(labels)} != num vectors {len(v)}")
        if metric == "cosine":
            norms = np.linalg.norm(v, axis=1, keepdims=True)
            v = v / np.maximum(norms, 1e-12)
        self.metric = metric
        self.size = int(v.shape[0])
        self.dim = int(v.shape[1])
        self.int8 = bool(int8)
        self.int4 = bool(int4)
        self.rerank = int(rerank)
        self.observer = observer
        self.scale: Optional[float] = None
        self.labels = list(labels) if labels is not None else None
        self.query_policy = (query_policy if query_policy is not None
                             else BucketPolicy(floor=8, cap=4096))
        self.compile_watch = CompileWatch(f"retrieval.{self.kind}")
        self._rerank_vecs = v if self.rerank else None
        self._build(v)

    # ------------------------------------------------------------ plumbing
    def _build(self, v: np.ndarray):
        raise NotImplementedError

    def _candidates(self) -> int:
        """Vectors scored per query (the ceiling for k)."""
        raise NotImplementedError

    def _search_device(self, q, k: int):
        """Jit dispatch on an already-padded device batch; returns device
        ``(distances, indices)``. The zero-host-sync scoring path."""
        raise NotImplementedError

    @property
    def codec(self) -> str:
        """Compression rung of the stored table: fp32 / int8 / int4 (the
        PQ classes answer "pq")."""
        return "int8" if self.int8 else ("int4" if self.int4 else "fp32")

    @property
    def max_k(self) -> int:
        """Largest k a query may ask for (the per-query candidate count:
        the whole corpus for brute force, the probed candidates for
        IVF)."""
        return self._candidates()

    def _k_pad(self, k: int) -> int:
        if k < 1:
            raise ValueError(f"k must be >= 1; got {k}")
        cand = self._candidates()
        if k > cand:
            raise ValueError(
                f"k={k} exceeds the {cand} candidates this index scores "
                "per query" + (" (raise nprobe or rebuild with more "
                               "cells)" if self.kind.startswith("ivf")
                               else ""))
        return min(1 << (int(k) - 1).bit_length(), cand)

    def _rerank_k(self, k: int) -> int:
        """Candidate count the device program answers when re-ranking."""
        return max(int(k), min(self.rerank * int(k), self._candidates()))

    # -------------------------------------------------------------- search
    def search(self, queries, k: int = 10
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Batched k-NN: ``queries`` is (b, d) (a single (d,) vector is
        auto-promoted); returns ``(indices, distances)`` as (b, k) arrays,
        each row ascending by distance — the host trees' ``search``
        contract, vectorized. Dispatch pads the batch to the bucket
        ladder and ``k`` to a pow2 rung, so steady traffic reuses the
        warmed programs. With ``rerank`` on, the device answers the top
        ``rerank·k`` candidates and the host re-scores them exactly."""
        q = np.asarray(queries, np.float32)
        single = q.ndim == 1
        if single:
            q = q[None, :]
        if q.ndim != 2 or q.shape[1] != self.dim:
            raise ValueError(
                f"queries must be (b, {self.dim}); got shape {q.shape}")
        idx, dist = self._search_batch(q, int(k))
        if single:
            return idx[0], dist[0]
        return idx, dist

    def _search_batch(self, q: np.ndarray, k: int):
        k_dev = self._rerank_k(k) if self.rerank else k
        kp = self._k_pad(k_dev)
        target = self.query_policy.bucket(q.shape[0])
        qp = pad_to_bucket(q, target)
        if self.metric == "cosine":
            qp = qp / np.maximum(np.linalg.norm(qp, axis=1, keepdims=True),
                                 1e-12)
        dist, idx = self._search_device(jnp.asarray(qp), kp)
        dist = np.asarray(dist)[:q.shape[0], :k_dev]
        idx = np.asarray(idx)[:q.shape[0], :k_dev].astype(np.int32)
        if self.rerank:
            return _rerank_exact(self._rerank_vecs, q, idx, k)
        return idx, dist

    def warmup(self, max_queries: int = 64,
               ks: Sequence[int] = (10,)) -> List[Tuple[int, int]]:
        """Precompile the (query-bucket × k-rung) ladder so live traffic
        compiles nothing (the serving warmup contract). Returns the warmed
        (batch, k) pairs. With ``rerank`` on, each requested k warms its
        ``rerank·k`` device rung — the one a live search dispatches at."""
        warmed = []
        if self.rerank:
            ks = tuple(self._rerank_k(int(k)) for k in ks)
        kpads = sorted({self._k_pad(int(k)) for k in ks})
        zeros = np.zeros((1, self.dim), np.float32)
        for b in self.query_policy.buckets_up_to(max(1, int(max_queries))):
            qp = jnp.asarray(pad_to_bucket(zeros, b))
            for kp in kpads:
                d, i = self._search_device(qp, kp)
                jax.block_until_ready((d, i))
                warmed.append((b, kp))
        return warmed

    # -------------------------------------------------------------- stats
    def memory_bytes(self) -> int:
        """DEVICE-resident index bytes — the HBM footprint the
        ``retrieval_index_bytes`` gauge reports next to the planner's
        numbers (a PQ index's opt-in host-side re-rank table is NOT in
        here; see ``stats()['rerank_bytes_host']``)."""
        raise NotImplementedError

    def nbytes(self) -> int:
        """Back-compat alias of :meth:`memory_bytes`."""
        return self.memory_bytes()

    def code_bytes(self) -> int:
        """Bytes of the stored table/codes arrays alone (no norms/ids/
        centroid sidecars) — the number the int4-is-half-of-int8
        acceptance compares."""
        raise NotImplementedError

    def stats(self) -> dict:
        mb = self.memory_bytes()
        return {"kind": self.kind, "metric": self.metric,
                "size": self.size, "dim": self.dim, "int8": self.int8,
                "int4": self.int4, "codec": self.codec,
                "rerank": self.rerank,
                "rerank_bytes_host": (int(self._rerank_vecs.nbytes)
                                      if self._rerank_vecs is not None
                                      else 0),
                "scale": self.scale, "nbytes": mb, "memory_bytes": mb,
                "code_bytes": self.code_bytes(),
                "bytes_per_vector": round(mb / max(1, self.size), 2),
                "compile_watch": self.compile_watch.as_dict()}

    # --------------------------------------------------------- persistence
    def _meta(self) -> dict:
        qp = self.query_policy
        return {"kind": self.kind, "metric": self.metric,
                "int8": self.int8, "int4": self.int4,
                "rerank": self.rerank,
                "observer": self.observer,
                "scale": self.scale, "size": self.size, "dim": self.dim,
                "labels": self.labels,
                # the bucket ladder is part of the serving contract (it
                # decides which program shapes exist): it must survive
                # save/load or a reloaded replica buckets traffic
                # differently than the warmed ladder assumed
                "query_policy": {"floor": qp.floor, "cap": qp.cap,
                                 "buckets": qp._explicit}}

    def _arrays(self) -> dict:
        raise NotImplementedError

    def save(self, path: str) -> str:
        """One ``.npz``: arrays + a JSON meta entry. ``load_index`` (or
        ``cls.load``) round-trips it — the hot-swap rebuild currency. A
        re-rank index's fp32 table rides along (it is the recall
        contract; it reloads host-side, never to device)."""
        arrays = {k: np.asarray(a) for k, a in self._arrays().items()}
        if self._rerank_vecs is not None:
            arrays["rerank_vecs"] = self._rerank_vecs
        arrays["meta_json"] = np.frombuffer(
            json.dumps(self._meta()).encode(), dtype=np.uint8)
        np.savez(path, **arrays)
        return path

    def _restore_common(self, meta: dict, arrays: Optional[dict] = None):
        """Rehydrate the base fields ``load_index`` hands every kind."""
        self.metric = meta["metric"]
        self.size = int(meta["size"])
        self.dim = int(meta["dim"])
        self.int8 = bool(meta["int8"])
        self.int4 = bool(meta.get("int4", False))
        self.rerank = int(meta.get("rerank", 0) or 0)
        self._rerank_vecs = (np.asarray((arrays or {}).get("rerank_vecs"),
                                        np.float32)
                             if self.rerank and arrays
                             and "rerank_vecs" in arrays else None)
        if self.rerank and self._rerank_vecs is None:
            raise ValueError("index metadata says rerank but the npz "
                             "carries no rerank_vecs table")
        self.observer = meta.get("observer", "minmax")
        self.scale = meta.get("scale")
        self.labels = meta.get("labels")
        qp = meta.get("query_policy") or {}
        self.query_policy = BucketPolicy(floor=qp.get("floor", 8),
                                         cap=qp.get("cap", 4096),
                                         buckets=qp.get("buckets"))
        self.compile_watch = CompileWatch(f"retrieval.{self.kind}")


# ----------------------------------------------------------- brute force
class BruteForceIndex(_DeviceIndex):
    """Exact top-k: every query scores the whole device-resident corpus
    in one fused matmul + top_k. The recall oracle for IVF/int8/int4/PQ.
    ``int8=True`` quantizes the table ×4; ``int4=True`` packs two codes
    per byte for ×8 over float32 (codes exactly half the int8 table's)."""

    kind = "brute"

    def _build(self, v: np.ndarray):
        if self.int8:
            q, scales, self.scale = _quantize_table(v, self.observer)
            self._vecs = jnp.asarray(q)
            self._scales = jnp.asarray(scales)
            # norms of the DEQUANTIZED vectors: consistent with the
            # quantized dot product, so d² stays unbiased
            deq = q.astype(np.float32) * scales[:, None]
            self._vnorm2 = jnp.asarray(np.sum(deq ** 2, axis=1))
        elif self.int4:
            packed, scales, wire4 = quantize_int4(v, observer=self.observer)
            # wire scale stays the int8 whole-vector grid: clients keep
            # quantizing queries to int8 regardless of the table codec —
            # same observed ceiling quantize_int4 just streamed, regridded
            # (no second corpus pass)
            self.scale = float(wire4 * QMAX4 / QMAX)
            self._vecs = jnp.asarray(packed)
            self._scales = jnp.asarray(scales)
            deq = (unpack_nibbles_host(packed, self.dim).astype(np.float32)
                   * scales[:, None])
            self._vnorm2 = jnp.asarray(np.sum(deq ** 2, axis=1))
        else:
            self._vecs = jnp.asarray(v)
            self._scales = None
            self._vnorm2 = jnp.asarray(np.sum(
                v.astype(np.float64) ** 2, axis=1).astype(np.float32))
        self._wire()

    def _wire(self):
        if self.int4:
            from deeplearning4j_tpu.perf import pallas as _pk
            from deeplearning4j_tpu.perf.pallas import adc as _pk_adc
            self._score = self.compile_watch.wrap(
                _pk.kernel_select("int4_dot", _pk_adc.score_brute_int4,
                                  _score_brute_int4,
                                  _pk_adc.brute_int4_supported),
                "retrieval.brute_int4")
        elif self.int8:
            self._score = self.compile_watch.wrap(_score_brute_int8,
                                                  "retrieval.brute_int8")
        else:
            self._score = self.compile_watch.wrap(_score_brute,
                                                  "retrieval.brute")

    def _candidates(self) -> int:
        return self.size

    def _search_device(self, q, k: int):
        if self.int8 or self.int4:
            return self._score(q, self._vecs, self._vnorm2, self._scales,
                               k, self.metric)
        return self._score(q, self._vecs, self._vnorm2, k, self.metric)

    def memory_bytes(self) -> int:
        n = int(self._vecs.nbytes + self._vnorm2.nbytes)
        if self._scales is not None:
            n += int(self._scales.nbytes)
        return n

    def code_bytes(self) -> int:
        return int(self._vecs.nbytes)

    def _arrays(self) -> dict:
        out = {"vecs": self._vecs, "vnorm2": self._vnorm2}
        if self._scales is not None:
            out["scales"] = self._scales
        return out

    @classmethod
    def load(cls, path: str) -> "BruteForceIndex":
        return _load_as(cls, path)


# ------------------------------------------------------------------- IVF
class IVFIndex(_DeviceIndex):
    """Inverted-file index: KMeans cells, ``nprobe`` probed per query —
    work scales with the probed candidates instead of ``n``. Cells are
    learned on a seeded subsample (``train_size``) and every vector is
    then assigned to its final nearest centroid in chunked jitted passes.

    ``layout="dense"`` stores padded ``(n_cells, cap, d)`` blocks (cap =
    the LARGEST cell — skew burns ``cap − count`` padded slots per
    cell); ``layout="csr"`` stores the corpus flat in cell-major order +
    a ``(n_cells+1,)`` offsets array and pads only the per-query gathered
    candidate axis to one pow2 rung, so resident memory is exactly ``n``
    rows at identical query results (parity-asserted in tier-1)."""

    kind = "ivf"

    def __init__(self, vectors, *, n_cells: Optional[int] = None,
                 nprobe: int = 8, train_size: int = 100_000,
                 max_iterations: int = 25, seed: int = 123,
                 layout: str = "dense", **kwargs):
        if kwargs.get("metric", "euclidean") != "euclidean":
            raise ValueError("IVFIndex supports euclidean only (KMeans "
                             "cells are euclidean centroids)")
        if layout not in ("dense", "csr"):
            raise ValueError(f"unknown cell layout {layout!r} "
                             "(known: 'dense', 'csr')")
        n = int(np.asarray(vectors).shape[0])
        self.n_cells = (max(1, int(round(n ** 0.5))) if n_cells is None
                        else int(n_cells))
        if self.n_cells > n:
            raise ValueError(f"n_cells={self.n_cells} exceeds corpus "
                             f"size {n}")
        self.nprobe = min(int(nprobe), self.n_cells)
        if self.nprobe < 1:
            raise ValueError(f"nprobe must be >= 1; got {nprobe}")
        self.train_size = int(train_size)
        self.max_iterations = int(max_iterations)
        self.seed = int(seed)
        self.layout = layout
        super().__init__(vectors, **kwargs)

    def _build(self, v: np.ndarray):
        centroids, assign = _train_cells(v, self.n_cells, self.train_size,
                                         self.max_iterations, self.seed)
        counts = np.bincount(assign, minlength=self.n_cells)
        self.cell_counts = counts
        self.cap = max(1, int(counts.max()))
        self._centroids = jnp.asarray(centroids)
        order = np.argsort(assign, kind="stable")
        if self.int8 or self.int4:
            # RESIDUAL encoding: quantize v − centroid[cell], whose amax
            # is the cell radius — an order finer grid than whole-vector
            # codes (measured: recall delta ~5e-3 vs ~5e-2 on clustered
            # corpora). The kernel recenters queries per probed cell.
            # The published WIRE scale must stay in the query's space
            # (whole-vector magnitudes): a client quantizing queries on
            # the residual grid would clip them at the cell radius.
            res = v - centroids[assign]
            if self.int4:
                codes, scales, _ = quantize_int4(res,
                                                 observer=self.observer)
                deq = (unpack_nibbles_host(codes, self.dim)
                       .astype(np.float32) * scales[:, None])
            else:
                codes, scales, _ = _quantize_table(res, self.observer)
                deq = codes.astype(np.float32) * scales[:, None]
            self.scale = float(_observe_stream(v, self.observer).scale())
            norm2 = np.sum(deq ** 2, axis=1).astype(np.float32)  # |r̂|²
            table = codes
        else:
            scales = None
            norm2 = np.sum(v.astype(np.float64) ** 2,
                           axis=1).astype(np.float32)
            table = v
        if self.layout == "csr":
            self._build_csr(table, scales, norm2, order, counts)
        else:
            self._build_dense(table, scales, norm2, order, counts)
        self._wire()

    def _build_dense(self, table, scales, norm2, order, counts):
        width = table.shape[1]  # packed width for int4, d otherwise
        cells = np.zeros((self.n_cells, self.cap, width), table.dtype)
        ids = np.full((self.n_cells, self.cap), -1, np.int32)
        vnorm2 = np.full((self.n_cells, self.cap), np.inf, np.float32)
        ofs = 0
        for c in range(self.n_cells):
            m = int(counts[c])
            rows = order[ofs:ofs + m]
            ofs += m
            cells[c, :m] = table[rows]
            ids[c, :m] = rows
            vnorm2[c, :m] = norm2[rows]
        self._cells = jnp.asarray(cells)
        self._ids = jnp.asarray(ids)
        self._vnorm2 = jnp.asarray(vnorm2)
        if scales is not None:
            cscales = np.ones((self.n_cells, self.cap), np.float32)
            cscales[ids >= 0] = scales[ids[ids >= 0]]
            self._scales = jnp.asarray(cscales)
        else:
            self._scales = None
        self._flat = self._flat_ids = self._offsets = None
        self._flat_scales = None
        self.cand_pad = None

    def _build_csr(self, table, scales, norm2, order, counts):
        self._flat = jnp.asarray(table[order])
        self._flat_ids = jnp.asarray(order.astype(np.int32))
        self._vnorm2 = jnp.asarray(norm2[order])
        self._offsets = jnp.asarray(np.concatenate(
            [[0], np.cumsum(counts)]).astype(np.int32))
        self._flat_scales = (jnp.asarray(scales[order])
                            if scales is not None else None)
        # the per-query gathered candidate axis: pow2 rung covering the
        # worst case (the nprobe FULLEST cells) — a static shape, so the
        # warmed ladder stays one program per (bucket, k-rung)
        worst = int(np.sort(counts)[-self.nprobe:].sum())
        self.cand_pad = _pow2ceil(max(1, worst))
        self._cells = self._ids = None
        self._scales = None

    def _wire(self):
        tag = {"dense": "", "csr": "_csr"}[self.layout]
        codec = {"fp32": "", "int8": "_int8", "int4": "_int4"}[self.codec]
        name = f"retrieval.ivf{tag}{codec}"
        kernels = {
            "retrieval.ivf": _score_ivf,
            "retrieval.ivf_int8": _score_ivf_int8,
            "retrieval.ivf_int4": _score_ivf_int4,
            "retrieval.ivf_csr": _score_ivf_csr,
            "retrieval.ivf_csr_int8": _score_ivf_csr_int8,
            "retrieval.ivf_csr_int4": _score_ivf_csr_int4,
        }
        self._score = self.compile_watch.wrap(kernels[name], name)

    def _candidates(self) -> int:
        if self.layout == "csr":
            return min(self.size, self.cand_pad)
        return min(self.size, self.nprobe * self.cap)

    def _search_device(self, q, k: int):
        if self.layout == "csr":
            if self.int8 or self.int4:
                return self._score(q, self._centroids, self._flat,
                                   self._flat_ids, self._vnorm2,
                                   self._flat_scales, self._offsets,
                                   k, self.nprobe, self.cand_pad)
            return self._score(q, self._centroids, self._flat,
                               self._flat_ids, self._vnorm2,
                               self._offsets, k, self.nprobe,
                               self.cand_pad)
        if self.int8 or self.int4:
            return self._score(q, self._centroids, self._cells, self._ids,
                               self._vnorm2, self._scales, k, self.nprobe)
        return self._score(q, self._centroids, self._cells, self._ids,
                           self._vnorm2, k, self.nprobe)

    def memory_bytes(self) -> int:
        n = int(self._vnorm2.nbytes + self._centroids.nbytes)
        if self.layout == "csr":
            n += int(self._flat.nbytes + self._flat_ids.nbytes
                     + self._offsets.nbytes)
            if self._flat_scales is not None:
                n += int(self._flat_scales.nbytes)
        else:
            n += int(self._cells.nbytes + self._ids.nbytes)
            if self._scales is not None:
                n += int(self._scales.nbytes)
        return n

    def code_bytes(self) -> int:
        return int(self._flat.nbytes if self.layout == "csr"
                   else self._cells.nbytes)

    def stats(self) -> dict:
        st = super().stats()
        st.update(n_cells=self.n_cells, nprobe=self.nprobe, cap=self.cap,
                  layout=self.layout,
                  empty_cells=int((self.cell_counts == 0).sum()))
        if self.layout == "csr":
            st["cand_pad"] = self.cand_pad
        return st

    def _meta(self) -> dict:
        m = super()._meta()
        m.update(n_cells=self.n_cells, nprobe=self.nprobe, cap=self.cap,
                 train_size=self.train_size, seed=self.seed,
                 max_iterations=self.max_iterations, layout=self.layout,
                 cand_pad=self.cand_pad)
        return m

    def _arrays(self) -> dict:
        out = {"centroids": self._centroids, "vnorm2": self._vnorm2,
               "cell_counts": self.cell_counts}
        if self.layout == "csr":
            out.update(flat=self._flat, flat_ids=self._flat_ids,
                       offsets=self._offsets)
            if self._flat_scales is not None:
                out["flat_scales"] = self._flat_scales
        else:
            out.update(cells=self._cells, ids=self._ids)
            if self._scales is not None:
                out["scales"] = self._scales
        return out

    @classmethod
    def load(cls, path: str) -> "IVFIndex":
        return _load_as(cls, path)


# ----------------------------------------------------------- persistence
def _load_as(cls, path: str) -> "_DeviceIndex":
    idx = load_index(path)
    if not isinstance(idx, cls):
        raise ValueError(f"{path} holds a {type(idx).__name__}, "
                         f"not a {cls.__name__}")
    return idx


def load_index(path: str) -> "_DeviceIndex":
    """Rebuild a saved index (``save()``'s npz) without re-clustering or
    re-quantizing — the fast path for replica start and hot-swap."""
    with np.load(path) as z:
        meta = json.loads(bytes(z["meta_json"].tobytes()).decode())
        arrays = {k: z[k] for k in z.files if k != "meta_json"}
    kind = meta.get("kind")
    if kind in ("pq", "ivf_pq"):
        from deeplearning4j_tpu.retrieval import pq
        return pq._load_pq(kind, meta, arrays)
    if kind == "brute":
        idx = BruteForceIndex.__new__(BruteForceIndex)
        idx._restore_common(meta, arrays)
        idx._vecs = jnp.asarray(arrays["vecs"])
        idx._vnorm2 = jnp.asarray(arrays["vnorm2"])
        idx._scales = (jnp.asarray(arrays["scales"])
                       if "scales" in arrays else None)
        idx._wire()
        return idx
    if kind != "ivf":
        raise ValueError(f"unknown index kind {kind!r} in {path}")
    idx = IVFIndex.__new__(IVFIndex)
    idx._restore_common(meta, arrays)
    idx.n_cells = int(meta["n_cells"])
    idx.nprobe = int(meta["nprobe"])
    idx.cap = int(meta["cap"])
    idx.train_size = int(meta.get("train_size", 100_000))
    idx.seed = int(meta.get("seed", 123))
    idx.max_iterations = int(meta.get("max_iterations", 25))
    idx.layout = meta.get("layout", "dense")
    idx.cand_pad = meta.get("cand_pad")
    idx.cell_counts = arrays["cell_counts"]
    idx._centroids = jnp.asarray(arrays["centroids"])
    idx._vnorm2 = jnp.asarray(arrays["vnorm2"])
    if idx.layout == "csr":
        idx._flat = jnp.asarray(arrays["flat"])
        idx._flat_ids = jnp.asarray(arrays["flat_ids"])
        idx._offsets = jnp.asarray(arrays["offsets"])
        idx._flat_scales = (jnp.asarray(arrays["flat_scales"])
                            if "flat_scales" in arrays else None)
        idx._cells = idx._ids = None
        idx._scales = None
    else:
        idx._cells = jnp.asarray(arrays["cells"])
        idx._ids = jnp.asarray(arrays["ids"])
        idx._scales = (jnp.asarray(arrays["scales"])
                       if "scales" in arrays else None)
        idx._flat = idx._flat_ids = idx._offsets = None
        idx._flat_scales = None
    idx._wire()
    return idx

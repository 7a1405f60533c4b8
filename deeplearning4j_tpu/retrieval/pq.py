"""Product quantization: 1-byte-per-subspace codes scored through an ADC
lookup table.

int8 tables (index.py) stop at 4× over float32 because every dimension
still costs a byte. PQ (Jégou et al., "Product Quantization for Nearest
Neighbor Search", TPAMI 2011) breaks the per-dimension coupling: split
``d`` into ``M`` subspaces, learn a 256-entry codebook per subspace with
the existing chunked-Lloyd :class:`KMeansClustering`, and store ONE BYTE
per subspace per vector — ``M`` bytes instead of ``4d``, 8–16× at equal
recall on clustered corpora (the FAISS device-batched realization,
Johnson et al. 2017, is the shape of the kernels here).

Scoring is asymmetric distance computation (ADC): one jitted program
builds the query-to-centroid lookup table

    LUT[b, m, j] = |q_m − c_{m,j}|²          (b, M, ksub)

— a batched matmul against the codebooks — then accumulates each stored
vector's distance by gathering its ``M`` codes through the LUT:

    d²(q, v) ≈ Σ_m LUT[b, m, code_m(v)]

entirely in jnp: zero host syncs in the scoring path (trace_check-
asserted), zero steady-state compiles on the existing pow2 query-bucket
× k-rung ladder (CompileWatch-asserted).

- :class:`PQIndex` — flat ADC over the whole code table.
- :class:`IVFPQIndex` — IVF cells compose PQ over RESIDUALS vs the cell
  centroid (exactly the int8 residual story one rung further): codes
  live in the CSR flat layout (cell-major codes + offsets — no dense
  ``cap − count`` padding waste), the LUT is built per probed cell from
  the recentered query, and candidates gather through the same segment
  arithmetic as the CSR int8 kernels.
- ``rerank=r`` — opt-in exact re-rank: the device program returns the
  top ``r·k`` ADC candidates and a host-side pass re-scores them against
  the original fp32 table (kept on the HOST — the FAISS deployment
  shape: codes in HBM, full-precision vectors in host RAM), recovering
  the recall ADC's quantization gives up at high compression.
  ``memory_bytes()`` stays the DEVICE footprint; the host table is
  reported as ``stats()['rerank_bytes_host']``.

Gate PQ indexes with ``gates.assert_recall_within`` against a float
:class:`~deeplearning4j_tpu.retrieval.index.BruteForceIndex` — the
tier-1 suite holds recall@10 within 0.05 of brute force with re-rank on,
at ≥ 8× compression (``test_zz_pq.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.clustering.kmeans import KMeansClustering
from deeplearning4j_tpu.perf.bucketing import pad_to_bucket
from deeplearning4j_tpu.retrieval.index import (_DeviceIndex, _centroid_d2,
                                                _csr_slots, _pow2ceil,
                                                _train_cells)

__all__ = ["PQCodec", "PQIndex", "IVFPQIndex"]

_ENCODE_CHUNK = 16384


# --------------------------------------------------------------- kernels
# (DLT013/DLT014 scope: pure jnp — the ADC path never touches the host)

def _adc_lut(qr, codebooks):
    """|q_m − c_{m,j}|² for every (query, subspace, codeword):
    ``qr`` (b, M, dsub) × ``codebooks`` (M, ksub, dsub) → (b, M, ksub).
    The einsum is the batched matmul the MXU runs; expanded form so the
    (b, M, ksub, dsub) difference tensor never materializes."""
    cn2 = jnp.sum(codebooks * codebooks, axis=2)          # (M, ksub)
    dots = jnp.einsum("bmd,mkd->bmk", qr, codebooks, precision="highest")
    qn2 = jnp.sum(qr * qr, axis=2)[..., None]             # (b, M, 1)
    return cn2[None] - 2.0 * dots + qn2


@jax.jit
def _encode_chunk(x, codebooks):
    """Nearest codeword per subspace for a chunk: (c, M, dsub) → (c, M)
    uint8 codes (ksub ≤ 256 by construction)."""
    return jnp.argmin(_adc_lut(x, codebooks), axis=2).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("k",))
def _score_pq(q, codebooks, codes, k: int):
    """Flat ADC: LUT once per query, then M gathers accumulate the code
    table's distances — the (b, n) accumulator is the only large
    intermediate (no (b, n, M) gather tensor)."""
    b = q.shape[0]
    m_count, ksub, dsub = codebooks.shape
    lut = _adc_lut(q.reshape(b, m_count, dsub), codebooks)
    d2 = jnp.zeros((b, codes.shape[0]), jnp.float32)
    for m in range(m_count):                       # static unroll over M
        d2 = d2 + jnp.take(lut[:, m, :], codes[:, m].astype(jnp.int32),
                           axis=1)
    neg, idx = lax.top_k(-d2, k)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "cand_pad"))
def _score_ivf_pq(q, centroids, codebooks, flat_codes, flat_ids, offsets,
                  k: int, nprobe: int, cand_pad: int):
    """IVF-PQ over residuals in the CSR layout: the LUT is built per
    probed cell from the RECENTERED query (|q − v|² ≈ Σ_m |qc_m − r̂_m|²
    with qc = q − c, the FAISS residual recipe — the centroid term is
    folded into the LUT), candidates gather through the CSR segment
    arithmetic, and each slot reads its cell's LUT via a fused
    (segment, code) flat-index gather."""
    b = q.shape[0]
    m_count, ksub, dsub = codebooks.shape
    cd2 = _centroid_d2(q, centroids)
    _, probe = lax.top_k(-cd2, nprobe)                    # (b, p)
    qc = q[:, None, :] - centroids[probe]                 # (b, p, d)
    lut = _adc_lut(qc.reshape(b * nprobe, m_count, dsub),
                   codebooks).reshape(b, nprobe, m_count, ksub)
    seg, pos, valid = _csr_slots(offsets, probe, cand_pad)
    d2 = jnp.zeros((b, cand_pad), jnp.float32)
    for m in range(m_count):                       # static unroll over M
        lut_m = lut[:, :, m, :].reshape(b, nprobe * ksub)
        code_m = flat_codes[pos, m].astype(seg.dtype)
        d2 = d2 + jnp.take_along_axis(lut_m, seg * ksub + code_m, axis=1)
    d2 = jnp.where(valid, d2, jnp.inf)
    ids = jnp.where(valid, flat_ids[pos], -1)
    neg, p2 = lax.top_k(-d2, k)
    took = jnp.take_along_axis(ids, p2, axis=1)
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), took


# ----------------------------------------------------------------- codec
class PQCodec:
    """Per-subspace codebooks + encoder. ``train`` runs one chunked-Lloyd
    KMeans per subspace (256 codewords by default — 1 byte each);
    ``encode`` assigns codes in fixed-size jitted chunks (at most two
    compiled programs per corpus, the ``_assign_all`` discipline)."""

    def __init__(self, M: int, ksub: int = 256, *, seed: int = 123,
                 max_iterations: int = 25):
        if M < 1:
            raise ValueError(f"M must be >= 1; got {M}")
        if not 2 <= int(ksub) <= 256:
            raise ValueError(f"ksub must be in [2, 256] (codes are one "
                             f"byte); got {ksub}")
        self.M = int(M)
        self.ksub = int(ksub)
        self.seed = int(seed)
        self.max_iterations = int(max_iterations)
        self.dsub: Optional[int] = None
        self.codebooks: Optional[np.ndarray] = None  # (M, ksub_eff, dsub)

    def train(self, sample) -> "PQCodec":
        s = np.asarray(sample, np.float32)
        if s.ndim != 2 or not len(s):
            raise ValueError(f"PQ training sample must be (t, d); got "
                             f"shape {s.shape}")
        d = s.shape[1]
        if d % self.M:
            raise ValueError(
                f"M={self.M} subspaces must divide d={d} evenly — pick an "
                "M that divides the embedding width")
        self.dsub = d // self.M
        ksub_eff = min(self.ksub, len(s))
        books = []
        for m in range(self.M):
            km = KMeansClustering(ksub_eff,
                                  max_iterations=self.max_iterations,
                                  seed=self.seed + m)
            km.apply_to(s[:, m * self.dsub:(m + 1) * self.dsub])
            books.append(km.centroids.astype(np.float32))
        self.codebooks = np.stack(books)
        return self

    @classmethod
    def _from_codebooks(cls, codebooks: np.ndarray, *, seed: int = 123,
                        max_iterations: int = 25) -> "PQCodec":
        cb = np.asarray(codebooks, np.float32)
        codec = cls(cb.shape[0], max(2, cb.shape[1]), seed=seed,
                    max_iterations=max_iterations)
        codec.dsub = int(cb.shape[2])
        codec.codebooks = cb
        return codec

    def encode(self, vecs, chunk: int = _ENCODE_CHUNK) -> np.ndarray:
        """(n, d) → (n, M) uint8 codes, chunked so the build never holds
        more than one (chunk, M, ksub) LUT on device."""
        if self.codebooks is None:
            raise ValueError("codec is not trained")
        v = np.asarray(vecs, np.float32)
        cb = jnp.asarray(self.codebooks)
        out = np.empty((len(v), self.M), np.uint8)
        for lo in range(0, len(v), chunk):
            c = v[lo:lo + chunk]
            n = len(c)
            if n < chunk and lo > 0:
                c = pad_to_bucket(c, chunk)
            x = c.reshape(len(c), self.M, self.dsub)
            out[lo:lo + n] = np.asarray(
                _encode_chunk(jnp.asarray(x), cb))[:n]
        return out

    def decode(self, codes: np.ndarray) -> np.ndarray:
        """fp32 reconstruction of encoded vectors (host-side — the
        distortion/test surface, never the scoring path)."""
        c = np.asarray(codes)
        return np.concatenate([self.codebooks[m][c[:, m]]
                               for m in range(self.M)], axis=1)

    def distortion(self, vecs, codes) -> float:
        """Mean squared reconstruction error per vector — the
        ``retrieval_pq_distortion`` gauge."""
        v = np.asarray(vecs, np.float32)
        rec = self.decode(codes)
        return float(np.mean(np.sum((v - rec) ** 2, axis=1)))


# -------------------------------------------------------------- PQIndex
class PQIndex(_DeviceIndex):
    """Flat PQ: the whole corpus as (n, M) uint8 codes + (M, ksub, dsub)
    codebooks on device — M bytes/vector against 4d fp32 — scored by one
    jitted ADC program. ``rerank=r`` re-scores the top r·k candidates
    exactly against the host-side fp32 table."""

    kind = "pq"

    def __init__(self, vectors, *, M: int = 8, ksub: int = 256,
                 rerank: int = 0, train_size: int = 100_000,
                 max_iterations: int = 25, seed: int = 123, **kwargs):
        if kwargs.get("metric", "euclidean") != "euclidean":
            raise ValueError("PQ indexes support euclidean only "
                             "(codebooks are euclidean centroids)")
        if kwargs.pop("int8", False) or kwargs.pop("int4", False):
            raise ValueError("PQ is its own codec — int8/int4 do not "
                             "compose with PQ codes")
        self.M = int(M)
        self.ksub = int(ksub)
        self.train_size = int(train_size)
        self.max_iterations = int(max_iterations)
        self.seed = int(seed)
        super().__init__(vectors, rerank=rerank, **kwargs)

    @property
    def codec(self) -> str:
        return "pq"

    def _build(self, v: np.ndarray):
        if v.shape[1] % self.M:
            raise ValueError(f"M={self.M} subspaces must divide "
                             f"d={v.shape[1]} evenly")
        rng = np.random.default_rng(self.seed)
        if len(v) > self.train_size:
            sample = v[rng.choice(len(v), self.train_size, replace=False)]
        else:
            sample = v
        codec = PQCodec(self.M, self.ksub, seed=self.seed,
                        max_iterations=self.max_iterations)
        codec.train(sample)
        codes = codec.encode(v)
        # distortion on a seeded uniform subsample (a prefix would bias
        # the rebuild-signal gauge on cluster- or time-ordered corpora)
        probe = rng.choice(len(v), min(len(v), 4096), replace=False)
        self.pq_distortion = codec.distortion(v[probe], codes[probe])
        self._finish(codec, codes)

    def _finish(self, codec: PQCodec, codes: np.ndarray):
        self.pq = codec
        self._codes = jnp.asarray(codes)
        self._codebooks = jnp.asarray(codec.codebooks)
        from deeplearning4j_tpu.perf import pallas as _pk
        from deeplearning4j_tpu.perf.pallas import adc as _pk_adc
        self._score = self.compile_watch.wrap(
            _pk.kernel_select("adc_pq", _pk_adc.score_pq, _score_pq,
                              _pk_adc.pq_supported),
            "retrieval.pq")

    def _candidates(self) -> int:
        return self.size

    def _search_device(self, q, k: int):
        return self._score(q, self._codebooks, self._codes, k)

    def memory_bytes(self) -> int:
        return int(self._codes.nbytes + self._codebooks.nbytes)

    def code_bytes(self) -> int:
        return int(self._codes.nbytes)

    def stats(self) -> dict:
        st = super().stats()
        st.update(M=self.M, ksub=int(self._codebooks.shape[1]),
                  dsub=int(self._codebooks.shape[2]),
                  pq_distortion=self.pq_distortion)
        return st

    def _meta(self) -> dict:
        m = super()._meta()
        m.update(M=self.M, ksub=self.ksub,
                 train_size=self.train_size, seed=self.seed,
                 max_iterations=self.max_iterations,
                 pq_distortion=self.pq_distortion)
        return m

    def _arrays(self) -> dict:
        return {"codes": self._codes, "codebooks": self._codebooks}

    @classmethod
    def load(cls, path: str) -> "PQIndex":
        from deeplearning4j_tpu.retrieval.index import _load_as
        return _load_as(cls, path)


# ----------------------------------------------------------- IVFPQIndex
class IVFPQIndex(_DeviceIndex):
    """IVF cells composing PQ over residuals, stored CSR-flat: cell-major
    (n, M) codes + offsets — no dense padding waste — probed and gathered
    by the same segment arithmetic as the CSR int8 kernels, scored
    through a per-probed-cell ADC LUT over the recentered query."""

    kind = "ivf_pq"

    def __init__(self, vectors, *, n_cells: Optional[int] = None,
                 nprobe: int = 8, M: int = 8, ksub: int = 256,
                 rerank: int = 0, train_size: int = 100_000,
                 max_iterations: int = 25, seed: int = 123, **kwargs):
        if kwargs.get("metric", "euclidean") != "euclidean":
            raise ValueError("PQ indexes support euclidean only "
                             "(codebooks are euclidean centroids)")
        if kwargs.pop("int8", False) or kwargs.pop("int4", False):
            raise ValueError("PQ is its own codec — int8/int4 do not "
                             "compose with PQ codes")
        n = int(np.asarray(vectors).shape[0])
        self.n_cells = (max(1, int(round(n ** 0.5))) if n_cells is None
                        else int(n_cells))
        if self.n_cells > n:
            raise ValueError(f"n_cells={self.n_cells} exceeds corpus "
                             f"size {n}")
        self.nprobe = min(int(nprobe), self.n_cells)
        if self.nprobe < 1:
            raise ValueError(f"nprobe must be >= 1; got {nprobe}")
        self.M = int(M)
        self.ksub = int(ksub)
        self.train_size = int(train_size)
        self.max_iterations = int(max_iterations)
        self.seed = int(seed)
        super().__init__(vectors, rerank=rerank, **kwargs)

    @property
    def codec(self) -> str:
        return "pq"

    def _build(self, v: np.ndarray):
        if v.shape[1] % self.M:
            raise ValueError(f"M={self.M} subspaces must divide "
                             f"d={v.shape[1]} evenly")
        centroids, assign = _train_cells(v, self.n_cells, self.train_size,
                                         self.max_iterations, self.seed)
        res = v - centroids[assign]
        rng = np.random.default_rng(self.seed)
        if len(res) > self.train_size:
            sample = res[rng.choice(len(res), self.train_size,
                                    replace=False)]
        else:
            sample = res
        codec = PQCodec(self.M, self.ksub, seed=self.seed,
                        max_iterations=self.max_iterations)
        codec.train(sample)
        codes = codec.encode(res)
        probe = rng.choice(len(res), min(len(res), 4096), replace=False)
        self.pq_distortion = codec.distortion(res[probe], codes[probe])
        counts = np.bincount(assign, minlength=self.n_cells)
        order = np.argsort(assign, kind="stable")
        self._finish(codec, codes, counts, order, centroids)

    def _finish(self, codec: PQCodec, codes: np.ndarray,
                counts: np.ndarray, order: np.ndarray,
                centroids: np.ndarray):
        self.pq = codec
        self.cell_counts = counts
        self.cap = max(1, int(counts.max()))
        worst = int(np.sort(counts)[-self.nprobe:].sum())
        self.cand_pad = _pow2ceil(max(1, worst))
        self._centroids = jnp.asarray(centroids)
        self._codebooks = jnp.asarray(codec.codebooks)
        self._flat_codes = jnp.asarray(np.asarray(codes)[order])
        self._flat_ids = jnp.asarray(order.astype(np.int32))
        self._offsets = jnp.asarray(np.concatenate(
            [[0], np.cumsum(counts)]).astype(np.int32))
        from deeplearning4j_tpu.perf import pallas as _pk
        from deeplearning4j_tpu.perf.pallas import adc as _pk_adc
        self._score = self.compile_watch.wrap(
            _pk.kernel_select("adc_ivf_pq", _pk_adc.score_ivf_pq,
                              _score_ivf_pq),
            "retrieval.ivf_pq")

    def _candidates(self) -> int:
        return min(self.size, self.cand_pad)

    def _search_device(self, q, k: int):
        return self._score(q, self._centroids, self._codebooks,
                           self._flat_codes, self._flat_ids,
                           self._offsets, k, self.nprobe, self.cand_pad)

    def memory_bytes(self) -> int:
        return int(self._flat_codes.nbytes + self._codebooks.nbytes
                   + self._centroids.nbytes + self._flat_ids.nbytes
                   + self._offsets.nbytes)

    def code_bytes(self) -> int:
        return int(self._flat_codes.nbytes)

    def stats(self) -> dict:
        st = super().stats()
        st.update(M=self.M, ksub=int(self._codebooks.shape[1]),
                  dsub=int(self._codebooks.shape[2]),
                  n_cells=self.n_cells, nprobe=self.nprobe, cap=self.cap,
                  layout="csr", cand_pad=self.cand_pad,
                  empty_cells=int((self.cell_counts == 0).sum()),
                  pq_distortion=self.pq_distortion)
        return st

    def _meta(self) -> dict:
        m = super()._meta()
        m.update(M=self.M, ksub=self.ksub,
                 n_cells=self.n_cells, nprobe=self.nprobe, cap=self.cap,
                 cand_pad=self.cand_pad, train_size=self.train_size,
                 seed=self.seed, max_iterations=self.max_iterations,
                 pq_distortion=self.pq_distortion)
        return m

    def _arrays(self) -> dict:
        out = {"centroids": self._centroids,
               "codebooks": self._codebooks,
               "flat_codes": self._flat_codes,
               "flat_ids": self._flat_ids,
               "offsets": self._offsets,
               "cell_counts": self.cell_counts}
        return out

    @classmethod
    def load(cls, path: str) -> "IVFPQIndex":
        from deeplearning4j_tpu.retrieval.index import _load_as
        return _load_as(cls, path)


# ------------------------------------------------------------- assembly
# (the streaming builder's seam: construct an index from already-encoded
# codes WITHOUT the fp32 matrix ever existing in one piece)

def _bare(cls, *, size, dim, labels, seed, train_size, max_iterations,
          M, ksub, distortion):
    idx = cls.__new__(cls)
    idx._restore_common({"metric": "euclidean", "size": int(size),
                         "dim": int(dim), "int8": False, "int4": False,
                         "observer": "minmax", "scale": None,
                         "labels": labels})
    idx.M = int(M)
    idx.ksub = int(ksub)
    idx.train_size = int(train_size)
    idx.seed = int(seed)
    idx.max_iterations = int(max_iterations)
    idx.pq_distortion = distortion
    return idx


def assemble_pq_index(codec: PQCodec, codes: np.ndarray, *, size, dim,
                      labels=None, distortion=None, seed=123,
                      train_size=100_000, max_iterations=25) -> "PQIndex":
    idx = _bare(PQIndex, size=size, dim=dim, labels=labels, seed=seed,
                train_size=train_size, max_iterations=max_iterations,
                M=codec.M, ksub=codec.ksub, distortion=distortion)
    idx._finish(codec, codes)
    return idx


def assemble_ivf_pq_index(codec: PQCodec, codes: np.ndarray,
                          assign: np.ndarray, centroids: np.ndarray, *,
                          nprobe=8, size, dim, labels=None,
                          distortion=None, seed=123, train_size=100_000,
                          max_iterations=25) -> "IVFPQIndex":
    idx = _bare(IVFPQIndex, size=size, dim=dim, labels=labels, seed=seed,
                train_size=train_size, max_iterations=max_iterations,
                M=codec.M, ksub=codec.ksub, distortion=distortion)
    idx.n_cells = int(len(centroids))
    idx.nprobe = min(int(nprobe), idx.n_cells)
    counts = np.bincount(assign, minlength=idx.n_cells)
    order = np.argsort(assign, kind="stable")
    idx._finish(codec, codes, counts, order, centroids)
    return idx


# ----------------------------------------------------------- persistence
def _load_pq(kind: str, meta: dict, arrays: dict) -> "_DeviceIndex":
    """``load_index`` dispatch target for the PQ kinds."""
    cls = PQIndex if kind == "pq" else IVFPQIndex
    idx = cls.__new__(cls)
    idx._restore_common(meta, arrays)
    idx.M = int(meta["M"])
    idx.ksub = int(meta["ksub"])
    idx.train_size = int(meta.get("train_size", 100_000))
    idx.seed = int(meta.get("seed", 123))
    idx.max_iterations = int(meta.get("max_iterations", 25))
    idx.pq_distortion = meta.get("pq_distortion")
    codec = PQCodec._from_codebooks(arrays["codebooks"], seed=idx.seed,
                                    max_iterations=idx.max_iterations)
    if kind == "pq":
        idx._finish(codec, arrays["codes"])
    else:
        idx.n_cells = int(meta["n_cells"])
        idx.nprobe = int(meta["nprobe"])
        # _finish flattens id-order codes through `order`; the npz holds
        # the already-flattened table, so scatter it back first
        counts = arrays["cell_counts"]
        order = np.asarray(arrays["flat_ids"]).astype(np.int64)
        codes_orig = np.empty_like(arrays["flat_codes"])
        codes_orig[order] = arrays["flat_codes"]
        idx._finish(codec, codes_orig, counts, order,
                    arrays["centroids"])
    return idx

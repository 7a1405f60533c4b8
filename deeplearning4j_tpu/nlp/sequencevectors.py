"""SequenceVectors — the generic embedding trainer.

Parity surface: reference ``models/sequencevectors/SequenceVectors.java:49``
(:136 vocab build, :192 fit spawning VectorCalculationsThreads) with learning
algorithms ``SkipGram.java:156`` / ``CBOW.java``.

TPU-native redesign: the reference's producer/consumer threads + native sg
kernel become (a) a vectorized numpy pass that turns a chunk of index
sequences into dense (center, context) pair batches — subsampling, dynamic
window shrink, negative sampling all vectorized — and (b) one jitted scatter
step per batch (kernels.py). Sequences are anything that yields token lists,
so DeepWalk graph walks and ParagraphVectors documents reuse this class
unchanged (mirroring the reference's SequenceVectors genericity)."""

from __future__ import annotations

import logging
from typing import Iterable, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.nlp import kernels
from deeplearning4j_tpu.nlp.vocab import (
    AbstractCache, VocabConstructor, build_huffman, unigram_table,
)
from deeplearning4j_tpu.perf.compile_watch import CompileWatch

log = logging.getLogger(__name__)


class SequenceVectors:
    """Train element embeddings over sequences (see module docstring).

    Builder-style keyword config mirrors the reference's
    SequenceVectors.Builder: layer_size, window_size, negative (0 => use
    hierarchical softmax), learning_rate/min_learning_rate (linear decay),
    sampling (subsampling threshold), epochs, batch_size, min_word_frequency,
    use_cbow."""

    def __init__(self, layer_size: int = 100, window_size: int = 5,
                 negative: int = 5, use_hierarchic_softmax: Optional[bool] = None,
                 learning_rate: float = 0.025, min_learning_rate: float = 1e-4,
                 sampling: float = 0.0, epochs: int = 1, iterations: int = 1,
                 batch_size: int = 2048, min_word_frequency: int = 1,
                 use_cbow: bool = False, seed: int = 12345,
                 device_corpus: Optional[bool] = None):
        self.layer_size = layer_size
        self.window_size = window_size
        self.negative = negative
        self.use_hs = (negative == 0 if use_hierarchic_softmax is None
                       else use_hierarchic_softmax)
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.sampling = sampling
        self.epochs = epochs
        self.iterations = iterations
        self.batch_size = batch_size
        self.min_word_frequency = min_word_frequency
        self.use_cbow = use_cbow
        self.seed = seed
        # None = auto: corpus-resident device training for plain SGNS
        # skip-gram when the corpus is big enough to matter (see fit())
        self.device_corpus = device_corpus

        self.vocab: Optional[AbstractCache] = None
        self.syn0: Optional[np.ndarray] = None
        self.syn1: Optional[np.ndarray] = None
        self._codes = self._points = self._lengths = None
        self._neg_table: Optional[np.ndarray] = None
        self._neg_table_dev = None   # device copy, shipped once per fit
        self._jax_key = None
        self._rng = np.random.default_rng(seed)
        self.words_processed = 0
        self.loss_history: List[float] = []
        # compile/dispatch counters for the device-corpus macro step: the
        # padded-segment scheme promises ONE compiled program for all
        # full-budget segments (tests assert it here)
        self.compile_watch = CompileWatch("SequenceVectors")

    # ------------------------------------------------------------ vocab/init
    def build_vocab(self, sequences: Iterable[List[str]]):
        self.vocab = VocabConstructor(self.min_word_frequency) \
            .build_joint_vocabulary([sequences])
        return self

    def _init_tables(self):
        v, d = self.vocab.num_words(), self.layer_size
        self.syn0 = ((self._rng.random((v, d), np.float32) - 0.5) / d)
        self.syn1 = np.zeros((v, d), np.float32)
        if self.use_hs:
            self._codes, self._points, self._lengths = build_huffman(self.vocab)
        if self.negative > 0:
            self._neg_table = unigram_table(self.vocab)
            self._neg_table_dev = None

    # --------------------------------------------------------- vectorization
    def _index_sequences(self, sequences: Iterable[List[str]]):
        """tokens -> index arrays, dropping OOV words (reference: vocab-filtered
        sequences in SequenceVectors' AsyncSequencer)."""
        widx = {vw.word: vw.index for vw in self.vocab.vocab_words()}
        for tokens in sequences:
            idx = [widx[t] for t in tokens if t in widx]
            if len(idx) >= 2:
                yield np.asarray(idx, np.int64)

    def _index_flat(self, sequences: Iterable[List[str]], widx=None):
        """Vectorized (flat, sid) indexing for the device-corpus path: one
        C-level pass instead of a per-sentence python list build (the
        per-token loop was ~40% of the device path's host budget)."""
        import itertools
        if widx is None:
            widx = {vw.word: vw.index for vw in self.vocab.vocab_words()}
        seqs = [s if isinstance(s, list) else list(s) for s in sequences]
        lens = np.fromiter((len(s) for s in seqs), np.int64, count=len(seqs))
        flat = np.fromiter(
            map(widx.get, itertools.chain.from_iterable(seqs),
                itertools.repeat(-1)),
            np.int64, count=int(lens.sum()))
        sid = np.repeat(np.arange(len(seqs), dtype=np.int64), lens)
        ok = flat >= 0  # drop OOV
        flat, sid = flat[ok], sid[ok]
        # drop sentences left with < 2 tokens (matches _index_sequences)
        counts = np.bincount(sid, minlength=len(seqs))
        good = counts[sid] >= 2
        flat, sid = flat[good], sid[good]
        return flat, sid

    def _subsample(self, flat, sid):
        """Frequent-word subsampling (word2vec formula; reference
        SkipGram's sequence pre-filter with ``sampling > 0``)."""
        if not self.sampling:
            return flat, sid
        counts = np.array([vw.count for vw in self.vocab.vocab_words()], np.float64)
        total = counts.sum()
        freq = counts / total
        t = self.sampling
        keep_prob = np.minimum(1.0, np.sqrt(t / freq) + t / freq)
        keep = self._rng.random(len(flat)) < keep_prob[flat]
        return flat[keep], sid[keep]

    def _pairs_for_chunk(self, seqs: List[np.ndarray]):
        """Vectorized window pair generation over a chunk of sequences.
        Returns (centers, contexts) with the reference's dynamic window:
        per-center radius uniform in [1, window]."""
        flat = np.concatenate(seqs)
        sid = np.repeat(np.arange(len(seqs)), [len(s) for s in seqs])
        flat, sid = self._subsample(flat, sid)
        n = len(flat)
        if n < 2:
            return (np.zeros(0, np.int64),) * 2
        r = self._rng.integers(1, self.window_size + 1, n)
        centers, contexts = [], []
        for d in range(1, self.window_size + 1):
            same = sid[:-d] == sid[d:]
            left = same & (d <= r[:-d])    # center i, context i+d
            right = same & (d <= r[d:])    # center i+d, context i
            centers.append(flat[:-d][left])
            contexts.append(flat[d:][left])
            centers.append(flat[d:][right])
            contexts.append(flat[:-d][right])
        return np.concatenate(centers), np.concatenate(contexts)

    def _bags_for_chunk(self, seqs: List[np.ndarray]):
        """CBOW bags: for each center, its (2*window) padded context bag."""
        flat = np.concatenate(seqs)
        sid = np.repeat(np.arange(len(seqs)), [len(s) for s in seqs])
        flat, sid = self._subsample(flat, sid)
        n = len(flat)
        w = self.window_size
        if n < 2:
            return (np.zeros(0, np.int64), np.zeros((0, 2 * w), np.int64),
                    np.zeros((0, 2 * w), np.float32))
        r = self._rng.integers(1, w + 1, n)
        bags = np.zeros((n, 2 * w), np.int64)
        mask = np.zeros((n, 2 * w), np.float32)
        col = 0
        for d in range(1, w + 1):
            for sign in (-1, 1):
                src = np.arange(n) + sign * d
                ok = (src >= 0) & (src < n)
                ok[ok] &= sid[src[ok]] == sid[ok.nonzero()[0]]
                ok &= d <= r
                bags[ok, col] = flat[src[ok]]
                mask[ok, col] = 1.0
                col += 1
        has_ctx = mask.sum(-1) > 0
        return flat[has_ctx], bags[has_ctx], mask[has_ctx]

    # -------------------------------------------------------------- training
    def _lr(self, total_expected: int) -> float:
        frac = min(1.0, self.words_processed / max(1, total_expected))
        return max(self.min_learning_rate, self.learning_rate * (1.0 - frac))

    def _pad(self, arr, b, fill=0):
        if len(arr) == b:
            return arr, None
        pad = b - len(arr)
        wmask = np.ones(b, np.float32)
        wmask[len(arr):] = 0.0
        widths = ((0, pad),) + ((0, 0),) * (arr.ndim - 1)
        return np.pad(arr, widths, constant_values=fill), wmask

    # full macros of NB x batch_size pairs go through ONE scanned dispatch
    # with device-side negative sampling (kernels.sgns_macro_step); the
    # ragged tail falls through to the per-batch path below. NB=8 keeps the
    # compile cache to one program while amortizing the per-dispatch
    # overhead.
    _MACRO_NB = 8

    def _train_pairs_macro(self, centers, contexts, lr):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nlp import kernels as _k
        b = self.batch_size
        macro = b * self._MACRO_NB
        n_macros = len(centers) // macro
        if self._neg_table_dev is None:
            self._neg_table_dev = jnp.asarray(self._neg_table)
        if self._jax_key is None:
            self._jax_key = jax.random.key(self.seed)
        # int16 halves H2D traffic when the tables allow.
        # Gate on the actual table height, NOT vocab.num_words():
        # ParagraphVectors appends doc rows beyond the word vocab, and an
        # int16 cast would silently wrap those indices negative.
        dt = np.int16 if self.syn0.shape[0] < 2 ** 15 else np.int32
        step = _k.sgns_macro_step(self.negative)
        losses = []
        for m in range(n_macros):
            sl = slice(m * macro, (m + 1) * macro)
            ce = np.ascontiguousarray(
                centers[sl].astype(dt).reshape(self._MACRO_NB, b))
            ct = np.ascontiguousarray(
                contexts[sl].astype(dt).reshape(self._MACRO_NB, b))
            self._jax_key, k = jax.random.split(self._jax_key)
            self.syn0, self.syn1, l = step(
                self.syn0, self.syn1, self._neg_table_dev, ce, ct, k,
                np.float32(lr))
            losses.append(l)
        return n_macros * macro, losses

    def _train_pairs(self, centers, contexts, lr):
        """Feed (center, context) pairs through the jitted steps in
        batch_size slices; the final ragged slice pads with a zero mask.
        Losses are returned as DEVICE scalars — any ``float()`` here would be
        a host-sync serialization barrier per batch; callers aggregate once
        per epoch."""
        b = self.batch_size
        losses = []
        start = 0
        if self.negative > 0 and len(centers) >= b * self._MACRO_NB:
            start, macro_losses = self._train_pairs_macro(centers, contexts, lr)
            losses.extend(macro_losses)
        for s in range(start, len(centers), b):
            ce, ct = centers[s:s + b], contexts[s:s + b]
            ce, wmask = self._pad(ce, b)
            ct, _ = self._pad(ct, b)
            if wmask is None:
                wmask = np.ones(b, np.float32)
            if self.negative > 0:
                negs = self._neg_table[
                    self._rng.integers(0, len(self._neg_table),
                                       (b, self.negative))].astype(np.int32)
                self.syn0, self.syn1, l = kernels.sgns_step(
                    self.syn0, self.syn1, ce.astype(np.int32),
                    ct.astype(np.int32), negs, wmask, np.float32(lr))
            else:
                codes = self._codes[ce]
                points = self._points[ce]
                lengths = (self._lengths[ce] * wmask).astype(np.int32)
                self.syn0, self.syn1, l = kernels.hs_step(
                    self.syn0, self.syn1, ct.astype(np.int32), codes, points,
                    lengths, np.float32(lr))
            losses.append(l)
        return losses

    def _train_bags(self, centers, bags, bmask, lr):
        b = self.batch_size
        losses = []
        for s in range(0, len(centers), b):
            ce, wmask = self._pad(centers[s:s + b], b)
            bg, _ = self._pad(bags[s:s + b], b)
            bm, _ = self._pad(bmask[s:s + b], b)
            if wmask is None:
                wmask = np.ones(b, np.float32)
            if self.negative > 0:
                negs = self._neg_table[
                    self._rng.integers(0, len(self._neg_table),
                                       (b, self.negative))].astype(np.int32)
                self.syn0, self.syn1, l = kernels.cbow_step(
                    self.syn0, self.syn1, ce.astype(np.int32),
                    bg.astype(np.int32), bm.astype(np.float32), negs, wmask,
                    np.float32(lr))
            else:
                # hierarchical softmax: walk the center word's Huffman path
                # (padded rows carry lengths=0, masking loss and updates)
                codes = self._codes[ce]
                points = self._points[ce]
                lengths = (self._lengths[ce] * wmask).astype(np.int32)
                self.syn0, self.syn1, l = kernels.cbow_hs_step(
                    self.syn0, self.syn1, codes, points, lengths,
                    bg.astype(np.int32), bm.astype(np.float32), np.float32(lr))
            losses.append(l)
        return losses

    # below this corpus size the host enumeration path wins (device pair
    # sampling needs enough batches to cover the corpus; tiny test corpora
    # also keep the exact reference enumeration semantics)
    _DEVICE_CORPUS_MIN_TOKENS = 50_000

    def fit(self, sequences, chunk_sentences: int = 512):
        """Train (reference SequenceVectors.fit :192). ``sequences`` is a
        factory (callable or re-iterable) of token-list iterables.

        Plain SGNS skip-gram on a large corpus takes the corpus-resident
        device path (kernels.sgns_corpus_macro_step): the encoded corpus
        ships to HBM once and pair/negative generation happens on-device,
        so throughput no longer scales with host->device bandwidth.
        ``device_corpus=True/False`` forces/disables it."""
        seq_factory = sequences if callable(sequences) else (lambda: sequences)
        if self.vocab is None:
            self.build_vocab(seq_factory())
        if self.syn0 is None:
            self._init_tables()
        dev_capable = (self.negative > 0 and not self.use_cbow
                       and not self.use_hs)
        if self.device_corpus and not dev_capable:
            raise ValueError(
                "device_corpus=True supports plain SGNS skip-gram only "
                "(negative > 0, no CBOW, no hierarchical softmax); this "
                f"config has negative={self.negative}, "
                f"use_cbow={self.use_cbow}, use_hs={self.use_hs}")
        # auto mode additionally requires sampling == 0: the device kernel
        # approximates subsampling by dropping pairs per-endpoint rather
        # than removing words from the stream (windows do not reach across
        # dropped words) — close in expectation but not the reference
        # semantics, so it must be opted into explicitly
        use_dev = (self.device_corpus if self.device_corpus is not None
                   else (dev_capable and self.sampling == 0))
        if use_dev:
            # decide the gate WITHOUT materializing the corpus: the vocab
            # pass already counted every in-vocab token, so the device-path
            # decision is free and the sequence factory streams segment by
            # segment inside _fit_device_corpus (host RAM stays bounded by
            # one segment, not the corpus)
            if (self.device_corpus
                    or (self.vocab.total_word_occurrences
                        >= self._DEVICE_CORPUS_MIN_TOKENS)):
                return self._fit_device_corpus(seq_factory)
            # below the gate the corpus is small by definition: tokenize
            # once and reuse on the host path instead of re-running the
            # factory per epoch
            token_lists = [t for t in seq_factory()]
            seq_factory = (lambda lists=token_lists: lists)
        total = self.vocab.total_word_occurrences * self.epochs * self.iterations
        for epoch in range(self.epochs):
            epoch_losses: List = []
            chunk: List[np.ndarray] = []
            for idx in self._index_sequences(seq_factory()):
                chunk.append(idx)
                if len(chunk) >= chunk_sentences:
                    self._fit_chunk(chunk, total, epoch_losses)
                    chunk = []
            if chunk:
                self._fit_chunk(chunk, total, epoch_losses)
            # single host sync per epoch: stack the device scalars and pull
            # one value (per-batch float() would serialize the dispatch queue)
            if epoch_losses:
                import jax.numpy as jnp
                # one host sync per epoch; atleast_1d also admits the vector
                # losses of the kernels.*_scan API
                flat_losses = jnp.concatenate(
                    [jnp.atleast_1d(l) for l in epoch_losses])
                self.loss_history.append(float(jnp.mean(flat_losses)))
        return self

    # segment size (tokens) for the device-corpus path: one segment = ONE
    # async macro dispatch, so host indexing of segment i+1 overlaps device
    # training of segment i; whole sentences per segment keep window
    # semantics exact (windows never cross sentence boundaries anyway)
    _DEVICE_CORPUS_SEG_TOKENS = 98_304

    def _segment_token_lists(self, token_lists):
        """Greedy whole-sentence packing, never exceeding the budget (so
        every full segment compiles the SAME macro program; only the
        leftover tail adds one more variant)."""
        budget = self._DEVICE_CORPUS_SEG_TOKENS
        seg, n = [], 0
        for t in token_lists:
            if seg and n + len(t) > budget:
                yield seg
                seg, n = [], 0
            seg.append(t)
            n += len(t)
        if seg:
            yield seg

    def _fit_device_corpus(self, seq_factory):
        """Corpus-resident training (see fit()): per segment of whole
        sentences, upload the encoded indices once (content-hash cached
        across epochs AND across fits on the same corpus) and run ONE
        jitted macro dispatch that generates pairs and negatives on device.

        ``seq_factory`` is consumed LAZILY, one segment at a time — the
        host never holds more than one segment of token lists, so RAM is
        bounded by the segment budget regardless of corpus size. Segments
        are PADDED up to ``_DEVICE_CORPUS_SEG_TOKENS`` with an inert
        sentinel (sid=-1; the true token count rides along as a device
        scalar for position sampling/validity), so every segment shares ONE
        compiled macro program instead of one per distinct length
        (``self.compile_watch`` counts the compiles).

        Pair quota per segment: T*(window+1) sampled pairs — the exact
        expected pair count of the reference's dynamic-window enumeration
        (per position 2*E[r] = window+1 pairs), drawn from the same joint
        (position, side, offset) distribution by the kernel; the static
        scan length is sized for the budget and trailing batches beyond
        the quota are masked on device. Dispatches are async; the only
        host sync is the per-epoch loss aggregation, so host-side indexing
        of the next segment overlaps device training of the current one."""
        import hashlib

        import jax
        import jax.numpy as jnp

        if self._neg_table_dev is None:
            self._neg_table_dev = jnp.asarray(
                self._neg_table.astype(np.int32))
        if self._jax_key is None:
            self._jax_key = jax.random.key(self.seed)
        # device-resident tables from the FIRST dispatch: a numpy first
        # step would compile its own donation-less specialization of the
        # macro program (breaking the one-compile contract) and copy the
        # tables every step
        self.syn0 = jnp.asarray(self.syn0)
        self.syn1 = jnp.asarray(self.syn1)
        keep = None
        if self.sampling:
            counts = np.array([vw.count for vw in self.vocab.vocab_words()],
                              np.float64)
            freq = counts / counts.sum()
            t = self.sampling
            keep = jnp.asarray(np.minimum(
                1.0, np.sqrt(t / freq) + t / freq).astype(np.float32))
        # int16 halves the upload when the index ranges allow
        cdt = np.int16 if self.syn0.shape[0] < 2 ** 15 else np.int32
        B = self.batch_size
        W = self.window_size
        total_expected = (self.vocab.total_word_occurrences * self.epochs
                          * self.iterations)
        cache = getattr(self, "_corpus_dev_cache", None)
        if cache is None:
            # insertion-ordered, FIFO-bounded: long-lived processes fitting
            # many distinct corpora must not pin HBM forever
            cache = self._corpus_dev_cache = {}
        widx = {vw.word: vw.index for vw in self.vocab.vocab_words()}
        if not callable(seq_factory):
            seq_factory = (lambda lists=seq_factory: lists)

        def first_pass_plan():
            """Index + upload segments lazily, so the caller's dispatch of
            segment i overlaps (async) with indexing of segment i+1 — and
            the factory is only ever consumed one segment ahead.
            Boundaries (sid) are part of the cache identity."""
            budget = self._DEVICE_CORPUS_SEG_TOKENS
            for seg in self._segment_token_lists(seq_factory()):
                flat, sid = self._index_flat(seg, widx)
                if len(flat) < 2:
                    continue
                flat = flat.astype(cdt)
                sdt = (np.int16 if sid[-1] < 2 ** 15 else np.int32)
                sid = sid.astype(sdt)
                T = len(flat)
                if T < budget:
                    # pad to the budget with an inert sentinel: sid=-1
                    # never matches a real sentence id, and the kernel
                    # samples positions from the TRUE length (shipped as a
                    # device scalar) — so every <=budget segment compiles
                    # the SAME macro program regardless of its length
                    flat = np.concatenate(
                        [flat, np.zeros(budget - T, flat.dtype)])
                    sid = np.concatenate(
                        [sid, np.full(budget - T, -1, sid.dtype)])
                h = hashlib.sha1(flat.tobytes())
                h.update(sid.tobytes())
                hit = cache.get(h.digest())
                if hit is None:
                    hit = (jnp.asarray(flat), jnp.asarray(sid))
                    while len(cache) >= 1024:  # FIFO bound on pinned HBM
                        cache.pop(next(iter(cache)))
                    cache[h.digest()] = hit
                # static scan length from the padded shape (one program);
                # the segment's true quota T*(W+1) rides along as n_active
                # — trailing batches are masked on device. A segment can
                # only EXCEED the budget via one oversized sentence; it
                # keeps its own (rare) program
                nb = max(1, -(-(max(T, budget) * (W + 1)) // B))
                nvb = min(nb, max(1, -(-(T * (W + 1)) // B)))
                yield hit[0], hit[1], T, nb, nvb

        plan = None  # filled on the first pass; later passes reuse it
        for _epoch in range(self.epochs):
            epoch_losses = []
            for _ in range(self.iterations):
                entries = first_pass_plan() if plan is None else plan
                built = [] if plan is None else None
                for corpus_dev, sid_dev, T, nb, nvb in entries:
                    lr = self._lr(total_expected)
                    step = self.compile_watch.wrap(
                        kernels.sgns_corpus_macro_step(
                            self.negative, W, B, nb), "sgns_corpus_macro")
                    self._jax_key, k = jax.random.split(self._jax_key)
                    self.syn0, self.syn1, losses = step(
                        self.syn0, self.syn1, corpus_dev, sid_dev,
                        self._neg_table_dev, keep, k, np.float32(lr),
                        np.int32(T), np.int32(nvb))
                    # quota-masked trailing batches carry no pairs: keep
                    # them out of the loss history
                    epoch_losses.append(losses[:nvb])
                    self.words_processed += T
                    if built is not None:
                        built.append((corpus_dev, sid_dev, T, nb, nvb))
                if built is not None:
                    plan = built
            if epoch_losses:
                self.loss_history.append(float(jnp.mean(
                    jnp.concatenate([jnp.atleast_1d(l)
                                     for l in epoch_losses]))))
        return self

    def _fit_chunk(self, chunk, total_expected, epoch_losses):
        for _ in range(self.iterations):
            lr = self._lr(total_expected)
            if self.use_cbow:
                centers, bags, bmask = self._bags_for_chunk(chunk)
                if len(centers):
                    epoch_losses.extend(self._train_bags(centers, bags, bmask, lr))
            else:
                centers, contexts = self._pairs_for_chunk(chunk)
                if len(centers):
                    epoch_losses.extend(self._train_pairs(centers, contexts, lr))
            self.words_processed += sum(len(s) for s in chunk)

    # -------------------------------------------------------------- lookups
    def word_vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])

    def get_word_vector_matrix(self) -> np.ndarray:
        return np.asarray(self.syn0)

    def has_word(self, word: str) -> bool:
        return self.vocab is not None and self.vocab.contains_word(word)

    def similarity(self, a: str, b: str) -> float:
        """Cosine similarity (reference WordVectors.similarity)."""
        va, vb = self.word_vector(a), self.word_vector(b)
        if va is None or vb is None:
            return float("nan")
        denom = (np.linalg.norm(va) * np.linalg.norm(vb)) or 1e-12
        return float(va @ vb / denom)

    def words_nearest(self, word_or_vec, top_n: int = 10) -> List[str]:
        """Nearest words by cosine (reference wordsNearest)."""
        if isinstance(word_or_vec, str):
            v = self.word_vector(word_or_vec)
            exclude = {word_or_vec}
        else:
            v = np.asarray(word_or_vec, np.float32)
            exclude = set()
        if v is None:
            return []
        # get_word_vector_matrix, not raw syn0: subclasses append non-word
        # rows (ParagraphVectors doc vectors) or combine tables (GloVe W+W~)
        m = self.get_word_vector_matrix()
        norms = np.linalg.norm(m, axis=1) * (np.linalg.norm(v) or 1e-12)
        sims = (m @ v) / np.maximum(norms, 1e-12)
        order = np.argsort(-sims)
        out = []
        for i in order:
            w = self.vocab.word_at_index(int(i))
            if w not in exclude:
                out.append(w)
            if len(out) >= top_n:
                break
        return out

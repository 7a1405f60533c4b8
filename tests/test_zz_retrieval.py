"""retrieval/ tier-1 suite: TPU-native vector retrieval.

Covers the tentpole contract end to end — batched brute-force top-k
EXACTLY matching the (tie-stable, property-verified) host VPTree, IVF
recall + int8 recall-delta gates on a seeded corpus, zero compiles in a
steady-state query burst after warmup, zero host syncs inside the jitted
scoring path, and the serving integration (429 under overload, 504 on
expired deadlines, hot-swap index rebuild mid-burst with zero non-200s
on admitted requests) — plus the satellites: tree-vs-brute property
tests (random + duplicate-point), the chunked-Lloyd KMeans parity, the
b64 wire format on /knn and the retrieval endpoints and the build CLI.

(Named test_zz_* so the file sorts after every seed test: if the tier-1
timeout ever cuts the tail, it evicts these before any seed dot.)
"""

import base64
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import retrieval
from deeplearning4j_tpu.clustering.kdtree import KDTree
from deeplearning4j_tpu.clustering.kmeans import KMeansClustering, _lloyd_step
from deeplearning4j_tpu.clustering.server import NearestNeighborsServer
from deeplearning4j_tpu.clustering.vptree import VPTree
from deeplearning4j_tpu.retrieval import (BruteForceIndex, IVFIndex,
                                          IndexEndpoint, RecallGateError,
                                          assert_recall_within, build_index,
                                          load_index, recall_at_k)
from deeplearning4j_tpu.serving import ModelServer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ utils
def _oracle(points, q, k):
    """Exact tie-stable top-k: the first k of sorted((d_i, i))."""
    d = np.linalg.norm(np.asarray(points, np.float64) - q, axis=1)
    order = np.lexsort((np.arange(len(d)), d))[:k]
    return list(map(int, order)), [float(d[i]) for i in order]


@pytest.fixture(scope="module")
def corpus():
    # the one shared recipe (retrieval.synthetic_corpus) so the tier-1
    # gates and the CLI measure the same distribution
    return retrieval.synthetic_corpus(4000, 32, n_clusters=50, seed=11,
                                      queries=64)


@pytest.fixture(scope="module")
def exact_index(corpus):
    V, _ = corpus
    return BruteForceIndex(V)


def _post(base, path, body, timeout=30, headers=None):
    req = urllib.request.Request(
        base + path, json.dumps(body).encode(),
        {"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


class SlowIndex:
    """Delegating index wrapper whose search can be slowed, HELD at a
    gate, or scripted to fail — the chaos lever for the overload tests."""

    def __init__(self, inner, delay_s=0.0):
        self._inner = inner
        self.delay_s = delay_s
        self.fail_next = 0
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()  # a dispatch reached the gate

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def search(self, queries, k=10):
        self.entered.set()
        self.gate.wait(timeout=30)
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail_next > 0:
            self.fail_next -= 1
            raise RuntimeError("injected index fault")
        return self._inner.search(queries, k)


# ---------------------------------------------- satellite: tree oracles
def test_trees_match_bruteforce_property_random_and_duplicates():
    """VPTree and KDTree search(k) EXACTLY matches tie-stable brute force
    (indices AND distances) on random, duplicate-heavy and exact-tie-grid
    inputs — the host trees are the device indexes' recall oracle, so
    they must be provably correct first."""
    rng = np.random.default_rng(1234)
    for trial in range(24):
        kind = trial % 4
        if kind == 0:
            P = rng.standard_normal((int(rng.integers(20, 300)),
                                     int(rng.integers(2, 7))))
        elif kind == 1:  # duplicate-heavy: few distinct points, many copies
            base = rng.standard_normal((int(rng.integers(2, 7)), 3))
            P = base[rng.integers(0, len(base), int(rng.integers(30, 150)))]
        elif kind == 2:  # integer grid: massive exact-distance ties
            g = np.stack(np.meshgrid(np.arange(5.0), np.arange(5.0)),
                         -1).reshape(-1, 2)
            P = g[rng.permutation(len(g))]
        else:  # near-degenerate cluster at the origin
            P = np.zeros((80, 4))
            P[:10] = rng.standard_normal((10, 4)) * 0.01
        k = int(rng.integers(1, min(12, len(P)) + 1))
        q = (P[int(rng.integers(0, len(P)))] if trial % 2
             else rng.standard_normal(P.shape[1]))
        want_i, want_d = _oracle(P, q, k)
        for tree in (VPTree(P), KDTree(P)):
            got_i, got_d = tree.search(q, k)
            assert list(got_i) == want_i, \
                f"{type(tree).__name__} trial {trial}: {got_i} != {want_i}"
            assert np.allclose(got_d, want_d, rtol=0, atol=1e-9)


# ------------------------------------------------- tentpole: exact brute
def test_batched_brute_force_matches_vptree_exactly(corpus, exact_index):
    """The device-batched matmul+top_k answers EXACTLY the host VPTree's
    results on float32 — indices equal, distances to fp tolerance — for
    batched queries at several k (pow2 and not)."""
    V, Q = corpus
    tree = VPTree(V)
    for k in (1, 7, 10):
        idx, dist = exact_index.search(Q, k)
        assert idx.shape == (len(Q), k) and dist.shape == (len(Q), k)
        for r in range(len(Q)):
            want_i, want_d = tree.search(Q[r], k)
            assert list(idx[r]) == want_i, f"row {r} k {k}"
            assert np.allclose(dist[r], want_d, rtol=1e-4, atol=1e-4)
    # single-vector convenience matches the tree's 1-query contract
    i1, d1 = exact_index.search(Q[0], 5)
    wi, wd = tree.search(Q[0], 5)
    assert list(i1) == wi and np.allclose(d1, wd, rtol=1e-4, atol=1e-4)


def test_brute_force_cosine_matches_vptree(corpus):
    V, Q = corpus
    ix = BruteForceIndex(V, metric="cosine")
    tree = VPTree(V, distance="cosine")
    idx, dist = ix.search(Q[:8], 5)
    for r in range(8):
        want_i, want_d = tree.search(Q[r], 5)
        assert list(idx[r]) == want_i
        assert np.allclose(dist[r], want_d, atol=1e-3)


def test_brute_force_tie_stability_on_duplicates():
    # exact duplicate rows produce exactly equal d2 on device; lax.top_k
    # breaks ties by lower index — same contract as the tie-stable trees
    base = np.array([[0.0, 0.0], [1.0, 0.0], [0.0, 1.0]], np.float32)
    P = np.repeat(base, 8, axis=0)
    ix = BruteForceIndex(P)
    idx, dist = ix.search(np.array([0.1, 0.0], np.float32), 10)
    want_i, want_d = _oracle(P, np.array([0.1, 0.0]), 10)
    assert list(idx) == want_i
    assert np.allclose(dist, want_d, atol=1e-5)


# ------------------------------------------------ tentpole: recall gates
def test_ivf_recall_gate_at_default_nprobe(corpus, exact_index):
    """IVF at the DEFAULT nprobe answers recall@10 >= 0.95 on the seeded
    corpus (asserted through the gate API, the PTQ-accuracy-gate shape)."""
    V, Q = corpus
    ivf = IVFIndex(V)  # default n_cells=sqrt(n), nprobe=8
    report = assert_recall_within(ivf, Q, 10, min_recall=0.95,
                                  exact=exact_index)
    assert report["recall"] >= 0.95
    # the measured number lands in the obs registry for rollout automation
    from deeplearning4j_tpu.obs import get_registry, prometheus_text
    assert "retrieval_recall_ivf" in prometheus_text(get_registry())


def test_int8_recall_delta_gate(corpus, exact_index):
    """int8 indexes pass the recall-delta gate: residual-encoded int8 IVF
    loses <= 0.01 recall@10 vs its float source, and the gate RAISES on
    an over-budget config (whole-vector int8 brute on this corpus)."""
    V, Q = corpus
    ivf = IVFIndex(V)
    i8 = IVFIndex(V, int8=True)
    report = assert_recall_within(i8, Q, 10, baseline=ivf, max_delta=0.01,
                                  exact=exact_index)
    assert report["delta"] <= 0.01
    assert i8.nbytes() < ivf.nbytes() / 2.5  # the compression is real
    # an impossible budget raises the typed gate error with the numbers
    with pytest.raises(RecallGateError):
        assert_recall_within(i8, Q, 10, min_recall=1.01, exact=exact_index)


def test_int8_brute_force_recall(corpus, exact_index):
    """Whole-vector per-row int8 (no residual structure to lean on) still
    recovers >= 0.95 recall@10 here — and the delta vs exact is visibly
    worse than the residual-encoded IVF, which is WHY the IVF encoding
    recenters."""
    V, Q = corpus
    b8 = BruteForceIndex(V, int8=True)
    r = recall_at_k(b8, Q, 10, exact=exact_index)
    assert r >= 0.95


# --------------------------------------- tentpole: compile/sync hygiene
def test_zero_compiles_during_steady_state_burst(corpus):
    V, Q = corpus
    ix = IVFIndex(V, int8=True)
    # warm the full (query-bucket x k-rung) ladder the burst will hit:
    # ks rounds to pow2 rungs {1, 2, 4, 8, 16}
    ix.warmup(max_queries=64, ks=(1, 2, 4, 8, 10))
    c0 = ix.compile_watch.compiles()
    rng = np.random.default_rng(0)
    for _ in range(25):
        b = int(rng.integers(1, 60))
        k = int(rng.integers(1, 11))
        ix.search(Q[:b] if b <= len(Q) else V[:b], k)
    assert ix.compile_watch.compiles() - c0 == 0, \
        ix.compile_watch.as_dict()
    assert ix.compile_watch.dispatches() >= 25


def test_scoring_path_zero_host_syncs(corpus):
    """trace_check over the jitted scoring dispatch itself (device-
    resident queries in, device arrays out): zero sync points, zero
    recompiles — for the float brute AND the int8 IVF kernels."""
    from deeplearning4j_tpu.analysis.trace_check import trace_check

    V, Q = corpus
    for ix in (BruteForceIndex(V), IVFIndex(V, int8=True)):
        ix.warmup(max_queries=16, ks=(8,))
        qdev = jnp.asarray(Q[:16])
        with trace_check() as report:
            d, i = ix._search_device(qdev, 8)
            jax.block_until_ready((d, i))
        counts = report.counts()
        assert counts["trace_sync_points"] == 0, report.summary()
        assert counts["trace_recompiles"] == 0, report.summary()


# -------------------------------------- satellite: chunked-Lloyd KMeans
def test_kmeans_chunked_lloyd_parity(corpus):
    """The lax.while_loop chunked Lloyd runs the SAME iteration sequence
    and stop point as a host-checked per-iteration loop: identical
    assignments, matching centroids/cost, same iteration count — while
    syncing once per chunk instead of once per iteration."""
    V, _ = corpus
    X = V[:1500]
    km = KMeansClustering(16, max_iterations=40, seed=3)
    assign, cents = km.apply_to(X)

    # the pre-chunking reference loop, step by step on the host
    x = jnp.asarray(X)
    c = jnp.asarray(km._seed_centroids(np.asarray(X, np.float32)))
    ref_iters = 0
    for _ in range(40):
        c, _, shift, _ = _lloyd_step(x, c, 16)
        ref_iters += 1
        if float(shift) < km.tol:
            break
    _, ref_assign, _, ref_cost = _lloyd_step(x, c, 16)

    assert km.iterations_run == ref_iters
    assert np.array_equal(assign, np.asarray(ref_assign))
    assert np.allclose(cents, np.asarray(c), rtol=1e-5, atol=1e-6)
    assert km.cost == pytest.approx(float(ref_cost), rel=1e-5)

    # check_every=1 (the old cadence) agrees with the default chunking
    km1 = KMeansClustering(16, max_iterations=40, seed=3)
    assign1, cents1 = km1.apply_to(X, check_every=1)
    assert km1.iterations_run == ref_iters
    assert np.array_equal(assign1, assign)
    assert np.allclose(cents1, cents, rtol=1e-5, atol=1e-6)


# ------------------------------------------- satellite: kNN wire format
def test_knn_server_b64_wire_parity():
    rng = np.random.default_rng(0)
    P = rng.standard_normal((300, 8)).astype(np.float32)
    srv = NearestNeighborsServer(P).start(port=0)
    base = f"http://127.0.0.1:{srv.port}"
    try:
        Q = (P[:4] + 0.01).astype(np.float32)
        # JSON batch vs b64 batch: same numbers
        stj, oj, _ = _post(base, "/knnnew", {"vector": Q.tolist(), "k": 3})
        assert stj == 200 and len(oj["batch_results"]) == 4
        b = {"x_b64": base64.b64encode(Q.astype("<f4").tobytes()).decode(),
             "dtype": "float32", "shape": list(Q.shape), "k": 3,
             "b64": True}
        stb, ob, _ = _post(base, "/knnnew", b)
        assert stb == 200
        idx = np.frombuffer(base64.b64decode(ob["indices_b64"]),
                            "<i4").reshape(ob["shape"])
        dist = np.frombuffer(base64.b64decode(ob["distances_b64"]),
                             "<f4").reshape(ob["shape"])
        for r in range(4):
            assert [p["index"] for p in oj["batch_results"][r]] \
                == list(idx[r])
            assert np.allclose([p["distance"]
                                for p in oj["batch_results"][r]],
                               dist[r], atol=1e-6)
        # int8 queries with an explicit scale; without one -> 400
        s = float(np.abs(Q).max() / 127)
        qq = np.clip(np.rint(Q / s), -127, 127).astype(np.int8)
        b8 = {"x_b64": base64.b64encode(qq.tobytes()).decode(),
              "dtype": "int8", "shape": list(Q.shape), "scale": s, "k": 3}
        st8, o8, _ = _post(base, "/knnnew", b8)
        assert st8 == 200 and len(o8["batch_results"]) == 4
        del b8["scale"]
        st9, o9, _ = _post(base, "/knnnew", b8)
        assert st9 == 400 and "scale" in o9["error"]
        # /knn (query by stored index) keeps its JSON contract and gains
        # the b64 response option
        stk, ok, _ = _post(base, "/knn", {"index": 5, "k": 3})
        assert stk == 200 and len(ok["results"]) == 3
        stk2, ok2, _ = _post(base, "/knn", {"index": 5, "k": 3,
                                            "b64": True})
        idx2 = np.frombuffer(base64.b64decode(ok2["indices_b64"]), "<i4")
        assert stk2 == 200 and \
            list(idx2) == [p["index"] for p in ok["results"]]
    finally:
        srv.stop()


# --------------------------------------------- tentpole: serving tier
def test_retrieval_endpoint_http_roundtrip_and_wire_parity(corpus):
    V, Q = corpus
    srv = ModelServer()
    ix = BruteForceIndex(V, labels=[f"v{i}" for i in range(len(V))])
    srv.add_index("vecs", ix, k_default=5, k_max=16, warmup_queries=32)
    srv.start(warmup=True, warmup_async=False)
    base = srv.address
    try:
        with urllib.request.urlopen(base + "/readyz", timeout=10) as r:
            assert r.status == 200
        q = Q[:3]
        st, out, _ = _post(base, "/v1/indexes/vecs:query",
                           {"queries": q.tolist(), "k": 4})
        assert st == 200 and np.asarray(out["indices"]).shape == (3, 4)
        assert out["labels"][0][0] == f"v{out['indices'][0][0]}"
        # b64 request + b64 response == JSON numbers
        b = {"x_b64": base64.b64encode(q.astype("<f4").tobytes()).decode(),
             "dtype": "float32", "shape": list(q.shape), "k": 4,
             "b64": True}
        st2, out2, _ = _post(base, "/v1/indexes/vecs:query", b)
        assert st2 == 200
        idx2 = np.frombuffer(base64.b64decode(out2["indices_b64"]),
                             "<i4").reshape(out2["shape"])
        dist2 = np.frombuffer(base64.b64decode(out2["distances_b64"]),
                              "<f4").reshape(out2["shape"])
        assert np.array_equal(idx2, np.asarray(out["indices"]))
        assert np.allclose(dist2, np.asarray(out["distances"]), atol=1e-6)
        # malformed: wrong dims, bad k, unknown index
        st3, o3, _ = _post(base, "/v1/indexes/vecs:query",
                           {"queries": [[0.0] * 7]})
        assert (st3, o3["reason"]) == (400, "bad_request")
        st4, o4, _ = _post(base, "/v1/indexes/vecs:query",
                           {"queries": q.tolist(), "k": 9999})
        assert st4 == 400
        st4b, o4b, _ = _post(base, "/v1/indexes/vecs:query",
                             {"queries": Q[:33].tolist(), "k": 4})
        assert st4b == 400 and "max_query_rows" in o4b["error"]
        st5, o5, _ = _post(base, "/v1/indexes/nope:query",
                           {"queries": q.tolist()})
        assert (st5, o5["reason"]) == (404, "unknown_index")
        # stats surfaces
        with urllib.request.urlopen(base + "/v1/indexes", timeout=10) as r:
            listing = json.loads(r.read())
        assert listing["indexes"]["vecs"]["index"]["size"] == len(V)
        with urllib.request.urlopen(base + "/v1/indexes/vecs",
                                    timeout=10) as r:
            one = json.loads(r.read())
        assert one["queries_served"] >= 2 and one["warmed"]
    finally:
        srv.stop()


def test_retrieval_int8_wire_queries_on_int8_index(corpus):
    """int8 wire queries decode on the index's PUBLISHED grid — which
    for a residual-encoded IVF must be the whole-VECTOR grid (queries
    live in embedding space; the residual table grid would clip them at
    the cell radius). Asserted over the full query set, not a lucky
    pair: the published scale must cover the queries, and top-1 must
    agree with float queries almost everywhere."""
    V, Q = corpus
    srv = ModelServer()
    i8 = IVFIndex(V, int8=True)
    srv.add_index("i8", i8, k_default=5, k_max=8, warmup_queries=64)
    srv.start(warmup=True, warmup_async=False)
    try:
        # the published wire grid covers query magnitudes (no clipping):
        # scale*127 is the observer amax over the WHOLE vectors
        assert i8.scale * 127.0 >= 0.95 * float(np.abs(Q).max())
        qq = np.clip(np.rint(Q / i8.scale), -127, 127).astype(np.int8)
        b = {"x_b64": base64.b64encode(qq.tobytes()).decode(),
             "dtype": "int8", "shape": list(Q.shape), "k": 5}
        st, out, _ = _post(srv.address, "/v1/indexes/i8:query", b)
        assert st == 200
        stf, outf, _ = _post(srv.address, "/v1/indexes/i8:query",
                             {"queries": Q.tolist(), "k": 5})
        agree = np.mean(np.asarray(out["indices"])[:, 0]
                        == np.asarray(outf["indices"])[:, 0])
        assert agree >= 0.9, agree  # grid rounding only, never clipping
    finally:
        srv.stop()


def test_retrieval_overload_sheds_429_and_deadline_504(corpus):
    """The serving contract under pressure: a burst far beyond a slowed
    index's capacity answers typed 429s (Retry-After set, queue bound
    respected) and queued requests whose deadline passes are evicted as
    504 BEFORE device dispatch — every response is one of 200/429/504,
    never a hang or a reset."""
    V, _ = corpus
    srv = ModelServer(retry_after_s=2.0)
    slow = SlowIndex(BruteForceIndex(V[:512]), delay_s=0.15)
    ep = IndexEndpoint("slow", slow, k_default=5, queue_depth=2,
                       batch_limit=1, default_deadline_ms=10_000.0)
    srv.add_index("slow", ep)
    srv.start(warmup=True, warmup_async=False)
    base = srv.address
    q = [V[0].tolist()]
    codes, retry_after = [], []
    lock = threading.Lock()

    def client():
        st, _, hdrs = _post(base, "/v1/indexes/slow:query",
                            {"queries": q, "k": 3}, timeout=30)
        with lock:
            codes.append(st)
            if st == 429:
                retry_after.append(hdrs.get("Retry-After"))

    try:
        threads = [threading.Thread(target=client) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert set(codes) <= {200, 429}, codes
        assert codes.count(429) >= 1, codes   # the burst overflowed
        assert codes.count(200) >= 1, codes   # admitted work completed
        assert all(ra is not None for ra in retry_after)
        st = ep.stats()
        assert st["queue"]["rejected"] >= 1

        # deadline: HOLD the worker inside a dispatch at the gate, queue a
        # short-deadline request, release the gate only after the deadline
        # has passed — the queued request MUST be evicted at batch
        # formation (before device dispatch) and answer 504
        slow.delay_s = 0.0
        slow.entered.clear()
        slow.gate.clear()
        long_res, short_res = [], []
        t1 = threading.Thread(target=lambda: long_res.append(
            _post(base, "/v1/indexes/slow:query",
                  {"queries": q, "k": 3}, timeout=30)))
        t1.start()
        assert slow.entered.wait(timeout=10)  # worker is inside dispatch
        expired_before = ep.stats()["queue"]["expired"]
        t2 = threading.Thread(target=lambda: short_res.append(
            _post(base, "/v1/indexes/slow:query",
                  {"queries": q, "k": 3, "deadline_ms": 100},
                  timeout=30)))
        t2.start()
        # wait until the short-deadline request is IN the queue (its
        # deadline clock started at admission), THEN let the deadline
        # lapse before releasing the gate — eviction is now certain, not
        # a race against HTTP handler latency
        give_up = time.monotonic() + 10.0
        while ep.stats()["queue"]["depth"] < 1:
            assert time.monotonic() < give_up, "request never queued"
            time.sleep(0.01)
        time.sleep(0.35)  # the queued request's 100ms deadline passes
        slow.gate.set()
        t1.join(timeout=30)
        t2.join(timeout=30)
        st2, o2, _ = short_res[0]
        assert (st2, o2["reason"]) == (504, "deadline_expired")
        assert "before batch dispatch" in o2["error"]  # evicted, not late
        assert ep.stats()["queue"]["expired"] == expired_before + 1
        assert long_res[0][0] == 200  # long-deadline request still landed
    finally:
        srv.stop()


def test_retrieval_breaker_opens_on_faults(corpus):
    from deeplearning4j_tpu.serving import CircuitBreaker
    from deeplearning4j_tpu.serving.server import BreakerOpenError

    V, Q = corpus
    slow = SlowIndex(BruteForceIndex(V[:256]))
    ep = IndexEndpoint("b", slow, k_default=3,
                       breaker=CircuitBreaker(failure_threshold=2,
                                              window_s=10.0,
                                              cooldown_s=30.0))
    try:
        slow.fail_next = 2
        for _ in range(2):
            with pytest.raises(retrieval.IndexDispatchError):
                ep.query(Q[:1], 3)
        with pytest.raises(BreakerOpenError):
            ep.query(Q[:1], 3)
    finally:
        ep.shutdown()


def test_endpoint_single_vector_promotion_and_swap_shrink(corpus):
    """submit() promotes a (d,) query to a one-row batch and rejects
    malformed shapes SYNCHRONOUSLY (caller error, no breaker hit); a
    request admitted with a k the index can no longer serve (a swap to a
    smaller index landed after admission) answers the standard padding
    tail (-1 @ inf) instead of a 500."""
    V, Q = corpus
    ep = IndexEndpoint("solo", BruteForceIndex(V[:600]), k_default=4,
                       k_max=8, warmup_queries=8)
    try:
        idx, dist = ep.query(V[0], 4)  # single vector -> one-row batch
        assert idx.shape == (1, 4) and int(idx[0][0]) == 0
        with pytest.raises(ValueError):
            ep.query(np.zeros((2, 3), np.float32), 4)  # wrong dim
        assert ep.breaker.state == "closed"  # caller errors never count
        # simulate a shrink-swap landing between admission and dispatch
        ep._index = BruteForceIndex(V[:5])
        idx2, dist2 = ep.query(Q[:2], 8)
        assert idx2.shape == (2, 8)
        assert (idx2[:, 5:] == -1).all()
        assert np.isinf(dist2[:, 5:]).all()
        assert set(idx2[0, :5]) == set(range(5))
    finally:
        ep.shutdown()


def test_hot_swap_rebuild_mid_burst_zero_non_200_on_admitted(corpus):
    """The acceptance chaos test: a client burst runs against a warmed
    index while a REBUILT index (fresh vectors, same dim) hot-swaps in
    mid-burst. Every admitted request answers 200 (zero drops, zero 5xx),
    results switch to the new corpus, and the swap compiles nothing (the
    rebuilt index reuses the module-level kernels' warmed programs)."""
    V, Q = corpus
    rng = np.random.default_rng(99)
    V2 = V + rng.standard_normal(V.shape).astype(np.float32) * 0.001
    srv = ModelServer()
    ep = srv.add_index("live", BruteForceIndex(V), k_default=5, k_max=8,
                       warmup_queries=32, default_deadline_ms=20_000.0)
    srv.start(warmup=True, warmup_async=False)
    base = srv.address
    stop = threading.Event()
    results, lock = [], threading.Lock()

    def client(cid):
        while not stop.is_set():
            b = int(1 + (cid % 4))
            st, out, _ = _post(base, "/v1/indexes/live:query",
                               {"queries": Q[:b].tolist(), "k": 5},
                               timeout=30)
            with lock:
                results.append(st)
            time.sleep(0.002)

    c0 = ep.index.compile_watch.compiles()
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(4)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.4)
        replacement = BruteForceIndex(V2)
        ep.swap_index(replacement)  # warms, then swaps between dispatches
        time.sleep(0.4)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        srv.stop()
    assert len(results) >= 20
    assert set(results) == {200}, \
        f"non-200s during hot-swap burst: {sorted(set(results))}"
    assert ep.stats()["swaps"] == 1
    assert ep.index is replacement
    # the replacement compiled nothing new during the burst window
    assert replacement.compile_watch.compiles() == 0


# ------------------------------------- tentpole: builders + persistence
def test_build_index_from_embedding_sources(tmp_path):
    # Word2Vec table -> labels are vocab words, rows the lookup table
    from deeplearning4j_tpu.nlp import Word2Vec
    rng = np.random.default_rng(5)
    words = [f"w{i}" for i in range(40)]
    sents = [" ".join(rng.choice(words, 8)) for _ in range(60)]
    w2v = Word2Vec(layer_size=16, window_size=2, negative=2, epochs=1,
                   batch_size=256, min_word_frequency=1, seed=1)
    w2v.fit(sents)
    ix = build_index(w2v, kind="brute")
    assert ix.size == w2v.vocab_size() and ix.labels is not None
    w0 = ix.labels[0]
    got, _ = ix.search(w2v.word_vector(w0), 1)
    assert ix.labels[int(got[0])] == w0

    # DeepWalk vertex embeddings -> rows ordered by vertex id
    from deeplearning4j_tpu.graphs import DeepWalk, Graph
    g = Graph(10)
    for a in range(10):
        g.add_edge(a, (a + 1) % 10)
    dw = DeepWalk(vector_size=8, walk_length=6, epochs=1, seed=1)
    dw.fit(g)
    ixg = build_index(dw, kind="brute")
    assert ixg.size == 10
    got, _ = ixg.search(dw.get_vertex_vector(3), 1)
    assert int(got[0]) == 3

    # a network's penultimate activations over a corpus
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Sgd
    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(Sgd(learning_rate=0.1)).weight_init("xavier").list()
            .layer(DenseLayer(n_out=12, activation="tanh"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    net = MultiLayerNetwork(conf).init()
    data = rng.standard_normal((64, 6)).astype(np.float32)
    ixn = build_index(net, kind="brute", inputs=data)
    assert ixn.size == 64 and ixn.dim == 12  # penultimate width
    got, dist = ixn.search(
        retrieval.vectors_from_model(net, data[:1]), 1)
    assert int(got[0][0]) == 0
    assert float(dist[0][0]) == pytest.approx(0.0, abs=1e-4)


def test_index_save_load_roundtrip(tmp_path, corpus):
    V, Q = corpus
    for ix in (BruteForceIndex(V[:800], labels=None),
               IVFIndex(V[:800], int8=True, n_cells=16, nprobe=6)):
        p = str(tmp_path / f"{ix.kind}{int(ix.int8)}.npz")
        ix.save(p)
        back = load_index(p)
        i1, d1 = ix.search(Q[:16], 7)
        i2, d2 = back.search(Q[:16], 7)
        assert np.array_equal(i1, i2)
        assert np.allclose(d1, d2)
        assert (back.kind, back.int8, back.size) == \
            (ix.kind, ix.int8, ix.size)


def test_build_index_cli_in_process(tmp_path):
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        import build_index as cli
    finally:
        sys.path.pop(0)
    out = str(tmp_path / "ix.npz")
    rc = cli.main(["--vectors", "random:1500x16@3", "--kind", "ivf",
                   "--int8", "--out", out, "--gate-min-recall", "0.9"])
    assert rc == 0 and os.path.exists(out)
    ix = load_index(out)
    assert ix.kind == "ivf" and ix.int8 and ix.size == 1500
    # a hopeless gate refuses to write
    out2 = str(tmp_path / "nope.npz")
    rc2 = cli.main(["--vectors", "random:400x8@3", "--kind", "ivf",
                    "--nprobe", "1", "--n-cells", "20", "--out", out2,
                    "--gate-min-recall", "1.01"])
    assert rc2 == 1 and not os.path.exists(out2)

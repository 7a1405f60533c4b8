"""perf/ subsystem: shape bucketing, device prefetch, compile observability.

The contract under test is the TPU execution substrate's (PAPER.md): batch
shapes must be STABLE — an epoch with a ragged tail is one compiled
program, a serving mix of request sizes dispatches only pre-warmed bucket
shapes, and host→device prefetch changes nothing numerically. The compile
counters (perf/compile_watch.py) make all three assertable instead of
inferred from wall clock.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import jax
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (AsyncDataSetIterator,
                                                   ListDataSetIterator)
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.parallel import ParallelInference, ParallelWrapper
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.perf import (BucketPolicy, DevicePrefetchIterator,
                                     pad_dataset, pad_to_bucket, unpad)


def _net(seed=7, lr=0.05, n_in=4, n_out=3):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(learning_rate=lr)).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=n_out, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def _ragged_batches(n=150, batch=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random((n, 4), np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y).split(batch)  # e.g. 64, 64, 22


# ----------------------------------------------------------- bucket policy
def test_bucket_policy_rounding():
    p = BucketPolicy(floor=8, cap=64)
    assert [p.bucket(n) for n in (1, 7, 8, 9, 20, 32, 33, 64)] == \
        [8, 8, 8, 16, 32, 32, 64, 64]
    # above the cap: multiples of the cap, not powers of two
    assert p.bucket(65) == 128 and p.bucket(129) == 192
    assert p.buckets_up_to(32) == [8, 16, 32]
    with pytest.raises(ValueError):
        p.bucket(0)
    with pytest.raises(ValueError):
        BucketPolicy(floor=16, cap=8)


def test_bucket_policy_explicit_ladder():
    p = BucketPolicy(buckets=[4, 16])
    assert [p.bucket(n) for n in (1, 4, 5, 16)] == [4, 4, 16, 16]
    assert p.bucket(17) == 32 and p.bucket(33) == 48  # multiples of 16


def test_bucket_policy_from_histogram_learned_ladder():
    """Satellite: the DP places buckets where traffic mass sits, minimizing
    expected dispatched rows under the compile budget."""
    # 100 single-row requests + 5 of size 32: one bucket would pad every
    # singleton to 32 (cost 3360); two buckets [1, 32] cost 260
    p = BucketPolicy.from_histogram([1] * 100 + [32] * 5, max_compiles=2)
    assert repr(p) == "BucketPolicy(buckets=[1, 32])"
    assert p.bucket(1) == 1 and p.bucket(2) == 32
    # K=1 must still cover the max
    p1 = BucketPolicy.from_histogram([1] * 100 + [32] * 5, max_compiles=1)
    assert repr(p1) == "BucketPolicy(buckets=[32])"
    # mass at 9: the pow2 ladder would pad 9 -> 16; the learned one won't
    p9 = BucketPolicy.from_histogram([1, 9, 9, 9, 9, 9, 9, 16],
                                     max_compiles=2)
    assert p9.bucket(9) == 9
    # above the learned top: multiples-of-top overflow rule still applies
    assert p9.bucket(40) % max(9, 16) == 0
    # compile budget >= distinct sizes: exact ladder, zero padding
    px = BucketPolicy.from_histogram([3, 5, 7], max_compiles=8)
    assert [px.bucket(n) for n in (3, 5, 7)] == [3, 5, 7]
    with pytest.raises(ValueError):
        BucketPolicy.from_histogram([], max_compiles=2)
    with pytest.raises(ValueError):
        BucketPolicy.from_histogram([0, 3], max_compiles=2)
    with pytest.raises(ValueError):
        BucketPolicy.from_histogram([3], max_compiles=0)


def test_parallel_inference_row_stats_and_learned_policy(devices):
    """Satellite: stats() records the pre-pad ROW histogram (batch_sizes
    counts coalesced requests) and learned_bucket_policy() trains on it."""
    net = _net(seed=23)
    pi = ParallelInference(net, mesh=make_mesh())
    rng = np.random.default_rng(4)
    for n in (3, 3, 3, 9, 9, 20):
        pi.output(rng.random((n, 4), np.float32))
    st = pi.stats()
    assert st["row_size"]["count"] == 6
    assert st["row_size"]["max"] == 20 and st["row_size"]["p50"] == 6.0
    learned = pi.learned_bucket_policy(max_compiles=3)
    assert learned.bucket(3) == 3 and learned.bucket(9) == 9
    assert learned.bucket(20) == 20
    with pytest.raises(ValueError):
        ParallelInference(net, mesh=make_mesh()).learned_bucket_policy()


def test_bucket_policy_cap_is_never_overshot():
    # a non-power-of-two cap is typically a memory budget: the pow2 ladder
    # must clamp to it, not jump past it
    p = BucketPolicy(floor=8, cap=1000)
    assert p.bucket(600) == 1000
    assert p.bucket(1000) == 1000
    assert p.bucket(1001) == 2000  # above the cap: multiples of the cap


def test_pad_unpad_roundtrip():
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    padded = pad_to_bucket(x, 8)
    assert padded.shape == (8, 4)
    np.testing.assert_array_equal(padded[3:], 0)
    np.testing.assert_array_equal(unpad(padded, 3), x)
    assert pad_to_bucket(x, 3) is x  # no-op keeps identity
    with pytest.raises(ValueError):
        pad_to_bucket(x, 2)


def test_pad_dataset_masks():
    rng = np.random.default_rng(1)
    ds = DataSet(rng.random((5, 4), np.float32),
                 np.eye(3, dtype=np.float32)[rng.integers(0, 3, 5)])
    padded = pad_dataset(ds, 8)
    assert padded.num_examples() == 8
    # fabricated labels mask: ones over real rows, zeros over padding
    np.testing.assert_array_equal(padded.labels_mask,
                                  [1, 1, 1, 1, 1, 0, 0, 0])
    assert padded.features_mask is None
    # sequence data: existing masks pad (fmask with ONES, lmask with zeros)
    seq = DataSet(rng.random((2, 6, 4), np.float32),
                  rng.random((2, 6, 3), np.float32),
                  features_mask=np.ones((2, 6), np.float32),
                  labels_mask=np.ones((2, 6), np.float32))
    pseq = pad_dataset(seq, 4)
    np.testing.assert_array_equal(pseq.features_mask[2:], 1.0)
    np.testing.assert_array_equal(pseq.labels_mask[2:], 0.0)
    # sequence OUTPUT without lmask: the fmask stands in (zero-padded)
    seq2 = DataSet(rng.random((2, 6, 4), np.float32),
                   rng.random((2, 6, 3), np.float32),
                   features_mask=np.ones((2, 6), np.float32))
    assert pad_dataset(seq2, 4).labels_mask.shape == (4, 6)
    # masked-sequence INPUT with 2-D labels (pooled classifier): the
    # fabricated lmask must match the per-example score shape (batch,),
    # NOT the (batch, T) features mask
    clf = DataSet(rng.random((2, 6, 4), np.float32),
                  np.eye(3, dtype=np.float32)[[0, 1]],
                  features_mask=np.ones((2, 6), np.float32))
    pclf = pad_dataset(clf, 4)
    assert pclf.labels_mask.shape == (4,)
    np.testing.assert_array_equal(pclf.labels_mask, [1, 1, 0, 0])


# ------------------------------------------------- shape-stable training
def test_ragged_epoch_single_compile_and_exact_numerics(devices):
    """Acceptance (a): a ragged final batch neither recompiles the train
    step nor changes the training math — the padded rows are masked out of
    the loss with the correct denominator."""
    batches = _ragged_batches()
    assert [b.num_examples() for b in batches] == [64, 64, 22]

    plain = _net(seed=7)
    plain.fit(batches, num_epochs=3)

    bucketed = _net(seed=7)
    bucketed.fit(batches, num_epochs=3, bucket_policy=True)

    assert bucketed.compile_watch.compiles("train") == 1, \
        bucketed.compile_watch.as_dict()
    assert bucketed.compile_watch.dispatches("train") == 9
    # the unbucketed run compiled twice: once for 64 rows, once for 22
    assert plain.compile_watch.compiles("train") == 2
    for a, b in zip(jax.tree_util.tree_leaves(plain.params),
                    jax.tree_util.tree_leaves(bucketed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_fit_fused_bucketed_ragged_group(devices):
    """fit_fused accepts a ragged DataSet list under a bucket policy: the
    whole group runs as one scan program and matches sequential fit()."""
    batches = _ragged_batches()
    seq = _net(seed=3)
    seq.fit(batches, bucket_policy=True)

    fused = _net(seed=3)
    fused.fit_fused(batches, bucket_policy=True)
    assert fused.compile_watch.compiles() == 1
    assert fused.compile_watch.dispatches() == 1
    for a, b in zip(jax.tree_util.tree_leaves(seq.params),
                    jax.tree_util.tree_leaves(fused.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


# -------------------------------------------------- shape-stable serving
def test_bucketed_serving_dispatches_only_warmed_buckets(devices):
    """Acceptance (b): with warmed buckets, a serving run over request
    sizes {1, 3, 7, 20} triggers ZERO compiles and zero un-warmed
    dispatches, and every caller still gets its exact slice."""
    net = _net(seed=9)
    pi = ParallelInference(net, mesh=make_mesh(), batch_limit=16,
                           queue_timeout_ms=30)
    sizes = (1, 3, 7, 20)
    # worst case the worker coalesces all four requests: 31 rows -> 32
    warmed = pi.warmup(np.zeros((1, 4), np.float32), buckets=[8, 16, 32])
    assert warmed == [8, 16, 32]
    compiles_after_warmup = net.compile_watch.compiles()

    rng = np.random.default_rng(2)
    inputs = {n: rng.random((n, 4), np.float32) for n in sizes}
    outs = {}

    def worker(n):
        outs[n] = pi.output_batched(inputs[n])

    threads = [threading.Thread(target=worker, args=(n,)) for n in sizes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    pi.shutdown()

    # compile check FIRST: the verification net.output() calls below use
    # raw (unbucketed) shapes and would legitimately compile
    assert pi.unwarmed_dispatches == 0, pi.stats()
    assert net.compile_watch.compiles() == compiles_after_warmup
    for n in sizes:
        assert outs[n].shape == (n, 3)
        np.testing.assert_allclose(outs[n], net.output(inputs[n]),
                                   rtol=1e-5, atol=1e-6)
    # every dispatch shape is on the warmed ladder
    assert set(pi.bucket_dispatches) <= set(warmed)

    st = pi.stats()
    assert st["batch_size"]["count"] == st["batches_dispatched"]


def test_warmup_warms_the_exact_live_dispatch_shape(devices):
    """Warmup must dispatch EXACTLY the shape live traffic will dispatch,
    even when the dp-rounded target is not a fixed point of the policy
    (e.g. explicit bucket 6 on a dp=8 mesh: live size-6 requests dispatch
    at 8, and re-bucketing 8 would have compiled 16 instead)."""
    net = _net(seed=21)
    pi = ParallelInference(net, mesh=make_mesh(),
                           bucket_policy=BucketPolicy(buckets=[6]))
    assert pi._pad_target(6) == 8          # 6 -> bucket 6 -> dp multiple 8
    assert pi._pad_target(8) != 8          # 8 is NOT a policy fixed point
    warmed = pi.warmup(np.zeros((1, 4), np.float32), buckets=[6])
    assert warmed == [8]
    compiles_after = net.compile_watch.compiles()
    out = pi.output(np.random.default_rng(0).random((6, 4), np.float32))
    assert out.shape == (6, 3)
    assert pi.unwarmed_dispatches == 0, pi.stats()
    assert net.compile_watch.compiles() == compiles_after


def test_warmed_serving_wave_compiles_nothing(devices):
    """The serving contract under a wave of mixed sizes: ``batch_limit``
    caps coalesced REQUESTS, not rows, so the ladder is warmed up to every
    in-flight client's largest request in one dispatch; the traffic then
    compiles nothing and every dispatch lands on a warmed bucket."""
    n_clients, reqs_per_client, batch_limit = 4, 4, 16
    sizes = [1, 3, 7, 20]
    net = _net(seed=11)
    policy = BucketPolicy(floor=8)
    pi = ParallelInference(net, batch_limit=batch_limit, queue_timeout_ms=3,
                           bucket_policy=policy)
    max_rows = min(batch_limit, n_clients) * max(sizes)
    pi.warmup(np.zeros((1, 4), np.float32),
              buckets=policy.buckets_up_to(max_rows))
    compiles_after_warmup = net.compile_watch.compiles()

    def client(cid):
        r = np.random.default_rng(cid)
        for i in range(reqs_per_client):
            n = sizes[(cid + i) % len(sizes)]
            assert pi.output_batched(r.random((n, 4), np.float32)).shape \
                == (n, 3)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    st = pi.stats()
    pi.shutdown()
    assert st["requests_served"] == n_clients * reqs_per_client
    assert st["model_compiles"] == compiles_after_warmup, st
    assert st["unwarmed_dispatches"] == 0
    assert st["batch_size"]["count"] == st["batches_dispatched"]


def test_ones_mask_cache_is_reused_and_readonly():
    from deeplearning4j_tpu.perf.bucketing import _ones_like_mask
    a = _ones_like_mask((), 5, 8)
    b = _ones_like_mask((), 5, 8)
    assert a is b  # fabricated every batch of every epoch: must be cached
    with pytest.raises(ValueError):
        a[0] = 0.0


def test_sequential_output_path_buckets_too(devices):
    """Satellite: the synchronous output() path rounds up to the bucket
    ladder (it used to pad only to a data-axis multiple — one compiled
    program per distinct size)."""
    net = _net(seed=5)
    pi = ParallelInference(net, mesh=make_mesh())
    rng = np.random.default_rng(3)
    for n in (3, 5, 7):  # all land in the floor bucket (8)
        out = pi.output(rng.random((n, 4), np.float32))
        assert out.shape == (n, 3)
    assert set(pi.bucket_dispatches) == {8}
    # a zero-row request must not poison the dispatch (regression: the
    # bucket ladder rejects n < 1; empty batches bypass it)
    assert pi.output(np.zeros((0, 4), np.float32)).shape == (0, 3)
    # disabling the policy restores pad-to-axis behaviour
    pi_raw = ParallelInference(net, mesh=make_mesh(), bucket_policy=None)
    assert pi_raw._pad_target(3) == 8 and pi_raw._pad_target(9) == 16


def test_batch_size_history_is_bounded(devices):
    """Satellite: batch_sizes must not grow without bound under sustained
    serving."""
    net = _net(seed=6)
    pi = ParallelInference(net, batch_size_history=4, queue_timeout_ms=1)
    x = np.zeros((2, 4), np.float32)
    for _ in range(7):
        pi.output_batched(x)
    assert len(pi.batch_sizes) <= 4
    assert pi.batches_dispatched == 7  # totals still exact
    st = pi.stats()
    assert st["batch_size"]["count"] <= 4 and st["batch_size"]["max"] >= 1
    pi.shutdown()


# -------------------------------------------------------- device prefetch
def test_device_prefetch_bitwise_identical(devices):
    """Acceptance (c): DevicePrefetchIterator changes WHERE arrays live,
    never their values — training through it is bitwise identical on CPU."""
    batches = _ragged_batches(n=128, batch=32)

    plain = _net(seed=11)
    plain.fit(batches, num_epochs=2)

    prefetched = _net(seed=11)
    prefetched.fit(batches, num_epochs=2, prefetch=True)

    for a, b in zip(jax.tree_util.tree_leaves(plain.params),
                    jax.tree_util.tree_leaves(prefetched.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_device_prefetch_yields_device_arrays_and_composes(devices):
    batches = _ragged_batches(n=96, batch=32)
    base = ListDataSetIterator(batches, 32)
    it = DevicePrefetchIterator(AsyncDataSetIterator(base, queue_size=2))
    seen = list(it)
    assert len(seen) == 3
    for got, want in zip(seen, batches):
        assert isinstance(got.features, jax.Array)
        np.testing.assert_array_equal(np.asarray(got.features), want.features)
    # re-iterable: a second pass yields the same stream
    assert len(list(it)) == 3
    assert it.batches_prefetched == 6


def test_device_prefetch_mesh_sharding_and_ragged_passthrough(devices):
    mesh = make_mesh()
    batches = _ragged_batches(n=150, batch=64)  # 64, 64, 22 (ragged tail)
    it = DevicePrefetchIterator(batches, mesh=mesh)
    seen = list(it)
    assert len(seen[0].features.sharding.device_set) == 8
    # the ragged tail passes through as a host array for the trainer to judge
    assert isinstance(seen[-1].features, np.ndarray)
    assert it.batches_prefetched == 2 and it.batches_passed_through == 1


def test_parallel_wrapper_prefetch_matches_and_reports_compiles(devices):
    ds = _ragged_batches(n=144, batch=48)  # 48x3, all shardable over dp=8
    a = _net(seed=13)
    ParallelWrapper(a, mesh=make_mesh()).fit(ds, num_epochs=2)

    b = _net(seed=13)
    pw = ParallelWrapper(b, mesh=make_mesh(), collect_stats=True)
    pw.fit(ds, num_epochs=2, prefetch=True)

    for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                      jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-7)
    st = pw.stats.as_dict()
    assert st["counters"]["model_compiles"] == 1
    assert st["counters"]["model_dispatches"] == 6
    assert "model_compiles" in pw.stats.to_string()


def test_cluster_trainer_fit_prefetch_matches_plain(devices):
    """Satellite (ROADMAP open item): ClusterTrainer prefetch is REAL now —
    the global-batch assembly of batch N+1 is staged through a
    DevicePrefetchIterator while step N runs — and changes nothing
    numerically."""
    from deeplearning4j_tpu.parallel import ClusterTrainer
    ds = _ragged_batches(n=144, batch=48)  # 48x3, all shardable over dp=8
    a = _net(seed=17)
    ClusterTrainer(a, mesh=make_mesh()).fit(ds, num_epochs=2)
    b = _net(seed=17)
    ClusterTrainer(b, mesh=make_mesh()).fit(ds, num_epochs=2, prefetch=True)
    for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                      jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-7)
    assert b.score() is not None


def test_cluster_trainer_fit_local_shard_prefetch_stages_batches(devices):
    """fit_local_shard(prefetch=True) assembles ahead via the place_fn hook;
    a staged (already-global) batch must not be re-assembled at dispatch."""
    from deeplearning4j_tpu.parallel import ClusterTrainer
    ds = _ragged_batches(n=96, batch=48)
    a = _net(seed=19)
    ClusterTrainer(a, mesh=make_mesh()).fit_local_shard(ds, num_epochs=2)
    b = _net(seed=19)
    ClusterTrainer(b, mesh=make_mesh()).fit_local_shard(ds, num_epochs=2,
                                                        prefetch=True)
    for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                      jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-7)


# ----------------------------------------------- ComputationGraph parity
def _graph(seed=5):
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder, MergeVertex
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.optimize.updaters import Adam
    conf = (GraphBuilder()
            .add_inputs("in")
            .add_layer("d1", DenseLayer(n_out=12, activation="relu"), "in")
            .add_layer("d2", DenseLayer(n_out=12, activation="tanh"), "in")
            .add_vertex("merge", MergeVertex(), "d1", "d2")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent",
                                          updater=Adam(0.02)), "merge")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(4))
            .build())
    return ComputationGraph(conf).init()


def test_graph_fit_bucket_policy_single_compile_and_parity(devices):
    """Satellite (ROADMAP open item): ComputationGraph.fit(bucket_policy=)
    pads the ragged tail with masked loss — one compiled train program per
    epoch, same math as the unbucketed run (MLN parity)."""
    batches = _ragged_batches()  # 64, 64, 22
    plain = _graph(seed=5)
    plain.fit(batches, num_epochs=2)
    bucketed = _graph(seed=5)
    bucketed.fit(batches, num_epochs=2, bucket_policy=True)
    assert bucketed.compile_watch.compiles("train") == 1, \
        bucketed.compile_watch.as_dict()
    assert bucketed.compile_watch.dispatches("train") == 6
    assert plain.compile_watch.compiles("train") == 2  # 64-row + 22-row
    for a, b in zip(jax.tree_util.tree_leaves(plain.params),
                    jax.tree_util.tree_leaves(bucketed.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_graph_fit_prefetch_bitwise_identical(devices):
    batches = _ragged_batches(n=128, batch=32)
    plain = _graph(seed=7)
    plain.fit(batches, num_epochs=2)
    pre = _graph(seed=7)
    pre.fit(batches, num_epochs=2, prefetch=True)
    for a, b in zip(jax.tree_util.tree_leaves(plain.params),
                    jax.tree_util.tree_leaves(pre.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_parallel_inference_serves_graph_by_row(devices):
    """A ComputationGraph answers with a LIST of outputs; serving pads,
    coalesces and splits by row, so a padded or coalesced request must
    still get exactly its own rows (found by chip_smoke.py: ResNet50
    behind ModelServer answered with the bucket's padding rows)."""
    net = _graph()
    x = np.random.default_rng(3).random((11, 4), np.float32)
    want = net.output_single(x)
    pi = ParallelInference(net, mesh=make_mesh(dp=1), batch_limit=4,
                           queue_timeout_ms=20)
    try:
        np.testing.assert_allclose(pi.output(x[:3]), want[:3], rtol=1e-6)
        pending = [pi.submit(x[lo:hi]) for lo, hi in ((0, 3), (3, 4),
                                                      (4, 11))]
        got = np.concatenate([p.get() for p in pending])
        np.testing.assert_allclose(got, want, rtol=1e-6)
    finally:
        pi.shutdown()


def test_pad_multi_dataset_masks(devices):
    """pad_multi_dataset fabricates a per-output labels mask with the same
    rules as pad_dataset, and the bucketed graph fit consumes MultiDataSets
    directly."""
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    from deeplearning4j_tpu.perf import pad_multi_dataset
    rng = np.random.default_rng(1)
    mds = MultiDataSet([rng.random((5, 4), np.float32)],
                       [np.eye(3, dtype=np.float32)[rng.integers(0, 3, 5)]])
    p = pad_multi_dataset(mds, 8)
    assert p.num_examples() == 8
    np.testing.assert_array_equal(p.labels_masks[0],
                                  [1, 1, 1, 1, 1, 0, 0, 0])
    assert p.features_masks is None
    # sequence output with an existing labels mask: zero-padded rows
    seq = MultiDataSet([rng.random((2, 6, 4), np.float32)],
                       [rng.random((2, 6, 3), np.float32)],
                       features_masks=[np.ones((2, 6), np.float32)],
                       labels_masks=[np.ones((2, 6), np.float32)])
    ps = pad_multi_dataset(seq, 4)
    np.testing.assert_array_equal(ps.features_masks[0][2:], 1.0)
    np.testing.assert_array_equal(ps.labels_masks[0][2:], 0.0)
    # graph fit over MultiDataSets under a bucket policy == DataSet path
    batches = _ragged_batches()
    mbatches = [MultiDataSet.from_dataset(d) for d in batches]
    g1 = _graph(seed=9)
    g1.fit(batches, num_epochs=1, bucket_policy=True)
    g2 = _graph(seed=9)
    g2.fit(mbatches, num_epochs=1, bucket_policy=True)
    assert g2.compile_watch.compiles("train") == 1
    for a, b in zip(jax.tree_util.tree_leaves(g1.params),
                    jax.tree_util.tree_leaves(g2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ stats plumbing
def test_training_stats_counters():
    from deeplearning4j_tpu.parallel.stats import TrainingStats
    st = TrainingStats()
    st.set_counter("model_compiles", 3)
    st.inc_counter("model_compiles")
    st.inc_counter("widgets", 2)
    d = st.as_dict()
    assert d["counters"] == {"model_compiles": 4, "widgets": 2}
    assert "widgets" in st.to_string()


# ------------------------------------------- where a run leaves its traces
@pytest.mark.parametrize("env, arg, want", [
    ("/from/env", None, "/from/env"),           # placed from outside
    ("/from/env", "/from/arg", "/from/env"),    # ...and nothing overrides it
    (None, "/from/arg", "/from/arg"),
    (None, None, "<checkout>/.jax_cache"),      # fixed: never tmp/pid/time
])
def test_compile_cache_dir_resolution(monkeypatch, env, arg, want):
    from deeplearning4j_tpu.perf import compile_cache
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.resolve_cache_dir(arg) == want.replace(
        "<checkout>", repo)


def test_chip_smoke_refuses_without_a_chip():
    """chip_smoke.py's only tier-1 test: with no accelerator it fails in
    its ``device`` phase, exits non-zero and prints no result line."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=repo,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0, proc.stdout[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    assert [(l.get("phase"), l["ok"]) for l in lines] == [("device", False)]
    assert '"ok": true' not in proc.stdout

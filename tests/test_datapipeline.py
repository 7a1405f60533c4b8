"""Data pipeline tests: record readers, CSV bridge, image iterators,
MultiDataSet iterator family, normalizers.

Mirrors the reference's RecordReaderDataSetiteratorTest.java,
MultiDataSet iterator tests (deeplearning4j-nn/src/test/.../datasets/iterator)
and ND4J normalizer tests.
"""

import os

import numpy as np
import pytest

from deeplearning4j_tpu.datasets import (
    AsyncMultiDataSetIterator, CifarDataSetIterator, CollectionRecordReader,
    CSVRecordReader, CSVSequenceRecordReader, DataSet,
    EarlyTerminationMultiDataSetIterator, EmnistDataSetIterator,
    ImagePreProcessingScaler, IteratorDataSetIterator,
    JointMultiDataSetIterator, LFWDataSetIterator, ListDataSetIterator,
    ListMultiDataSetIterator, MultiDataSet, MultiDataSetIteratorAdapter,
    MultiDataSetWrapperIterator, MultipleEpochsIterator,
    NormalizerMinMaxScaler, NormalizerStandardize,
    RecordReaderDataSetIterator, SamplingDataSetIterator,
    SequenceRecordReaderDataSetIterator, SvhnDataSetIterator,
    TinyImageNetDataSetIterator,
)
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import GraphBuilder, MergeVertex
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.optimize.updaters import Adam


IRISH_CSV = "\n".join(
    f"{5.0 + 0.1 * i},{3.0 + 0.05 * i},{1.5 + 0.2 * i},{0.2 + 0.1 * i},{i % 3}"
    for i in range(30))


def test_csv_record_reader_classification():
    reader = CSVRecordReader(IRISH_CSV)
    it = RecordReaderDataSetIterator(reader, batch_size=10, label_index=4,
                                     num_possible_labels=3)
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].features.shape == (10, 4)
    assert batches[0].labels.shape == (10, 3)
    # one-hot correctness: row i has class i%3
    assert np.argmax(batches[0].labels[4]) == 4 % 3
    # iterating again re-reads from the start (reset contract)
    assert len(list(it)) == 3


def test_csv_record_reader_regression_and_range():
    reader = CSVRecordReader(IRISH_CSV)
    it = RecordReaderDataSetIterator(reader, batch_size=30, label_index=4,
                                     regression=True)
    ds = next(iter(it))
    assert ds.labels.shape == (30, 1)
    assert ds.labels[7, 0] == 7 % 3
    # label range: columns 2..3 as targets
    it2 = RecordReaderDataSetIterator(CSVRecordReader(IRISH_CSV), 30,
                                      regression=True,
                                      label_index_from=2, label_index_to=3)
    ds2 = next(iter(it2))
    assert ds2.features.shape == (30, 3) and ds2.labels.shape == (30, 2)
    assert it2.total_outcomes() == 2


def test_csv_record_reader_skip_and_max_batches():
    src = "h1,h2,h3\n" + "\n".join(f"{i},{i+1},{i % 2}" for i in range(20))
    reader = CSVRecordReader(src, skip_lines=1)
    it = RecordReaderDataSetIterator(reader, 5, label_index=2,
                                     num_possible_labels=2, max_num_batches=2)
    assert len(list(it)) == 2


def test_string_labels_mapped_and_string_features_rejected():
    csv = "\n".join(f"1.0,2.0,{name}" for name in
                    ["setosa", "versicolor", "setosa", "virginica"])
    it = RecordReaderDataSetIterator(CSVRecordReader(csv), 4, label_index=2,
                                     num_possible_labels=3)
    ds = next(iter(it))
    assert ds.labels.shape == (4, 3)
    # first-appearance order: setosa=0, versicolor=1, virginica=2
    assert np.argmax(ds.labels, 1).tolist() == [0, 1, 0, 2]
    # string FEATURE columns fail with a clear message
    bad = RecordReaderDataSetIterator(CSVRecordReader("a,1.0,0\nb,2.0,1"), 2,
                                      label_index=2, num_possible_labels=2)
    with pytest.raises(ValueError, match="Non-numeric"):
        next(iter(bad))


def test_sampling_iterator_distinct_epochs():
    ds = DataSet(np.arange(40, dtype=np.float32).reshape(20, 2),
                 np.zeros((20, 1), np.float32))
    it = SamplingDataSetIterator(ds, batch=4, num_samples=10, seed=9)
    e1 = np.concatenate([b.features for b in it])
    e2 = np.concatenate([b.features for b in it])
    assert len(e1) == 12  # ceil(10/4) * 4: at least num_samples emitted
    assert not np.array_equal(e1, e2)  # re-draws each epoch


def test_collection_record_reader():
    recs = [[0.0, 1.0, 0], [1.0, 0.0, 1], [0.5, 0.5, 0], [0.2, 0.9, 1]]
    it = RecordReaderDataSetIterator(CollectionRecordReader(recs), 2,
                                     label_index=2, num_possible_labels=2)
    batches = list(it)
    assert len(batches) == 2 and batches[0].features.shape == (2, 2)


def test_sequence_record_reader_masks():
    # two ragged sequences: 4 and 2 steps, 2 features + label column
    seq1 = ["0.1,0.2,0", "0.3,0.4,1", "0.5,0.6,0", "0.7,0.8,1"]
    seq2 = ["0.9,1.0,1", "1.1,1.2,0"]
    reader = CSVSequenceRecordReader([seq1, seq2])
    it = SequenceRecordReaderDataSetIterator(reader, batch_size=2,
                                             label_index=2,
                                             num_possible_labels=2)
    ds = next(iter(it))
    assert ds.features.shape == (2, 4, 2)
    assert ds.labels.shape == (2, 4, 2)
    assert ds.features_mask.tolist() == [[1, 1, 1, 1], [1, 1, 0, 0]]
    # padded region zeroed
    assert ds.features[1, 2:].sum() == 0


def test_classification_requires_label_width():
    with pytest.raises(ValueError, match="num_possible_labels"):
        RecordReaderDataSetIterator(CSVRecordReader(IRISH_CSV), 10,
                                    label_index=4)
    with pytest.raises(ValueError, match="num_possible_labels"):
        SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader([["1,2,0"]]), 2, label_index=2)


def test_rebatch_preserves_masks():
    x = np.zeros((7, 4, 2), np.float32)
    y = np.zeros((7, 4, 2), np.float32)
    m = np.zeros((7, 4), np.float32)
    m[:, :2] = 1.0
    src = ListDataSetIterator(DataSet(x, y, m, m), batch=3)
    out = list(IteratorDataSetIterator(src, batch=5))
    assert [b.num_examples() for b in out] == [5, 2]
    assert out[0].features_mask.shape == (5, 4)
    assert out[0].features_mask[:, :2].all() and not out[0].features_mask[:, 2:].any()


def test_async_early_exit_releases_producer():
    import threading
    import time
    before = threading.active_count()
    base = ListMultiDataSetIterator(
        MultiDataSet([np.zeros((64, 2), np.float32)],
                     [np.zeros((64, 1), np.float32)]), batch=2)
    for _ in range(5):
        for i, _mds in enumerate(AsyncMultiDataSetIterator(base, queue_size=2)):
            if i == 1:
                break  # abandon mid-stream
    # producers must terminate once the consumer walks away
    for _ in range(50):
        if threading.active_count() <= before:
            break
        time.sleep(0.05)
    assert threading.active_count() <= before


def test_image_iterators_shapes():
    assert next(iter(CifarDataSetIterator(8, 16))).features.shape == (8, 32, 32, 3)
    em = EmnistDataSetIterator("letters", 8, 16)
    assert next(iter(em)).labels.shape == (8, 26)
    assert EmnistDataSetIterator.num_labels("balanced") == 47
    assert next(iter(SvhnDataSetIterator(4, 8))).features.shape == (4, 32, 32, 3)
    assert next(iter(TinyImageNetDataSetIterator(4, 8))).labels.shape == (4, 200)
    lfw = next(iter(LFWDataSetIterator(4, 8)))
    assert lfw.features.shape[0] == 4 and lfw.features.shape[-1] == 3


def test_iterator_rebatching_and_sampling():
    src = ListDataSetIterator(
        DataSet(np.arange(26, dtype=np.float32).reshape(13, 2),
                np.ones((13, 1), np.float32)), batch=3)  # ragged 3s
    out = list(IteratorDataSetIterator(src, batch=5))
    assert [b.num_examples() for b in out] == [5, 5, 3]
    # order preserved across rebatch
    assert out[1].features[0, 0] == 10.0
    samp = SamplingDataSetIterator(
        DataSet(np.zeros((10, 2), np.float32), np.zeros((10, 1), np.float32)),
        batch=4, num_samples=12)
    assert [b.num_examples() for b in samp] == [4, 4, 4]
    me = MultipleEpochsIterator(3, ListDataSetIterator(
        DataSet(np.zeros((4, 2), np.float32), np.zeros((4, 1), np.float32)), 2))
    assert len(list(me)) == 6


def test_normalizers():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((100, 5)).astype(np.float32) * 3 + 7
    ds = DataSet(x, np.zeros((100, 1), np.float32))
    norm = NormalizerStandardize().fit(ds)
    out = norm.pre_process(ds)
    assert np.allclose(out.features.mean(0), 0, atol=1e-4)
    assert np.allclose(out.features.std(0), 1, atol=1e-3)
    assert np.allclose(norm.revert_features(out.features), x, atol=1e-3)
    mm = NormalizerMinMaxScaler().fit(ds)
    mo = mm.pre_process(ds)
    assert mo.features.min() >= 0 and mo.features.max() <= 1.0001
    img = ImagePreProcessingScaler().pre_process(
        DataSet(np.full((2, 4, 4, 1), 255.0, np.float32),
                np.zeros((2, 1), np.float32)))
    assert img.features.max() == pytest.approx(1.0)


def test_pre_processor_hook_on_iterator():
    x = np.full((8, 3), 10.0, np.float32)
    it = ListDataSetIterator(DataSet(x, np.zeros((8, 1), np.float32)), 4)
    norm = NormalizerStandardize().fit(DataSet(x + np.random.default_rng(0)
                                               .standard_normal((8, 3))
                                               .astype(np.float32),
                                               np.zeros((8, 1))))
    it.set_pre_processor(norm)
    for b in it:
        assert b.features.shape == (4, 3)
        assert abs(b.features.mean()) < 5  # scaled, not raw 10s


def _two_input_graph():
    return ComputationGraph(
        (GraphBuilder()
         .add_inputs("a", "b")
         .add_layer("da", DenseLayer(n_out=8, activation="relu",
                                     updater=Adam(0.01)), "a")
         .add_layer("db", DenseLayer(n_out=8, activation="relu",
                                     updater=Adam(0.01)), "b")
         .add_vertex("m", MergeVertex(), "da", "db")
         .add_layer("out", OutputLayer(n_out=2, activation="softmax",
                                       loss="mcxent", updater=Adam(0.01)), "m")
         .set_outputs("out")
         .set_input_types(InputType.feed_forward(3), InputType.feed_forward(5))
         .build())).init()


def test_joint_and_async_multidataset_cg_fit():
    rng = np.random.default_rng(1)
    n = 24
    a = rng.standard_normal((n, 3)).astype(np.float32)
    b = rng.standard_normal((n, 5)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
    ita = ListDataSetIterator(DataSet(a, y), 8)
    itb = ListDataSetIterator(DataSet(b, y), 8)
    joint = JointMultiDataSetIterator(ita, itb, output_index=0)
    mds = next(iter(joint))
    assert len(mds.features) == 2 and len(mds.labels) == 1
    # async prefetch over the joint stream feeding a ComputationGraph fit
    net = _two_input_graph()
    async_it = AsyncMultiDataSetIterator(joint, queue_size=2)
    net.fit(async_it, num_epochs=2)
    assert net.iteration == 6  # 3 batches x 2 epochs
    assert np.isfinite(net.score())
    # capped variant
    capped = EarlyTerminationMultiDataSetIterator(joint, 2)
    assert len(list(capped)) == 2


def test_mds_adapters_roundtrip():
    x = np.zeros((6, 4), np.float32)
    y = np.zeros((6, 2), np.float32)
    base = ListDataSetIterator(DataSet(x, y), 3)
    mds_it = MultiDataSetIteratorAdapter(base)
    out = list(mds_it)
    assert len(out) == 2 and isinstance(out[0], MultiDataSet)
    back = list(MultiDataSetWrapperIterator(ListMultiDataSetIterator(out)))
    assert isinstance(back[0], DataSet) and back[0].features.shape == (3, 4)
    # batching a single MultiDataSet
    lm = ListMultiDataSetIterator(MultiDataSet([x], [y]), batch=4)
    assert [m.num_examples() for m in lm] == [4, 2]


def test_native_csv_parser():
    from deeplearning4j_tpu.native import native_available, parse_csv_numeric
    if not native_available():
        pytest.skip("native toolchain unavailable")
    data = b"1.5,2.5,0\n3.0,-4.0,1\n"
    mat = parse_csv_numeric(data)
    assert mat.dtype == np.float32 and mat.shape == (2, 3)
    assert mat.tolist() == [[1.5, 2.5, 0.0], [3.0, -4.0, 1.0]]
    # header skip
    assert parse_csv_numeric(b"a,b,c\n1,2,3\n", skip_lines=1).shape == (1, 3)
    # strings / ragged -> None (fallback contract)
    assert parse_csv_numeric(b"1,foo,2\n") is None
    assert parse_csv_numeric(b"1,2\n1,2,3\n") is None


def test_native_and_python_csv_paths_agree():
    from deeplearning4j_tpu.native import native_available
    if not native_available():
        pytest.skip("native toolchain unavailable")
    it = RecordReaderDataSetIterator(CSVRecordReader(IRISH_CSV), 10,
                                     label_index=4, num_possible_labels=3)
    native_batches = list(it)  # numeric source: native bulk path
    # force the Python row path
    reader = CSVRecordReader(IRISH_CSV)
    reader.numeric_matrix = lambda: None
    py_batches = list(RecordReaderDataSetIterator(
        reader, 10, label_index=4, num_possible_labels=3))
    assert len(native_batches) == len(py_batches)
    for a, b in zip(native_batches, py_batches):
        np.testing.assert_allclose(a.features, b.features, atol=1e-6)
        np.testing.assert_array_equal(a.labels, b.labels)


# ---------------------------------------------------------------------------
# fetcher REAL-file parse paths via checked-in-style fixtures (zero-egress:
# the download never runs in CI, so fixture files exercise parse + cache)

def _write_idx(tmp, stem, images, labels, gz=False):
    import gzip as _gzip
    import struct as _struct
    op = (lambda p: _gzip.open(p, "wb")) if gz else (lambda p: open(p, "wb"))
    ext = ".gz" if gz else ""
    n, rows, cols = images.shape
    with op(os.path.join(tmp, f"{stem}-images-idx3-ubyte{ext}")) as f:
        f.write(_struct.pack(">IIII", 2051, n, rows, cols))
        f.write(images.astype(np.uint8).tobytes())
    with op(os.path.join(tmp, f"{stem}-labels-idx1-ubyte{ext}")) as f:
        f.write(_struct.pack(">II", 2049, n))
        f.write(labels.astype(np.uint8).tobytes())


def test_mnist_fetcher_parses_real_idx_files(tmp_path, monkeypatch):
    from deeplearning4j_tpu.datasets import fetchers

    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (7, 28, 28), np.uint8)
    labels = np.arange(7, dtype=np.uint8) % 10
    base = tmp_path / "mnist"
    base.mkdir()
    _write_idx(str(base), "train", imgs, labels)
    _write_idx(str(base), "t10k", imgs[:3], labels[:3], gz=True)  # gz branch
    monkeypatch.setenv("DL4J_TPU_DATA_DIR", str(tmp_path))

    x, y = fetchers.mnist_data(num_examples=7, train=True)
    assert x.shape == (7, 784) and y.shape == (7, 10)
    # REAL file content, not the synthetic fallback
    np.testing.assert_allclose(x[0], imgs[0].reshape(-1) / 255.0, atol=1e-6)
    assert np.argmax(y[0]) == labels[0]

    xt, yt = fetchers.mnist_data(num_examples=3, train=False)
    np.testing.assert_allclose(xt[2], imgs[2].reshape(-1) / 255.0, atol=1e-6)


def test_cifar_fetcher_parses_real_binary_batches(tmp_path, monkeypatch):
    from deeplearning4j_tpu.datasets import fetchers

    rng = np.random.default_rng(1)
    base = tmp_path / "cifar10" / "cifar-10-batches-bin"
    base.mkdir(parents=True)
    n_per = 4
    raws = []
    for i in range(1, 6):
        rec = np.zeros((n_per, 3073), np.uint8)
        rec[:, 0] = rng.integers(0, 10, n_per)
        rec[:, 1:] = rng.integers(0, 256, (n_per, 3072))
        rec.tofile(str(base / f"data_batch_{i}.bin"))
        raws.append(rec)
    monkeypatch.setenv("DL4J_TPU_DATA_DIR", str(tmp_path))

    x, y = fetchers.cifar10_data(num_examples=20, train=True)
    assert x.shape == (20, 32, 32, 3) and y.shape == (20, 10)
    # CHW planar -> NHWC conversion against the first record
    want = raws[0][0, 1:].reshape(3, 32, 32).transpose(1, 2, 0) / 255.0
    np.testing.assert_allclose(x[0], want, atol=1e-6)
    assert np.argmax(y[0]) == raws[0][0, 0]


def test_moving_window_matrix():
    """reference util/MovingWindowMatrix.java"""
    from deeplearning4j_tpu.utils.moving_window import MovingWindowMatrix

    a = np.arange(16).reshape(4, 4)
    w = MovingWindowMatrix(a, 2, 2).windows()
    assert len(w) == 4
    np.testing.assert_array_equal(w[0], [[0, 1], [4, 5]])
    np.testing.assert_array_equal(w[3], [[10, 11], [14, 15]])
    wr = MovingWindowMatrix(a, 2, 2, add_rotate=True).windows()
    assert len(wr) == 16  # each window + 3 rotations
    np.testing.assert_array_equal(wr[1], np.rot90(wr[0], 1))
    with pytest.raises(ValueError):
        MovingWindowMatrix(a, 5, 2)


# ---------------------------------------------------------------- streaming
def test_streaming_iterator_trains_from_producer_thread():
    """An external producer pushes batches while fit() consumes — the
    dl4j-streaming capability (CamelKafkaRouteBuilder.java:1) without the
    Kafka fabric."""
    import threading
    from deeplearning4j_tpu.datasets.streaming import StreamingDataSetIterator
    from deeplearning4j_tpu.nn.conf import (
        InputType, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Adam

    rng = np.random.default_rng(0)
    it = StreamingDataSetIterator(queue_size=4)

    def produce():
        for _ in range(12):
            x = rng.standard_normal((16, 8)).astype(np.float32)
            y = np.eye(2, dtype=np.float32)[(x[:, 0] > 0).astype(int)]
            it.push(x, y)
        it.end()

    t = threading.Thread(target=produce)
    t.start()
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8)).build())
    net = MultiLayerNetwork(conf).init()
    net.fit(it)
    t.join()
    assert it.consumed == 12 and it.pushed == 12
    assert np.isfinite(net.score())
    # a second segment streams through the same iterator
    t2 = threading.Thread(target=lambda: (it.push(
        rng.standard_normal((16, 8)).astype(np.float32),
        np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]), it.end()))
    t2.start()
    net.fit(it)
    t2.join()
    assert it.consumed == 13


def test_streaming_http_receiver():
    import io
    import urllib.request
    from deeplearning4j_tpu.datasets.streaming import (
        StreamingDataSetIterator, StreamingHttpReceiver,
    )
    it = StreamingDataSetIterator()
    recv = StreamingHttpReceiver(it)
    try:
        buf = io.BytesIO()
        np.savez(buf, features=np.ones((4, 3), np.float32),
                 labels=np.zeros((4, 2), np.float32))
        req = urllib.request.Request(
            f"http://127.0.0.1:{recv.port}/push", data=buf.getvalue(),
            method="POST")
        assert urllib.request.urlopen(req).status == 200
        urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{recv.port}/end", data=b"", method="POST"))
        batches = list(it)
        assert len(batches) == 1
        assert batches[0].features.shape == (4, 3)
        assert batches[0].labels.shape == (4, 2)
    finally:
        recv.stop()


# ===================================================== sharded data plane
# datasets/sharded.py (ISSUE 11 tentpole): deterministic distributed
# shuffle, record-range leases, seekable exactly-once resume, and the
# per-record consumption ledger. The multi-process 4→3 SIGKILL acceptance
# lives in tests/test_data_plane.py (slow); everything here is in-process
# tier-1 coverage of the same machinery.

def _dp_records(n=48, width=4, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random((n, width)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, n)]
    return x, y


class TestShardedPlan:
    def test_epoch_order_identical_at_any_world(self):
        from deeplearning4j_tpu.datasets.sharded import ShardedDataset
        x, y = _dp_records()
        sds = ShardedDataset(x, y, batch_size=24, seed=7)
        stacked = {}
        for world in (1, 2, 4):
            readers = [iter(sds.reader(r, world).bind_epoch(lambda: 0))
                       for r in range(world)]
            batches = []
            for _ in range(sds.num_batches):
                parts = [next(it) for it in readers]
                batches.append(np.concatenate([p.features for p in parts]))
            stacked[world] = np.stack(batches)
        np.testing.assert_array_equal(stacked[1], stacked[2])
        np.testing.assert_array_equal(stacked[1], stacked[4])

    def test_epoch_orders_shuffle_and_replay(self):
        from deeplearning4j_tpu.datasets.sharded import ShardedDataset
        x, y = _dp_records()
        sds = ShardedDataset(x, y, batch_size=12, seed=7)
        o0, o1 = sds.epoch_order(0), sds.epoch_order(1)
        assert not np.array_equal(o0, o1)           # epochs reshuffle
        np.testing.assert_array_equal(o0, sds.epoch_order(0))  # replayable
        assert sorted(o0.tolist()) == list(range(48))  # a true permutation
        # a different seed is a different plan
        other = ShardedDataset(x, y, batch_size=12, seed=8)
        assert not np.array_equal(o0, other.epoch_order(0))

    def test_seek_never_fetches_skipped_batches(self):
        from deeplearning4j_tpu.checkpoint.manager import (
            skip_consumed_batches)
        from deeplearning4j_tpu.datasets.sharded import ShardedDataset
        x, y = _dp_records()
        sds = ShardedDataset(x, y, batch_size=12, seed=7)
        fetched = []
        sds.fetch_hook = lambda epoch, batch: fetched.append(batch)
        rd = sds.reader().bind_epoch(lambda: 0)
        full = [ds.features for ds in rd]
        fetched.clear()
        tail = list(skip_consumed_batches(rd, 2))
        assert fetched == [2, 3]  # the seek primitive: nothing before 2
        np.testing.assert_array_equal(tail[0].features, full[2])
        np.testing.assert_array_equal(tail[1].features, full[3])
        with pytest.raises(ValueError, match="seek"):
            list(rd.iter_from(99))

    def test_reader_enforces_equal_shard_contract(self):
        from deeplearning4j_tpu.datasets.sharded import ShardedDataset
        x, y = _dp_records()
        sds = ShardedDataset(x, y, batch_size=10, seed=1)
        with pytest.raises(ValueError, match="divisible"):
            sds.reader(0, 4)
        with pytest.raises(ValueError, match="out of range"):
            sds.reader(4, 4)

    def test_async_wrapper_forwards_seek_and_epoch(self):
        from deeplearning4j_tpu.datasets import AsyncDataSetIterator
        from deeplearning4j_tpu.datasets.sharded import ShardedDataset
        x, y = _dp_records()
        sds = ShardedDataset(x, y, batch_size=12, seed=3)
        wrapped = AsyncDataSetIterator(sds.reader())
        assert hasattr(wrapped, "iter_from")     # forwarded from the base
        wrapped.bind_epoch(lambda: 0)
        ref = [ds.features for ds in sds.reader().bind_epoch(lambda: 0)]
        got = [ds.features for ds in wrapped.iter_from(1)]
        assert len(got) == len(ref) - 1
        np.testing.assert_array_equal(got[0], ref[1])
        # a plain (non-seekable) base does NOT grow the seek surface
        plain = AsyncDataSetIterator(
            ListDataSetIterator(DataSet(x, y), 12))
        assert not hasattr(plain, "iter_from")

    def test_pre_processor_applies_on_seek_and_never_doubles(self):
        # the resumed remainder of an epoch must see the SAME transform
        # as plain iteration — and plain iteration must not apply it twice
        from deeplearning4j_tpu.datasets.sharded import ShardedDataset
        x, y = _dp_records()
        sds = ShardedDataset(x, y, batch_size=12, seed=3)

        def double(ds):
            return DataSet(ds.features * 2.0, ds.labels)
        rd = sds.reader().bind_epoch(lambda: 0).set_pre_processor(double)
        plain = [ds.features for ds in rd]
        seeked = [ds.features for ds in rd.iter_from(1)]
        np.testing.assert_array_equal(seeked[0], plain[1])
        raw = sds.reader().bind_epoch(lambda: 0)
        np.testing.assert_array_equal(plain[0],
                                      next(iter(raw)).features * 2.0)

    def test_device_prefetch_wrapper_forwards_seek_and_epoch(self):
        from deeplearning4j_tpu.datasets import AsyncDataSetIterator
        from deeplearning4j_tpu.datasets.sharded import ShardedDataset
        from deeplearning4j_tpu.perf.prefetch import DevicePrefetchIterator
        x, y = _dp_records()
        sds = ShardedDataset(x, y, batch_size=12, seed=3)
        # the documented composition: Async innermost, prefetch outermost
        wrapped = DevicePrefetchIterator(
            AsyncDataSetIterator(sds.reader()))
        assert hasattr(wrapped, "iter_from")
        wrapped.bind_epoch(lambda: 0)
        ref = [ds.features for ds in sds.reader().bind_epoch(lambda: 0)]
        got = [np.asarray(ds.features) for ds in wrapped.iter_from(1)]
        assert len(got) == len(ref) - 1
        np.testing.assert_array_equal(got[0], ref[1])
        plain = DevicePrefetchIterator(
            ListDataSetIterator(DataSet(x, y), 12))
        assert not hasattr(plain, "iter_from")

    def test_streaming_segment_builds_sharded_dataset(self):
        from deeplearning4j_tpu.datasets.sharded import ShardedDataset
        from deeplearning4j_tpu.datasets.streaming import (
            StreamingDataSetIterator)
        x, y = _dp_records()
        stream = StreamingDataSetIterator()
        for i in range(0, 48, 16):
            stream.push(x[i:i + 16], y[i:i + 16])
        stream.end()
        sds = ShardedDataset.from_iterator(stream, batch_size=12, seed=7)
        assert sds.num_records == 48 and sds.num_batches == 4
        ref = ShardedDataset(x, y, batch_size=12, seed=7)
        np.testing.assert_array_equal(sds.epoch_order(0),
                                      ref.epoch_order(0))
        got = np.concatenate(
            [d.features for d in sds.reader().bind_epoch(lambda: 0)])
        np.testing.assert_array_equal(
            got, x[ref.epoch_order(0)])


class TestShardLeases:
    def test_conflicting_overlap_waits_then_times_out(self):
        from deeplearning4j_tpu.checkpoint import ObjectStoreBackend
        from deeplearning4j_tpu.datasets.sharded import (DataLeaseTimeout,
                                                         ShardLeaseBoard)
        store = ObjectStoreBackend()
        a = ShardLeaseBoard(store, "wa", ttl_s=5.0, wait_s=0.2,
                            poll_s=0.02)
        b = ShardLeaseBoard(store, "wb", ttl_s=5.0, wait_s=0.2,
                            poll_s=0.02)
        a.claim(0, 0, rank=0, world=2)
        # overlapping slice (rows [0,.25) vs [0,.5)) → bounded wait, loud
        with pytest.raises(DataLeaseTimeout, match="held by"):
            b.claim(0, 0, rank=0, world=4)
        assert b.conflicts_waited == 1
        # disjoint slice of the same chunk claims immediately
        b.claim(0, 0, rank=1, world=2)
        a.release_all()
        b.release_all()
        assert store.list("dlease-") == []

    def test_expired_lease_clears_and_stale_generation_fences(self):
        from deeplearning4j_tpu.checkpoint import ObjectStoreBackend
        from deeplearning4j_tpu.datasets.sharded import (
            ShardLeaseBoard, StaleDataLeaseError)
        store = ObjectStoreBackend()
        now = [1000.0]
        clock = lambda: now[0]
        a = ShardLeaseBoard(store, "wa", ttl_s=2.0, wait_s=0.5,
                            clock=clock)
        b = ShardLeaseBoard(store, "wb", ttl_s=2.0, wait_s=0.5,
                            clock=clock)
        a.claim(0, 0, rank=0, world=1, generation=1)
        now[0] += 3.0   # the SIGKILLed holder's lease simply expires
        b.claim(0, 0, rank=0, world=1, generation=2)
        # ...and the zombie coming back for a range the NEWER generation
        # holds: the data-plane half of the split-brain fence
        with pytest.raises(StaleDataLeaseError, match="stale"):
            a.claim(0, 0, rank=0, world=1, generation=1)

    def test_flaky_storage_rides_retries_without_double_claim(self):
        """ISSUE 11 satellite: FlakyBackend chaos aimed at the
        shard-lease objects (match= prefix) is ridden out by
        RetryingBackend, and the idempotent claim + read-back means a
        retried put can never double-claim a range."""
        from deeplearning4j_tpu.checkpoint import (FlakyBackend,
                                                   ObjectStoreBackend,
                                                   RetryingBackend)
        from deeplearning4j_tpu.datasets.sharded import (DATA_LEASE_PREFIX,
                                                         ShardLeaseBoard)
        inner = ObjectStoreBackend()
        flaky = FlakyBackend(inner, seed=3, transient_rate=0.35,
                             match=DATA_LEASE_PREFIX)
        board = ShardLeaseBoard(
            RetryingBackend(flaky, max_retries=8, base_backoff_s=0.0),
            "wf", ttl_s=30.0)
        for c in range(8):
            board.claim(0, c, rank=0, world=1)
        assert flaky.faults_injected > 0   # the chaos actually happened
        assert board.claims == 8
        leases = inner.list(DATA_LEASE_PREFIX)
        assert len(leases) == 8            # exactly one claim per chunk
        import json as _json
        for name in leases:
            rec = _json.loads(inner.get(name).decode())
            assert rec["worker"] == "wf"
            assert rec["incarnation"] == board.incarnation


class TestConsumptionLedger:
    def test_exactly_once_resume_is_bitwise_with_clean_ledger(self):
        """Single-process acceptance slice: kill mid-epoch with per-step
        checkpoints → train_until restores, the reader SEEKS to the exact
        batch, the final params are bitwise-identical to the
        uninterrupted run, and the ledger shows every record exactly once
        per epoch in exactly the planned order."""
        import jax
        from deeplearning4j_tpu.checkpoint import (CheckpointManager,
                                                   FaultInjector,
                                                   ObjectStoreBackend)
        from deeplearning4j_tpu.checkpoint import sharded as shd
        from deeplearning4j_tpu.checkpoint.resume import (RestartPolicy,
                                                          train_until)
        from deeplearning4j_tpu.datasets.sharded import (ShardedDataset,
                                                         reconcile_ledger)
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.optimize.updaters import Sgd

        def net():
            conf = (NeuralNetConfiguration.builder().seed(5)
                    .updater(Sgd(learning_rate=0.05))
                    .weight_init("xavier").list()
                    .layer(DenseLayer(n_out=8, activation="tanh"))
                    .layer(OutputLayer(n_out=3, loss="mcxent"))
                    .set_input_type(InputType.feed_forward(4)).build())
            return MultiLayerNetwork(conf).init()

        x, y = _dp_records()
        ref_sds = ShardedDataset(x, y, batch_size=12, seed=9)
        ref = net()
        ref.fit(ref_sds.reader(), num_epochs=3)
        ref_sha = shd.state_sha(ref)

        dstore = ObjectStoreBackend()
        sds = ShardedDataset(x, y, batch_size=12, seed=9, store=dstore,
                             ledger=True)
        cm = CheckpointManager(storage=ObjectStoreBackend(),
                               save_every_n_steps=1, async_write=False)
        victim = net()
        victim.set_listeners(FaultInjector(kill_at_step=7))  # mid-epoch 2
        summary = train_until(
            victim, sds.reader(), num_epochs=3, checkpoint_manager=cm,
            restart_policy=RestartPolicy(max_restarts=3, backoff_s=0.0))
        assert summary.completed and summary.restarts == 1
        assert shd.state_sha(summary.model) == ref_sha
        report = reconcile_ledger(dstore, batch_size=12)
        assert report.clean
        assert report.contested == []     # same generation: keyed rewrite
        for e in range(3):
            assert report.epochs[e] == sds.epoch_order(e).tolist()
        cm.close()

    def test_reconcile_highest_generation_wins(self):
        """A batch whose first training attempt was rolled back by a
        restore may be re-consumed by a LATER generation at a different
        world size: the newer cover is authoritative, the batch is
        reported contested, and no record counts twice."""
        import json as _json
        from deeplearning4j_tpu.checkpoint import ObjectStoreBackend
        from deeplearning4j_tpu.datasets.sharded import (LEDGER_PREFIX,
                                                         reconcile_ledger)
        store = ObjectStoreBackend()

        def put(batch, rank, world, gen, records):
            name = (f"{LEDGER_PREFIX}e0000-b{batch:06d}-"
                    f"r{rank:03d}of{world:03d}")
            store.put(name, _json.dumps({
                "epoch": 0, "batch": batch, "rank": rank, "world": world,
                "generation": gen, "worker": f"w{rank}",
                "records": records}).encode())
        # batch 0: consumed once at world 4, gen 1 (records 0..11)
        for r in range(4):
            put(0, r, 4, 1, list(range(r * 3, r * 3 + 3)))
        # batch 1 (records 12..23): in-flight at gen 1 world 4 when the
        # fleet shrank, rolled back by the restore, re-consumed at gen 2
        # world 3 — the 4→3 reshard shape
        for r in range(4):
            put(1, r, 4, 1, list(range(12 + r * 3, 12 + r * 3 + 3)))
        for r in range(3):
            put(1, r, 3, 2, list(range(12 + r * 4, 12 + r * 4 + 4)))
        rep = reconcile_ledger(store, batch_size=12)
        assert rep.clean                       # no dups, no gaps
        assert rep.epochs[0] == list(range(24))  # gen-2 cover counted once
        assert rep.contested == [(0, 1, [1, 2])]
        # ...and a TORN newer cover (missing rank) can never pass silently
        store.delete(f"{LEDGER_PREFIX}e0000-b000001-r002of003")
        rep2 = reconcile_ledger(store, batch_size=12)
        assert (0, 1) in rep2.gaps

    def test_reconcile_duplicate_record_detected(self):
        import json as _json
        from deeplearning4j_tpu.checkpoint import ObjectStoreBackend
        from deeplearning4j_tpu.datasets.sharded import (LEDGER_PREFIX,
                                                         reconcile_ledger)
        store = ObjectStoreBackend()
        for batch, recs in ((0, [0, 1, 2]), (1, [2, 3, 4])):  # 2 repeats
            store.put(f"{LEDGER_PREFIX}e0000-b{batch:06d}-r000of001",
                      _json.dumps({"epoch": 0, "batch": batch, "rank": 0,
                                   "world": 1, "generation": 0,
                                   "worker": "w", "records": recs}).encode())
        rep = reconcile_ledger(store, batch_size=3)
        assert rep.duplicates == [(0, 2)]
        assert not rep.clean

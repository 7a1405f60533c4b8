"""quant/ tier: post-training int8 quantization.

Covers the PTQ contract end to end: observer math on known distributions,
bitwise-deterministic calibration records, per-channel int8 lowering
numerics (dense/conv/output, int32 accumulation, one requantize), the
fp32 fallback boundary on mixed CNN→LSTM stacks, zero-host-sync quantized
predict (trace_check-gated), compile-once-per-bucket serving, accuracy
gates on every zoo CNN + keras imports (≤1pp top-1 / ≤1% relative loss),
model-zip + CheckpointManager round-trips, hot-swap re-quantization under
concurrent load with zero dropped requests, the binary/int8 predict wire
format, and the offline CLI.
"""

import base64
import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request
import zlib

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.convolutional import (Convolution1DLayer,
                                                      ConvolutionLayer)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.normalization import BatchNormalization
from deeplearning4j_tpu.nn.conf.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.quant import (CalibrationRecord, MinMaxObserver,
                                      PercentileObserver, accuracy_delta,
                                      assert_accuracy_within, calibrate,
                                      input_quant_scale, is_quantized,
                                      make_observer, param_bytes, quantize,
                                      quantized_layers)
from deeplearning4j_tpu.quant.lowering import (QuantizedConvolution1DLayer,
                                               QuantizedDenseLayer,
                                               QuantizedOutputLayer,
                                               quantize_weights)


def _dense_net(seed=7, n_in=12, n_out=4):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(learning_rate=0.05))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(DenseLayer(n_out=32, activation="tanh"))
            .layer(OutputLayer(n_out=n_out, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def _cnn_bn_net(seed=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.05))
            .list()
            .layer(ConvolutionLayer(n_out=6, kernel_size=(3, 3),
                                    convolution_mode="same",
                                    activation="identity", has_bias=False))
            .layer(BatchNormalization())
            .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                    convolution_mode="same",
                                    activation="relu"))
            .layer(OutputLayer(n_out=5, loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 3))
            .build())
    return MultiLayerNetwork(conf).init()


def _cnn_lstm_net(seed=11):
    """Mixed stack: the conv front quantizes, the recurrent tail (LSTM +
    RnnOutputLayer, per-timestep loss) must fall back to fp32."""
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.05))
            .list()
            .layer(Convolution1DLayer(n_out=8, kernel_size=3,
                                      convolution_mode="same",
                                      activation="relu"))
            .layer(LSTM(n_out=6, activation="tanh"))
            .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.recurrent(5, 10))
            .build())
    return MultiLayerNetwork(conf).init()


def _batches(n, bs, shape, seed=0, n_classes=None):
    rng = np.random.default_rng(seed)
    xs = [rng.standard_normal((bs,) + shape).astype(np.float32)
          for _ in range(n)]
    if n_classes is None:
        return xs
    return [DataSet(x, np.eye(n_classes, dtype=np.float32)[
        rng.integers(0, n_classes, bs)]) for x in xs]


# --------------------------------------------------------------- observers
class TestObservers:
    def test_minmax_math(self):
        o = MinMaxObserver()
        o.update(-0.5, 2.0, 2.0)    # p=100 ⇒ pct_amax IS max|x|
        o.update(-3.0, 1.0, 3.0)
        assert o.min == -3.0 and o.max == 2.0
        assert o.amax() == 3.0
        assert o.scale() == pytest.approx(3.0 / 127.0)
        e = o.entry()
        assert e == {"min": -3.0, "max": 2.0, "amax": 3.0,
                     "scale": pytest.approx(3.0 / 127.0), "zero_point": 0}

    def test_percentile_math(self):
        o = PercentileObserver(99.0)
        for amax in (1.0, 2.0, 3.0):
            o.update(-amax, amax, amax)
        # mean of per-batch percentiles, not the max
        assert o.amax() == pytest.approx(2.0)
        assert o.scale() == pytest.approx(2.0 / 127.0)
        assert o.percentile == 99.0

    def test_percentile_validation(self):
        with pytest.raises(ValueError, match="percentile"):
            PercentileObserver(0.0)
        with pytest.raises(ValueError, match="percentile"):
            PercentileObserver(101.0)

    def test_zero_stream_scale_floor(self):
        o = MinMaxObserver()
        o.update(0.0, 0.0, 0.0)
        assert o.scale() > 0.0  # an all-zero layer still gets a usable grid

    def test_make_observer(self):
        assert isinstance(make_observer("minmax"), MinMaxObserver)
        p = make_observer("percentile", 99.5)
        assert isinstance(p, PercentileObserver) and p.percentile == 99.5
        with pytest.raises(ValueError, match="Unknown observer"):
            make_observer("entropy")

    def test_quantize_weights_per_channel(self):
        w = np.array([[1.0, -0.01], [-2.0, 0.02]], np.float32)
        q, s = quantize_weights(w)
        assert q.dtype == np.int8 and s.shape == (2,)
        # each OUTPUT channel uses its own grid: both columns reach ±127
        np.testing.assert_array_equal(np.abs(q).max(axis=0), [127, 127])
        np.testing.assert_allclose(q * s, w, atol=float(s.max()) / 2)


# -------------------------------------------------------------- calibration
class TestCalibration:
    def test_record_bitwise_deterministic(self):
        net = _dense_net()
        r1 = calibrate(net, _batches(4, 8, (12,), seed=5))
        r2 = calibrate(net, _batches(4, 8, (12,), seed=5))
        assert r1.to_json() == r2.to_json()  # bitwise, via sorted-key JSON
        r3 = calibrate(net, _batches(4, 8, (12,), seed=6))
        assert r3.to_json() != r1.to_json()  # actually data-dependent

    def test_record_json_roundtrip(self, tmp_path):
        net = _dense_net()
        rec = calibrate(net, _batches(2, 8, (12,)), observer="percentile",
                        percentile=99.9)
        back = CalibrationRecord.from_json(rec.to_json())
        assert back == rec
        p = str(tmp_path / "cal.json")
        rec.save(p)
        assert CalibrationRecord.load(p) == rec
        assert rec.observer == "percentile" and rec.percentile == 99.9
        assert all(v["zero_point"] == 0 for v in rec.ranges.values())

    def test_percentile_vs_minmax_on_heavy_tail(self):
        """A single huge outlier inflates the minmax scale but barely moves
        the percentile scale — the reason the percentile observer exists."""
        net = _dense_net()
        xs = _batches(4, 64, (12,), seed=1)
        xs[2][0, 0] = 1e4  # one pathological activation at the input layer
        r_mm = calibrate(net, xs, observer="minmax")
        r_pc = calibrate(net, xs, observer="percentile", percentile=99.0)
        amax_mm = r_mm.ranges["layer0"]["amax"]
        amax_pc = r_pc.ranges["layer0"]["amax"]
        assert amax_mm == pytest.approx(1e4)
        assert amax_pc < 10.0  # the tail was clipped, the bulk kept
        assert r_pc.ranges["layer0"]["max"] == pytest.approx(1e4)  # observed

    def test_empty_stream_and_unquantizable_net_raise(self):
        net = _dense_net()
        with pytest.raises(ValueError, match="empty batch stream"):
            calibrate(net, [])
        conf = (NeuralNetConfiguration.builder().seed(1).updater(Sgd(0.1))
                .list()
                .layer(LSTM(n_out=4, activation="tanh"))
                .layer(RnnOutputLayer(n_out=2, loss="mcxent"))
                .set_input_type(InputType.recurrent(3, 6))
                .build())
        rnn = MultiLayerNetwork(conf).init()
        with pytest.raises(ValueError, match="no quantizable layer"):
            calibrate(rnn, _batches(1, 4, (6, 3)))

    def test_signature_mismatch_refused(self):
        rec = calibrate(_dense_net(), _batches(2, 8, (12,)))
        other = _dense_net(n_in=12, n_out=7)  # different head width
        with pytest.raises(ValueError, match="does not match"):
            quantize(other, rec)
        with pytest.raises(TypeError, match="CalibrationRecord"):
            quantize(_dense_net(), {"layer0": 0.1})


# ----------------------------------------------------------------- lowering
class TestLowering:
    def test_dense_numerics_bytes_and_metrics(self):
        from deeplearning4j_tpu.obs.registry import get_registry
        net = _dense_net()
        data = _batches(4, 16, (12,), n_classes=4)
        rec = calibrate(net, (d.features for d in data))
        q = quantize(net, rec)
        assert q is not net and is_quantized(q) and not is_quantized(net)
        keys = [k for k, _ in quantized_layers(q)]
        assert keys == ["layer0", "layer1", "layer2"]
        assert isinstance(q.layers[0], QuantizedDenseLayer)
        assert isinstance(q.layers[2], QuantizedOutputLayer)
        for p in q.params:
            assert np.asarray(p["Wq"]).dtype == np.int8
            assert np.asarray(p["w_scale"]).dtype == np.float32
        assert param_bytes(net) / param_bytes(q) >= 3.0
        assert input_quant_scale(q) == pytest.approx(
            rec.ranges["layer0"]["scale"])
        report = assert_accuracy_within(
            accuracy_delta(net, q, data), agreement_floor=0.95)
        assert report["examples"] == 64
        reg = get_registry()
        assert reg.metric("quant_model_bytes").value == param_bytes(q)
        assert reg.metric("quant_accuracy_delta").value == \
            report["top1_delta"]

    def test_bn_is_folded_before_lowering(self):
        net = _cnn_bn_net()
        data = _batches(3, 8, (8, 8, 3), n_classes=5)
        # BN warm-up so running stats are non-trivial
        for d in data:
            net.fit(d)
        rec = calibrate(net, (d.features for d in data))
        q = quantize(net, rec)
        assert not any(isinstance(l, BatchNormalization) for l in q.layers)
        assert len(quantized_layers(q)) == 3  # both convs + the output head
        assert_accuracy_within(accuracy_delta(net, q, data),
                               agreement_floor=0.95)

    def test_mixed_cnn_lstm_fp32_fallback_boundary(self):
        net = _cnn_lstm_net()
        xs = _batches(3, 8, (10, 5), seed=2)
        rec = calibrate(net, xs)
        q = quantize(net, rec)
        # the conv front lowered, the recurrent tail untouched — including
        # RnnOutputLayer, which is a BaseOutputLayer SUBCLASS, not an
        # OutputLayer: exact-type matching keeps it fp32
        assert [k for k, _ in quantized_layers(q)] == ["layer0"]
        assert isinstance(q.layers[0], QuantizedConvolution1DLayer)
        assert isinstance(q.layers[1], LSTM)
        assert isinstance(q.layers[2], RnnOutputLayer)
        # fallback params ride over bitwise — fp32 layers are NOT requantized
        for i in (1, 2):
            for k, v in net.params[i].items():
                np.testing.assert_array_equal(np.asarray(v),
                                              np.asarray(q.params[i][k]))
        # the dequant boundary hands the LSTM ordinary f32 activations:
        # end-to-end outputs stay close to the fp32 reference
        out_f = np.asarray(net.output(xs[0]))
        out_q = np.asarray(q.output(xs[0]))
        assert out_q.dtype == np.float32
        np.testing.assert_allclose(out_q, out_f, atol=5e-2)
        assert np.abs(out_q - out_f).mean() < 5e-3

    def test_quantized_predict_zero_host_sync(self):
        """The int8 predict is ONE jitted XLA program: driving it on device
        arrays performs no host-device sync and no recompile — quantize/
        dequantize/requantize are all inside the trace (the only sync in
        ``output()`` is the terminal result fetch, same as fp32)."""
        import jax.numpy as jnp
        from deeplearning4j_tpu import analysis
        net = _dense_net()
        q = quantize(net, calibrate(net, _batches(2, 8, (12,))))
        fn = q._get_jitted("output")
        x = jnp.zeros((8, 12), jnp.float32)
        fn(q.params, q.state, x, None)  # compile outside the region
        with analysis.trace_check(model=q) as report:
            out = fn(q.params, q.state, x, None)
            out.block_until_ready()
        assert report.sync_points == [], report.summary()
        assert report.recompiles == [], report.summary()
        assert report.captured_constants == [], report.summary()


# ------------------------------------------------------------ zoo + keras
def _zoo_cnn_cases():
    from deeplearning4j_tpu.models import (AlexNet, Darknet19,
                                           FaceNetNN4Small2, GoogLeNet,
                                           InceptionResNetV1, LeNet,
                                           ResNet50, SimpleCNN, TinyYOLO,
                                           VGG16, VGG19)
    return [
        ("LeNet", lambda: LeNet(num_classes=10).init(), (28, 28, 1), 10),
        ("SimpleCNN",
         lambda: SimpleCNN(num_classes=5, input_shape=(32, 32, 3)).init(),
         (32, 32, 3), 5),
        ("AlexNet",
         lambda: AlexNet(num_classes=7, input_shape=(96, 96, 3)).init(),
         (96, 96, 3), 7),
        ("VGG16",
         lambda: VGG16(num_classes=10, input_shape=(32, 32, 3)).init(),
         (32, 32, 3), 10),
        ("VGG19",
         lambda: VGG19(num_classes=10, input_shape=(32, 32, 3)).init(),
         (32, 32, 3), 10),
        ("ResNet50",
         lambda: ResNet50(num_classes=11, input_shape=(64, 64, 3)).init(),
         (64, 64, 3), 11),
        ("Darknet19",
         lambda: Darknet19(num_classes=6, input_shape=(32, 32, 3)).init(),
         (32, 32, 3), 6),
        ("TinyYOLO",
         lambda: TinyYOLO(num_classes=3, input_shape=(32, 32, 3)).init(),
         (32, 32, 3), 3),
        ("GoogLeNet",
         lambda: GoogLeNet(num_classes=10, input_shape=(64, 64, 3)).init(),
         (64, 64, 3), 10),
        ("InceptionResNetV1",
         lambda: InceptionResNetV1(num_classes=4,
                                   input_shape=(96, 96, 3)).init(),
         (96, 96, 3), 4),
        ("FaceNetNN4Small2",
         lambda: FaceNetNN4Small2(num_classes=3,
                                  input_shape=(96, 96, 3)).init(),
         (96, 96, 3), 3),
    ]


@pytest.mark.parametrize("name,builder,shape,n_classes", _zoo_cnn_cases(),
                         ids=[c[0] for c in _zoo_cnn_cases()])
def test_zoo_cnn_accuracy_gate(name, builder, shape, n_classes):
    """Acceptance: quantize() produces an int8 serving graph for EVERY zoo
    CNN with top-1/loss delta within the ≤1% budget vs fp32."""
    net = builder()
    data = _batches(3, 4, shape, seed=zlib.crc32(name.encode()),
                    n_classes=n_classes)
    rec = calibrate(net, (d.features for d in data))
    q = quantize(net, rec)
    assert is_quantized(q) and len(quantized_layers(q)) >= 2
    assert param_bytes(net) / param_bytes(q) >= 3.0, name
    assert_accuracy_within(accuracy_delta(net, q, data),
                           top1_budget=0.01, loss_budget=0.01)


class TestKerasImport:
    def test_keras_cnn_gate(self, tmp_path):
        keras = pytest.importorskip("keras")
        from deeplearning4j_tpu.modelimport.keras import \
            import_keras_sequential_model_and_weights
        # keras inits from a GLOBAL rng: pin it so the imported weights
        # don't depend on which keras tests ran earlier in the process
        keras.utils.set_random_seed(7)
        m = keras.Sequential([
            keras.layers.Input((12, 12, 1)),
            keras.layers.Conv2D(4, (3, 3), activation="relu"),
            keras.layers.MaxPooling2D((2, 2)),
            keras.layers.Conv2D(6, (3, 3), activation="relu",
                                padding="same"),
            keras.layers.Flatten(),
            keras.layers.Dense(16, activation="relu"),
            keras.layers.Dense(3, activation="softmax"),
        ])
        m.compile(loss="categorical_crossentropy", optimizer="sgd")
        path = str(tmp_path / "cnn.h5")
        m.save(path)
        net = import_keras_sequential_model_and_weights(path)
        data = _batches(3, 8, (12, 12, 1), seed=4, n_classes=3)
        # brief training separates the logits: the gate then measures real
        # disagreement, not coin-flips between a random init's near-ties
        net.fit(data, num_epochs=2)
        rec = calibrate(net, (d.features for d in data))
        q = quantize(net, rec)
        assert len(quantized_layers(q)) >= 4  # both convs + both denses
        assert_accuracy_within(accuracy_delta(net, q, data),
                               top1_budget=0.01, loss_budget=0.01)

    def test_keras_lstm_mixed_fallback(self, tmp_path):
        keras = pytest.importorskip("keras")
        from deeplearning4j_tpu.modelimport.keras import \
            import_keras_sequential_model_and_weights
        keras.utils.set_random_seed(4321)
        m = keras.Sequential([
            keras.layers.Input((7, 5)),
            keras.layers.LSTM(12, return_sequences=True),
            keras.layers.LSTM(8),
            keras.layers.Dense(4, activation="softmax"),
        ])
        m.compile(loss="categorical_crossentropy", optimizer="sgd")
        path = str(tmp_path / "lstm.h5")
        m.save(path)
        net = import_keras_sequential_model_and_weights(path)
        xs = _batches(2, 6, (7, 5), seed=9)
        q = quantize(net, calibrate(net, xs))
        qkeys = [k for k, _ in quantized_layers(q)]
        assert qkeys, "imported Dense head should quantize"
        assert all(not isinstance(l, LSTM) for _, l in quantized_layers(q))
        np.testing.assert_allclose(np.asarray(q.output(xs[0])),
                                   np.asarray(net.output(xs[0])), atol=2e-2)


# ------------------------------------------------------------ serialization
class TestSerialization:
    def test_model_zip_roundtrip_exact(self, tmp_path):
        from deeplearning4j_tpu.utils.serialization import (restore,
                                                            write_model)
        net = _dense_net()
        rec = calibrate(net, _batches(2, 8, (12,)))
        q = quantize(net, rec)
        x = np.random.default_rng(3).standard_normal((5, 12)).astype(
            np.float32)
        want = np.asarray(q.output(x))
        p = str(tmp_path / "q.zip")
        write_model(q, p, save_updater=False)
        back = restore(p, load_updater=False)
        assert is_quantized(back)
        assert back._quant_calibration == rec  # the record rode along
        # identical int8 weights + scales ⇒ identical predict, bitwise
        np.testing.assert_array_equal(np.asarray(back.output(x)), want)

    def test_checkpoint_manager_roundtrip(self, tmp_path):
        from deeplearning4j_tpu.checkpoint import CheckpointManager
        net = _dense_net()
        rec = calibrate(net, _batches(2, 8, (12,)))
        q = quantize(net, rec)
        x = np.random.default_rng(4).standard_normal((3, 12)).astype(
            np.float32)
        want = np.asarray(q.output(x))
        cm = CheckpointManager(str(tmp_path / "ck"), async_write=False)
        try:
            cm.save(q)
            back = cm.restore_latest(load_updater=False)
        finally:
            cm.close()
        assert is_quantized(back)
        assert back._quant_calibration == rec
        np.testing.assert_array_equal(np.asarray(back.output(x)), want)


# ---------------------------------------------------------------- serving
class TestServing:
    def test_parallel_inference_quantize_parity_and_buckets(self):
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        net = _dense_net()
        rec = calibrate(net, _batches(2, 8, (12,)))
        q_ref = quantize(net, rec)
        pi = ParallelInference(net, quantize=rec, batch_limit=16,
                               inference_mode="sequential")
        try:
            assert pi.quantized and is_quantized(pi.model)
            assert pi.stats()["quantized"] is True
            x = np.random.default_rng(5).standard_normal((6, 12)).astype(
                np.float32)
            np.testing.assert_allclose(np.asarray(pi.output(x)),
                                       np.asarray(q_ref.output(x)),
                                       rtol=1e-6, atol=1e-7)
            # the caller's model is untouched
            assert not is_quantized(net)
            # compile once per bucket: warmup compiles the ladder, then
            # mixed-size traffic inside those buckets adds NO compiles
            warmed = pi.warmup(x[:1], buckets=[8, 16])
            assert warmed == [8, 16]
            cw = pi.model.compile_watch
            before = cw.compiles()
            for n in (1, 3, 6, 8, 11, 16):
                pi.output(x[:1].repeat(n, axis=0))
            assert cw.compiles() == before, cw.as_dict()
        finally:
            pi.shutdown()

    def test_hot_swap_requantizes_under_load_zero_dropped(self):
        """A quantized endpoint hot-swaps a NEWER fp32 checkpoint under
        concurrent traffic: the swap re-applies the same calibration, no
        request is dropped, and post-swap answers match quantize(new)."""
        from deeplearning4j_tpu.checkpoint import (CheckpointManager,
                                                   ObjectStoreBackend)
        from deeplearning4j_tpu.serving import ModelServer
        store = {}
        trainer_cm = CheckpointManager(storage=ObjectStoreBackend(store),
                                       async_write=False)
        trainer = _dense_net(seed=21)
        data = _batches(3, 16, (12,), seed=7, n_classes=4)
        trainer.fit(data, num_epochs=1)
        trainer_cm.save(trainer)
        serve_cm = CheckpointManager(storage=ObjectStoreBackend(store))
        served = serve_cm.restore_latest(load_updater=False)
        rec = calibrate(served, (d.features for d in data))
        srv = ModelServer()
        ep = srv.add_model("m", served, quantize=rec,
                           warmup_example=np.zeros((1, 12), np.float32))
        ep.pi.start_hot_swap(serve_cm)  # manual polls: deterministic
        srv.start(warmup=True, warmup_async=False)
        x = np.asarray(data[0].features[:4])
        results, lock = [], threading.Lock()
        stop = threading.Event()

        def client():
            while not stop.is_set():
                body = json.dumps({"inputs": x.tolist()}).encode()
                req = urllib.request.Request(
                    f"{srv.address}/v1/models/m:predict", data=body,
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=30) as r:
                        code = r.status
                        r.read()
                except urllib.error.HTTPError as e:
                    code = e.code
                with lock:
                    results.append(code)

        threads = [threading.Thread(target=client) for _ in range(4)]
        try:
            assert ep.quantized and ep.input_scale is not None
            for t in threads:
                t.start()
            # newer fp32 checkpoint commits while clients hammer predict
            trainer.fit(data, num_epochs=2)
            trainer_cm.save(trainer)
            deadline = 50
            while ep.pi.poll_checkpoint() is not True and deadline:
                deadline -= 1
            assert deadline, "hot-swap never observed the new checkpoint"
            stop.set()
            for t in threads:
                t.join(timeout=30)
            with lock:
                assert results and all(c == 200 for c in results), \
                    [c for c in results if c != 200]
            st = ep.pi.stats()
            assert st["hot_swap"]["swaps"] == 1
            assert st["quantized"] is True and is_quantized(ep.pi.model)
            # post-swap answers are the NEW weights' int8 lowering
            want = np.asarray(quantize(trainer, rec).output(x))
            code, out = _predict(srv.address, "m", {"inputs": x.tolist()})
            assert code == 200
            np.testing.assert_allclose(np.asarray(out["outputs"],
                                                  np.float32),
                                       want, rtol=1e-4, atol=1e-5)
        finally:
            stop.set()
            srv.stop(drain=False)
            trainer_cm.close()
            serve_cm.close()

    def test_binary_wire_format_parity_and_errors(self):
        from deeplearning4j_tpu.serving import ModelServer
        net = _dense_net(seed=31)
        rec = calibrate(net, _batches(2, 8, (12,)))
        # no warmup: the first request pays the bucket compile, which can
        # exceed the server's default 1s deadline on a busy host
        srv = ModelServer({"fp32": net}, default_deadline_ms=60_000)
        srv.add_model("q", net, quantize=rec)
        srv.start(warmup=False)
        try:
            base = srv.address
            x = np.random.default_rng(6).standard_normal((4, 12)).astype(
                np.float32)
            b64 = base64.b64encode(x.tobytes()).decode()
            for model in ("fp32", "q"):
                code, o_json = _predict(base, model, {"inputs": x.tolist()})
                assert code == 200
                code, o_b64 = _predict(base, model, {
                    "x_b64": b64, "dtype": "float32", "shape": [4, 12]})
                assert code == 200
                # round-trip parity: raw-bytes payload ≡ JSON floats
                np.testing.assert_array_equal(
                    np.asarray(o_json["outputs"]),
                    np.asarray(o_b64["outputs"]))
            # int8 payload on the quantized endpoint: client encodes on
            # the endpoint's published input grid
            scale = srv.endpoints["q"].input_scale
            assert scale == pytest.approx(rec.ranges["layer0"]["scale"])
            xq = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
            code, o_i8 = _predict(base, "q", {
                "x_b64": base64.b64encode(xq.tobytes()).decode(),
                "dtype": "int8", "shape": [4, 12]})
            assert code == 200
            # the first quantized layer re-snaps to the SAME grid, so an
            # int8 wire payload is answered exactly like its f32 original
            code, o_f32 = _predict(base, "q", {"inputs": x.tolist()})
            np.testing.assert_array_equal(np.asarray(o_i8["outputs"]),
                                          np.asarray(o_f32["outputs"]))
            # int8 against an UN-quantized endpoint is a structured 400
            code, body = _predict(base, "fp32", {
                "x_b64": base64.b64encode(xq.tobytes()).decode(),
                "dtype": "int8", "shape": [4, 12]})
            assert code == 400 and "not quantized" in body["error"]
            # malformed binary bodies: bad dtype, bad shape, length lie
            for bad in ({"x_b64": b64, "dtype": "float16",
                         "shape": [4, 12]},
                        {"x_b64": b64, "dtype": "float32", "shape": []},
                        {"x_b64": b64, "dtype": "float32",
                         "shape": [4, 999]},
                        {"x_b64": "!!!", "dtype": "float32",
                         "shape": [4, 12]}):
                code, body = _predict(base, "q", bad)
                assert code == 400, bad
        finally:
            srv.stop(drain=False)


def _predict(base, model, body, timeout=30):
    req = urllib.request.Request(
        f"{base}/v1/models/{model}:predict", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# --------------------------------------------------------------------- CLI
def test_quantize_cli_end_to_end(tmp_path):
    """tools/quantize.py: model zip in → quantized zip + report out; the
    emitted zip restores into a quantized net."""
    from deeplearning4j_tpu.utils.serialization import restore, write_model
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = str(tmp_path / "fp32.zip")
    out = str(tmp_path / "int8.zip")
    write_model(_dense_net(), src, save_updater=False)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "tools/quantize.py", "--ckpt", src, "--out", out,
         "--data", "random:12@3", "--batches", "2", "--batch-size", "8",
         "--observer", "percentile"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.splitlines()[-1])
    assert summary["quantized"] == 3
    assert summary["byte_reduction_x"] >= 3.0
    with open(out + ".report.json") as f:
        report = json.load(f)
    assert report["quantized_layers"] == ["layer0", "layer1", "layer2"]
    assert report["byte_reduction_x"] >= 3.0
    assert set(report["ranges"]) == {"layer0", "layer1", "layer2"}
    back = restore(out, load_updater=False)
    assert is_quantized(back)
    assert back._quant_calibration is not None

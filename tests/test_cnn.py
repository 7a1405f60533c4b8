"""CNN stack tests: shape inference, LeNet end-to-end on (synthetic) MNIST,
and gradient checks (mirroring the reference's CNNGradientCheckTest.java and
BNGradientCheckTest.java in deeplearning4j-core/src/test/.../gradientcheck/)."""

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, MultiLayerConfiguration, InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.convolutional import (
    ConvolutionLayer, SubsamplingLayer, SeparableConvolution2D, Upsampling2D,
    ZeroPaddingLayer, Convolution1DLayer, Subsampling1DLayer,
)
from deeplearning4j_tpu.nn.conf.normalization import BatchNormalization, LocalResponseNormalization
from deeplearning4j_tpu.nn.conf.pooling import GlobalPoolingLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd, NoOp
from deeplearning4j_tpu.datasets import MnistDataSetIterator
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.utils.gradient_check import check_gradients


def lenet_conf(seed=12345):
    """LeNet as in the reference zoo (deeplearning4j-zoo/.../model/LeNet.java),
    shrunk channels for test speed."""
    return (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(Adam(learning_rate=1e-3))
            .weight_init("xavier")
            .list()
            .layer(ConvolutionLayer(n_out=8, kernel_size=(5, 5), stride=(1, 1),
                                    convolution_mode="same", activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(ConvolutionLayer(n_out=16, kernel_size=(5, 5), stride=(1, 1),
                                    convolution_mode="same", activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())


def test_lenet_shape_inference():
    conf = lenet_conf()
    types = conf.layer_input_types()
    # flat 784 -> NHWC 28x28x1 before first conv
    assert types[0].kind == "cnn" and (types[0].height, types[0].width, types[0].channels) == (28, 28, 1)
    assert (types[1].height, types[1].width, types[1].channels) == (28, 28, 8)
    assert (types[2].height, types[2].width, types[2].channels) == (14, 14, 8)
    # dense layer sees the flattened post-preprocessor type
    assert (types[4].kind, types[4].flat_size()) == ("ff", 7 * 7 * 16)
    assert conf.wired_layers()[4].n_in == 7 * 7 * 16


def test_lenet_forward_shapes():
    net = MultiLayerNetwork(lenet_conf()).init()
    x = np.random.default_rng(0).random((4, 784), np.float32)
    out = net.output(x)
    assert out.shape == (4, 10)
    np.testing.assert_allclose(out.sum(-1), np.ones(4), rtol=1e-4)


def test_lenet_trains_on_mnist():
    """End-to-end LeNet training (BASELINE configs[0] shape; reference pattern:
    MNIST smoke tests in deeplearning4j-core)."""
    net = MultiLayerNetwork(lenet_conf()).init()
    it = MnistDataSetIterator(batch=64, num_examples=512)
    net.fit(it, num_epochs=6)
    test_it = MnistDataSetIterator(batch=256, num_examples=256, train=False)
    ds = next(iter(test_it))
    acc = (net.predict(ds.features) == np.argmax(ds.labels, -1)).mean()
    assert acc > 0.8, acc


def test_conv_json_round_trip():
    conf = lenet_conf()
    assert MultiLayerConfiguration.from_json(conf.to_json()) == conf


def _gradcheck_net(layers, input_type, seed=42):
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(NoOp()).weight_init("xavier").list())
    for l in layers:
        b = b.layer(l)
    conf = b.set_input_type(input_type).build()
    return MultiLayerNetwork(conf).init()


def test_gradcheck_conv_subsampling():
    net = _gradcheck_net(
        [ConvolutionLayer(n_out=3, kernel_size=(2, 2), activation="tanh"),
         SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2), pooling_type="max"),
         OutputLayer(n_out=4, activation="softmax", loss="mcxent")],
        InputType.convolutional(6, 6, 2))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 6, 6, 2)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 3)]
    assert check_gradients(net, DataSet(x, y))


def test_gradcheck_avg_pool_and_separable():
    net = _gradcheck_net(
        [SeparableConvolution2D(n_out=3, kernel_size=(2, 2), activation="tanh"),
         SubsamplingLayer(kernel_size=(2, 2), stride=(1, 1), pooling_type="avg"),
         OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
        InputType.convolutional(5, 5, 2))
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 5, 5, 2)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 2)]
    assert check_gradients(net, DataSet(x, y))


def test_gradcheck_batchnorm():
    """Reference: BNGradientCheckTest.java."""
    net = _gradcheck_net(
        [ConvolutionLayer(n_out=3, kernel_size=(2, 2), activation="identity"),
         BatchNormalization(),
         GlobalPoolingLayer(pooling_type="avg"),
         OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
        InputType.convolutional(5, 5, 1))
    rng = np.random.default_rng(2)
    x = rng.standard_normal((4, 5, 5, 1)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]
    assert check_gradients(net, DataSet(x, y))


def test_gradcheck_dense_losses():
    """Reference: LossFunctionGradientCheck.java — a spread of loss/activation pairs."""
    cases = [
        ("mse", "identity", 4),
        ("mse", "tanh", 4),
        ("xent", "sigmoid", 4),
        ("mcxent", "softmax", 4),
        ("l1", "tanh", 4),
        ("poisson", "softplus", 4),
        ("squared_hinge", "identity", 4),
    ]
    rng = np.random.default_rng(3)
    for loss, act, n_out in cases:
        net = _gradcheck_net(
            [DenseLayer(n_out=6, activation="tanh"),
             OutputLayer(n_out=n_out, activation=act, loss=loss)],
            InputType.feed_forward(5))
        x = rng.standard_normal((3, 5)).astype(np.float32)
        if loss in ("mcxent",):
            y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, 3)]
        elif loss in ("xent",):
            y = (rng.random((3, n_out)) > 0.5).astype(np.float32)
        elif loss == "poisson":
            y = rng.integers(0, 5, (3, n_out)).astype(np.float32)
        elif loss == "squared_hinge":
            y = np.where(rng.random((3, n_out)) > 0.5, 1.0, -1.0).astype(np.float32)
        else:
            y = rng.standard_normal((3, n_out)).astype(np.float32)
        assert check_gradients(net, DataSet(x, y)), (loss, act)


def test_gradcheck_l1_l2_regularization():
    """Reference: GradientCheckTests with l1/l2 set."""
    net = _gradcheck_net(
        [DenseLayer(n_out=5, activation="tanh", l1=0.01, l2=0.02),
         OutputLayer(n_out=3, activation="softmax", loss="mcxent", l2=0.05)],
        InputType.feed_forward(4))
    rng = np.random.default_rng(4)
    x = rng.standard_normal((3, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 3)]
    assert check_gradients(net, DataSet(x, y))


def test_upsampling_zeropadding_shapes():
    conf = (NeuralNetConfiguration.builder().list()
            .layer(ZeroPaddingLayer(padding=(1, 2)))
            .layer(Upsampling2D(size=(2, 2)))
            .layer(GlobalPoolingLayer(pooling_type="max"))
            .layer(OutputLayer(n_out=2, loss="mcxent"))
            .set_input_type(InputType.convolutional(4, 4, 3))
            .build())
    types = conf.layer_input_types()
    assert (types[1].height, types[1].width) == (6, 8)
    assert (types[2].height, types[2].width) == (12, 16)
    net = MultiLayerNetwork(conf).init()
    out = net.output(np.ones((2, 4, 4, 3), np.float32))
    assert out.shape == (2, 2)


def test_conv1d_shapes():
    conf = (NeuralNetConfiguration.builder().list()
            .layer(Convolution1DLayer(n_out=6, kernel_size=3, convolution_mode="same"))
            .layer(Subsampling1DLayer(kernel_size=2, stride=2))
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(OutputLayer(n_out=2, loss="mcxent"))
            .set_input_type(InputType.recurrent(4, 10))
            .build())
    net = MultiLayerNetwork(conf).init()
    out = net.output(np.random.default_rng(0).random((3, 10, 4), np.float32))
    assert out.shape == (3, 2)


def test_lrn_preserves_shape():
    conf = (NeuralNetConfiguration.builder().list()
            .layer(LocalResponseNormalization())
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(OutputLayer(n_out=2, loss="mcxent"))
            .set_input_type(InputType.convolutional(4, 4, 8))
            .build())
    net = MultiLayerNetwork(conf).init()
    out = net.output(np.random.default_rng(0).random((2, 4, 4, 8), np.float32))
    assert out.shape == (2, 2)


def test_batchnorm_gamma_beta_trained():
    """Regression: BN gamma/beta must receive optimizer updates even though
    they are not regularizable (found in review — updater selection must not
    key off regularizable())."""
    conf = (NeuralNetConfiguration.builder()
            .seed(1).updater(Sgd(learning_rate=0.5)).list()
            .layer(BatchNormalization())
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(OutputLayer(n_out=2, loss="mcxent"))
            .set_input_type(InputType.convolutional(4, 4, 2))
            .build())
    net = MultiLayerNetwork(conf).init()
    g0 = np.asarray(net.params[0]["gamma"]).copy()
    rng = np.random.default_rng(0)
    x = rng.random((8, 4, 4, 2), np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
    net.fit(DataSet(x, y), num_epochs=5)
    assert not np.allclose(np.asarray(net.params[0]["gamma"]), g0)


def test_subsampling1d_pnorm_and_unknown():
    """Regression: 1-D pooling must implement pnorm and reject typos."""
    import jax.numpy as jnp
    layer = Subsampling1DLayer(kernel_size=2, stride=2, pooling_type="pnorm", pnorm=2)
    x = jnp.asarray([[[3.0], [4.0]]])  # one window [3,4]
    out, _ = layer.apply({}, {}, x)
    np.testing.assert_allclose(np.asarray(out), [[[5.0]]], rtol=1e-6)
    with pytest.raises(ValueError):
        Subsampling1DLayer(pooling_type="median").apply({}, {}, x)


def test_dilated_conv_shape_inference_matches_runtime():
    """Regression: output_type must account for dilation."""
    conf = (NeuralNetConfiguration.builder().list()
            .layer(ConvolutionLayer(n_out=2, kernel_size=(3, 3), dilation=(2, 2)))
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(OutputLayer(n_out=2, loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())
    t = conf.layer_input_types()[1]
    assert (t.height, t.width) == (4, 4)
    net = MultiLayerNetwork(conf).init()
    assert net.output(np.ones((1, 8, 8, 1), np.float32)).shape == (1, 2)


def test_lrn_even_window():
    """Regression: even LRN window must preserve channel count."""
    layer = LocalResponseNormalization(n=4)
    x = np.random.default_rng(0).random((2, 4, 4, 8)).astype(np.float32)
    out, _ = layer.apply({}, {}, x)
    assert out.shape == x.shape


def test_lrn_matches_reference_window_semantics():
    """LRN sums 2*(n//2)+1 channels (reference halfN loop), so n=2 covers 3."""
    import jax.numpy as jnp
    x = np.zeros((1, 1, 1, 5), np.float32)
    x[0, 0, 0, 2] = 2.0  # single hot channel
    layer = LocalResponseNormalization(n=2, k=1.0, alpha=1.0, beta=1.0)
    out, _ = layer.apply({}, {}, jnp.asarray(x))
    out = np.asarray(out)
    # channels 1..3 see the squared 4.0 in their window: denom 1+4=5
    np.testing.assert_allclose(out[0, 0, 0], [0, 0, 2/5, 0, 0], rtol=1e-6)


def test_global_pooling_keep_dimensions():
    conf = (NeuralNetConfiguration.builder().list()
            .layer(GlobalPoolingLayer(pooling_type="avg", collapse_dimensions=False))
            .layer(OutputLayer(n_out=2, loss="mcxent"))
            .set_input_type(InputType.convolutional(4, 4, 3))
            .build())
    t = conf.layers[0].output_type(InputType.convolutional(4, 4, 3))
    assert (t.kind, t.height, t.width, t.channels) == ("cnn", 1, 1, 3)
    net = MultiLayerNetwork(conf).init()
    assert net.output(np.ones((2, 4, 4, 3), np.float32)).shape == (2, 2)


def _bn_conf(dtype="float32", seed=12345):
    b = (NeuralNetConfiguration.builder()
         .seed(seed).dtype(dtype)
         .updater(Adam(learning_rate=1e-3)).weight_init("xavier")
         .list()
         .layer(ConvolutionLayer(n_out=6, kernel_size=(3, 3),
                                 convolution_mode="same", activation="identity"))
         .layer(BatchNormalization())
         .layer(SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)))
         .layer(DenseLayer(n_out=16, activation="relu"))
         .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
         .set_input_type(InputType.convolutional_flat(28, 28, 1)))
    return b.build()


def test_bfloat16_inference_path():
    """bf16 compute end-to-end through conv+BN: eval-mode batchnorm must
    normalize in the compute dtype (f32 running stats upcasting activations
    used to break conv dtype matching at the next layer)."""
    ds = next(iter(MnistDataSetIterator(batch=16, num_examples=16)))
    net = MultiLayerNetwork(_bn_conf("bfloat16")).init()
    net.fit(ds)
    assert np.isfinite(net.score())
    out = net.output(ds.features)  # inference-mode BN
    assert out.shape == (16, 10) and np.isfinite(np.asarray(out)).all()
    # same-seed f32 net agrees to bf16 tolerance
    ref = MultiLayerNetwork(_bn_conf("float32")).init()
    ref.fit(ds)
    np.testing.assert_allclose(np.asarray(ref.output(ds.features)),
                               np.asarray(out), atol=0.05)


def test_space_to_depth_stem_matches_direct_conv():
    """The 7x7/s2 SAME stem rewrite (_space_to_depth_conv) must be exact
    math vs lax.conv_general_dilated — fwd AND gradients — across odd/even
    output parities and 1..4 input channels (ADVICE r4: the blocking/padding
    derivation had no equivalence test)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def direct(x, w):
        return lax.conv_general_dilated(
            x, w, window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    rng = np.random.default_rng(7)
    for h, w_, c in [(14, 14, 3), (16, 12, 1), (12, 18, 4), (10, 10, 2)]:
        x = jnp.asarray(rng.standard_normal((2, h, w_, c), np.float32))
        k = jnp.asarray(rng.standard_normal((7, 7, c, 5), np.float32) * 0.1)
        lay = ConvolutionLayer(n_out=5, kernel_size=(7, 7), stride=(2, 2),
                               convolution_mode="same")
        assert lay._space_to_depth_eligible(x)
        got = ConvolutionLayer._space_to_depth_conv(x, k)
        want = direct(x, k)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        # gradients wrt input and kernel through an arbitrary scalar loss
        co = jnp.asarray(rng.standard_normal(want.shape, np.float32))
        gx, gk = jax.jit(jax.grad(
            lambda a, b: jnp.sum(ConvolutionLayer._space_to_depth_conv(a, b) * co),
            argnums=(0, 1)))(x, k)
        rx, rk = jax.jit(jax.grad(
            lambda a, b: jnp.sum(direct(a, b) * co), argnums=(0, 1)))(x, k)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                                   rtol=2e-4, atol=2e-4)

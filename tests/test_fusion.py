"""Fusion / memory-traffic pass tests (perf/fusion.py).

Covers the ISSUE-4 acceptance bars:
- fused conv→BN→act blocks reproduce the unfused stack's loss and
  gradients within fp tolerance (MLN + ComputationGraph, train mode,
  residual and non-residual variants);
- fold_bn() inference output matches BN-inference output within fp
  tolerance for the zoo CNNs (BN-free graphs after folding);
- conf.memory_report()'s training-activation-bytes for ResNet50 drops
  >= 25% with fusion enabled vs disabled (jaxpr-derived, no device
  allocation);
- per-layer remat= knob lowers to jax.checkpoint (same math, smaller
  residual set), validated ahead of trace by analysis/validation.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.convolutional import (
    ConvolutionLayer, FusedConvBNActivation,
)
from deeplearning4j_tpu.nn.conf.graph import (
    ElementWiseVertex, GraphBuilder,
)
from deeplearning4j_tpu.nn.conf.layers import (
    ActivationLayer, DenseLayer, OutputLayer,
)
from deeplearning4j_tpu.nn.conf.normalization import BatchNormalization
from deeplearning4j_tpu.nn.conf.network import Builder as NNBuilder
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.perf.fusion import (
    fold_bn, fuse, fuse_network, training_activation_bytes,
)

RNG = np.random.default_rng(7)


def _mln_conf(**kw):
    return (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.05))
            .list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                    convolution_mode="same",
                                    activation="identity", has_bias=False))
            .layer(BatchNormalization())
            .layer(ActivationLayer(activation="relu"))
            .layer(ConvolutionLayer(n_out=3, kernel_size=(3, 3),
                                    convolution_mode="same",
                                    activation="identity"))
            .layer(BatchNormalization())
            .layer(OutputLayer(n_out=5, loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 3)).build())


def _loss_and_grads(net, x, y):
    if isinstance(net, ComputationGraph):
        def f(p):
            return net._loss_fn(p, net.state, [x], [y], None, None, None)[0]
    else:
        def f(p):
            return net._loss_fn(p, net.state, x, y, None, None, None)[0]
    return jax.jit(jax.value_and_grad(f))(net.params)


def _relerr(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-12)


# ------------------------------------------------------------ MLN rewrite
def test_mln_rewriter_matches_and_preserves_structure():
    conf = _mln_conf()
    fused = conf.fused()
    assert [type(l).__name__ for l in fused.layers] == [
        "FusedConvBNActivation", "FusedConvBNActivation", "OutputLayer"]
    # first triple carried the relu, second pair fused to identity
    assert fused.layers[0].activation == "relu"
    assert fused.layers[1].activation == "identity"
    assert fused.layers[1].has_bias  # conv bias carried over
    # serde round-trip keeps the fused layers
    from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
    rt = MultiLayerConfiguration.from_json(fused.to_json())
    assert isinstance(rt.layers[0], FusedConvBNActivation)
    assert rt.layers[0].kernel_size == (3, 3)


def test_mln_rewriter_skips_non_matches():
    # conv with a real activation between conv and BN: not foldable
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Sgd(0.1)).list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                    convolution_mode="same",
                                    activation="relu"))
            .layer(BatchNormalization())
            .layer(OutputLayer(n_out=2, loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 3)).build())
    assert conf.fused() == conf
    # preprocessor landing ON the BN blocks the match
    from deeplearning4j_tpu.nn.conf.preprocessors import (
        CnnToFeedForwardPreProcessor,
    )
    conf2 = dataclasses.replace(
        _mln_conf(), input_preprocessors={
            1: CnnToFeedForwardPreProcessor(8, 8, 4)})
    fused2 = fuse(conf2)
    assert not isinstance(fused2.layers[0], FusedConvBNActivation)
    # BN carrying its own gradient-normalization override: fusing would
    # silently drop the clipping on gamma/beta, so the chain is skipped
    base = _mln_conf()
    layers = list(base.layers)
    layers[1] = dataclasses.replace(
        layers[1], gradient_normalization="clip_l2_per_layer")
    conf3 = dataclasses.replace(base, layers=tuple(layers))
    assert not isinstance(fuse(conf3).layers[0], FusedConvBNActivation)


def test_mln_fusion_train_parity_loss_grads_state_and_output():
    conf = _mln_conf()
    net = MultiLayerNetwork(conf).init()
    fnet = fuse_network(net)
    x = jnp.asarray(RNG.standard_normal((4, 8, 8, 3), np.float32))
    y = jnp.asarray(np.eye(5, dtype=np.float32)[RNG.integers(0, 5, 4)])
    (l0, g0) = _loss_and_grads(net, x, y)
    (l1, g1) = _loss_and_grads(fnet, x, y)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g0[0]["W"]),
                               np.asarray(g1[0]["W"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g0[1]["gamma"]),
                               np.asarray(g1[0]["gamma"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g0[1]["beta"]),
                               np.asarray(g1[0]["beta"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g0[3]["W"]),
                               np.asarray(g1[1]["W"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g0[3]["b"]),
                               np.asarray(g1[1]["b"]), atol=1e-5)
    # running-stat EMA parity (train-mode state updates)
    _, ns0 = net._loss_fn(net.params, net.state, x, y, None, None, None)
    _, ns1 = fnet._loss_fn(fnet.params, fnet.state, x, y, None, None, None)
    np.testing.assert_allclose(np.asarray(ns0[1]["mean"]),
                               np.asarray(ns1[0]["mean"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ns0[1]["var"]),
                               np.asarray(ns1[0]["var"]), atol=1e-6)
    # eval-mode output parity
    np.testing.assert_allclose(net.output(np.asarray(x)),
                               fnet.output(np.asarray(x)), atol=1e-5)


def test_mln_fused_network_trains_and_counts_blocks():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    conf = _mln_conf()
    net = MultiLayerNetwork(fuse(conf)).init()
    x = RNG.standard_normal((8, 8, 8, 3)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[RNG.integers(0, 5, 8)]
    s0 = net.score_dataset(DataSet(x, y))
    net.fit(DataSet(x, y), num_epochs=8)
    assert net.score_dataset(DataSet(x, y)) < s0
    # fused-block trace hits are countable (CompileWatch counter)
    assert net.compile_watch.counter("fusion.fused_block") > 0


# --------------------------------------------------------- graph rewrite
def _toy_residual_graph():
    parent = NNBuilder()
    parent.seed(5).updater(Sgd(0.05)).weight_init("relu")
    g = GraphBuilder(parent)
    g.add_inputs("in")
    g.add_layer("c1", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                       convolution_mode="same",
                                       activation="identity",
                                       has_bias=False), "in")
    g.add_layer("b1", BatchNormalization(), "c1")
    g.add_layer("a1", ActivationLayer(activation="relu"), "b1")
    g.add_layer("c2", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                       convolution_mode="same",
                                       activation="identity",
                                       has_bias=False), "a1")
    g.add_layer("b2", BatchNormalization(), "c2")
    g.add_vertex("add", ElementWiseVertex(op="add"), "b2", "a1")
    g.add_layer("a2", ActivationLayer(activation="relu"), "add")
    g.add_layer("out", OutputLayer(n_out=3, loss="mcxent"), "a2")
    g.set_outputs("out")
    g.set_input_types(InputType.convolutional(8, 8, 3))
    return g.build()


def test_graph_fusion_residual_parity():
    conf = _toy_residual_graph()
    fused = conf.fused()
    kinds = [type(o).__name__ for o, _ in fused.vertices.values()]
    assert "BatchNormalization" not in kinds
    assert "ElementWiseVertex" not in kinds  # residual add absorbed
    assert kinds.count("FusedConvBNActivation") == 2
    # the residual block keeps the act vertex's name and gains 2 inputs
    obj, ins = fused.vertices["a2"]
    assert isinstance(obj, FusedConvBNActivation) and obj.residual
    assert ins == ("a1", "a1")

    net = ComputationGraph(conf).init()
    fnet = fuse_network(net)
    x = jnp.asarray(RNG.standard_normal((4, 8, 8, 3), np.float32))
    y = jnp.asarray(np.eye(3, dtype=np.float32)[RNG.integers(0, 3, 4)])
    (l0, g0) = _loss_and_grads(net, x, y)
    (l1, g1) = _loss_and_grads(fnet, x, y)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g0["c1"]["W"]),
                               np.asarray(g1["a1"]["W"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g0["b2"]["gamma"]),
                               np.asarray(g1["a2"]["gamma"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g0["c2"]["W"]),
                               np.asarray(g1["a2"]["W"]), atol=1e-5)
    np.testing.assert_allclose(net.output_single(np.asarray(x)),
                               fnet.output_single(np.asarray(x)), atol=1e-5)
    # fused graph trains
    from deeplearning4j_tpu.datasets.dataset import DataSet
    ds = DataSet(np.asarray(x), np.asarray(y))
    s0 = fnet.score_dataset(ds)
    fnet.fit(ds, num_epochs=8)
    assert fnet.score_dataset(ds) < s0


def test_resnet50_fusion_parity_and_memory_drop():
    """North-star acceptance: all 53 conv→BN chains of ResNet50 fuse
    (residual bottlenecks included), train-mode loss/gradients match, and
    the jaxpr-derived training-activation-bytes drop >= 25%."""
    from deeplearning4j_tpu.models import ResNet50
    conf = ResNet50(num_classes=4, input_shape=(32, 32, 3)).conf()
    fused = conf.fused()
    kinds = {}
    for _, (o, _ins) in fused.vertices.items():
        kinds[type(o).__name__] = kinds.get(type(o).__name__, 0) + 1
    assert kinds.get("FusedConvBNActivation") == 53
    assert "BatchNormalization" not in kinds

    net = ComputationGraph(conf).init(validate=False)
    fnet = fuse_network(net)
    x = jnp.asarray(RNG.standard_normal((2, 32, 32, 3), np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[[0, 1]])
    (l0, g0) = _loss_and_grads(net, x, y)
    (l1, g1) = _loss_and_grads(fnet, x, y)
    np.testing.assert_allclose(float(l0), float(l1), rtol=2e-5)
    # grads are huge on an untrained resnet (~1e9): compare by relative
    # L2 norm, which is what "fp tolerance" means at this magnitude
    assert _relerr(g0["stem_conv"]["W"], g1["stem_act"]["W"]) < 1e-3
    assert _relerr(g0["res2a_2c_bn"]["gamma"],
                   g1["res2a_out"]["gamma"]) < 1e-3
    np.testing.assert_allclose(net.output_single(np.asarray(x)),
                               fnet.output_single(np.asarray(x)), atol=2e-5)

    b_off = training_activation_bytes(conf, minibatch=2)
    b_on = training_activation_bytes(fused, minibatch=2)
    assert b_on <= 0.75 * b_off, (b_on, b_off)
    # and the memory_report surfaces the same numbers
    rep = fused.memory_report(minibatch=2)
    assert rep.training_activation_bytes == b_on
    assert rep.fused_blocks == 53
    assert "Training residuals" in rep.to_string()


# ---------------------------------------------------------------- fold_bn
def _randomize_bn_stats(net):
    """Random running stats make the fold parity check non-trivial."""
    if isinstance(net, ComputationGraph):
        items = net.state.items()
        for n, s in list(items):
            if "mean" in s:
                c = s["mean"].shape[0]
                net.state[n] = {
                    "mean": jnp.asarray(
                        RNG.standard_normal(c).astype(np.float32)),
                    "var": jnp.asarray(
                        RNG.random(c).astype(np.float32) + 0.5)}
    else:
        for i, s in enumerate(net.state):
            if "mean" in s:
                c = s["mean"].shape[0]
                net.state[i] = {
                    "mean": jnp.asarray(
                        RNG.standard_normal(c).astype(np.float32)),
                    "var": jnp.asarray(
                        RNG.random(c).astype(np.float32) + 0.5)}


def _assert_no_bn(conf):
    if hasattr(conf, "layers"):
        assert not any(isinstance(l, BatchNormalization)
                       for l in conf.layers)
    else:
        assert not any(isinstance(o, BatchNormalization)
                       for o, _ in conf.vertices.values())


# folds=True: every BN sits directly on an identity-activation conv, so
# folding removes it. SimpleCNN's BN normalizes the conv's RELU output —
# mathematically unfoldable; fold_bn must leave it intact AND preserve
# the output exactly.
@pytest.mark.parametrize("model_cls,shape,folds", [
    ("LeNet", None, False),
    ("SimpleCNN", (32, 32, 3), False),
    ("AlexNet", (96, 96, 3), False),
    ("VGG16", (64, 64, 3), False),
    ("VGG19", (64, 64, 3), False),
    ("ResNet50", (32, 32, 3), True),
    ("Darknet19", (64, 64, 3), True),
    ("GoogLeNet", (64, 64, 3), False),
    ("InceptionResNetV1", (96, 96, 3), True),
    ("FaceNetNN4Small2", (96, 96, 3), True),
])
def test_fold_bn_zoo_parity(model_cls, shape, folds):
    import deeplearning4j_tpu.models as models
    cls = getattr(models, model_cls)
    kw = {"num_classes": 4}
    if shape is not None:
        kw["input_shape"] = shape
    model = cls(**kw)
    net = model.init()
    _randomize_bn_stats(net)
    folded = fold_bn(net)
    if folds:
        _assert_no_bn(folded.conf)
        n_before = (len(net.conf.layers) if hasattr(net.conf, "layers")
                    else len(net.conf.vertices))
        n_after = (len(folded.conf.layers) if hasattr(folded.conf, "layers")
                   else len(folded.conf.vertices))
        assert n_after < n_before
    if model_cls == "LeNet":
        x = np.zeros((2, 784), np.float32)
    else:
        h, w, c = shape if shape is not None else model.input_shape
        x = RNG.standard_normal((2, h, w, c)).astype(np.float32)
    if isinstance(net, ComputationGraph):
        o0, o1 = net.output_single(x), folded.output_single(x)
    else:
        o0, o1 = net.output(x), folded.output(x)
    np.testing.assert_allclose(o0, o1, rtol=2e-4, atol=2e-5)


def test_zoo_init_fold_bn_flag():
    from deeplearning4j_tpu.models import Darknet19
    net = Darknet19(num_classes=3, input_shape=(32, 32, 3)).init(
        fold_bn=True)
    _assert_no_bn(net.conf)
    assert net.output(np.zeros((1, 32, 32, 3), np.float32)).shape == (1, 3)


def test_fold_bn_handles_fused_blocks_and_transfer_learning():
    # a FUSED network folds too (non-residual blocks become plain convs)
    conf = _mln_conf()
    net = MultiLayerNetwork(fuse(conf)).init()
    _randomize_bn_stats(net)  # fused blocks keep the mean/var state keys
    folded = fold_bn(net)
    assert all(not isinstance(l, FusedConvBNActivation)
               for l in folded.conf.layers)
    x = RNG.standard_normal((2, 8, 8, 3)).astype(np.float32)
    np.testing.assert_allclose(net.output(x), folded.output(x),
                               rtol=2e-4, atol=2e-5)
    # transfer-learning output nets are plain networks: folding applies
    from deeplearning4j_tpu.nn.transferlearning import TransferLearning
    base = MultiLayerNetwork(_mln_conf()).init()
    tl = (TransferLearning.Builder(base)
          .remove_output_layer()
          .add_layer(OutputLayer(n_out=2, loss="mcxent"))
          .build())
    folded_tl = fold_bn(tl)
    _assert_no_bn(folded_tl.conf)
    np.testing.assert_allclose(tl.output(x), folded_tl.output(x),
                               rtol=2e-4, atol=2e-5)


def test_parallel_inference_fold_bn_serves_bn_free():
    from deeplearning4j_tpu.parallel import ParallelInference
    net = MultiLayerNetwork(_mln_conf()).init()
    pi = ParallelInference(net, fold_bn=True)  # lint: disable=DLT005
    try:
        _assert_no_bn(pi.model.conf)
        assert pi.model is not net  # caller's model untouched
        x = RNG.standard_normal((3, 8, 8, 3)).astype(np.float32)
        np.testing.assert_allclose(pi.output(x), net.output(x),
                                   rtol=2e-4, atol=2e-5)
        assert "fusion" not in pi.stats()  # folded graph: zero fused hits
    finally:
        pi.shutdown()


# ------------------------------------------------------------------ remat
def test_remat_knob_same_math_smaller_residuals():
    def build(remat):
        return (NeuralNetConfiguration.builder().seed(9).updater(Sgd(0.1))
                .list()
                .layer(DenseLayer(n_out=32, activation="tanh", remat=remat))
                .layer(DenseLayer(n_out=32, activation="tanh", remat=remat))
                .layer(OutputLayer(n_out=4, loss="mcxent"))
                .set_input_type(InputType.feed_forward(16)).build())
    x = jnp.asarray(RNG.standard_normal((8, 16), np.float32))
    y = jnp.asarray(np.eye(4, dtype=np.float32)[RNG.integers(0, 4, 8)])
    net0 = MultiLayerNetwork(build(None)).init()
    net1 = MultiLayerNetwork(build("full")).init()
    (l0, g0) = _loss_and_grads(net0, x, y)
    (l1, g1) = _loss_and_grads(net1, x, y)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    b_none = training_activation_bytes(build(None), minibatch=8)
    b_full = training_activation_bytes(build("full"), minibatch=8)
    b_dots = training_activation_bytes(build("dots_saveable"), minibatch=8)
    assert b_full < b_none
    assert b_dots <= b_none
    # remat shows up in the memory report table
    rep = build("dots_saveable").memory_report(minibatch=8)
    assert rep.layers[0].remat == "dots_saveable"
    assert "remat=dots_saveable" in rep.to_string()


def test_remat_validated_ahead_of_trace():
    from deeplearning4j_tpu.analysis.validation import ConfigValidationError
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=8, activation="relu", remat="bogus"))
            .layer(OutputLayer(n_out=2, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    with pytest.raises(ConfigValidationError, match="unknown-remat"):
        conf.validate()
    issues = conf.validate(raise_on_error=False)
    assert any(i.rule == "unknown-remat" for i in issues)


def test_remat_on_graph_and_fused_layer():
    conf = _toy_residual_graph()
    fused = conf.fused()
    # set remat on one fused vertex; the graph still trains identically
    vertices = dict(fused.vertices)
    obj, ins = vertices["a1"]
    vertices["a1"] = (dataclasses.replace(obj, remat="full"), ins)
    rconf = dataclasses.replace(fused, vertices=vertices)
    net = ComputationGraph(fused).init()
    rnet = ComputationGraph(rconf).init()
    x = jnp.asarray(RNG.standard_normal((2, 8, 8, 3), np.float32))
    y = jnp.asarray(np.eye(3, dtype=np.float32)[[0, 1]])
    (l0, g0) = _loss_and_grads(net, x, y)
    (l1, g1) = _loss_and_grads(rnet, x, y)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g0["a1"]["W"]),
                               np.asarray(g1["a1"]["W"]), atol=1e-5)
    assert (training_activation_bytes(rconf, minibatch=2)
            < training_activation_bytes(fused, minibatch=2))

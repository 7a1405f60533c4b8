"""VAE / AutoEncoder / CenterLoss / YOLO layer-family tests.

Mirrors the reference's gradient-check suites
(VaeGradientCheckTests.java, YoloGradientCheckTests.java, and the
CenterLossOutputLayer coverage in gradientcheck/) plus small end-to-end
pretraining runs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import (
    InputType, MultiLayerConfiguration, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.conf.layers import (
    CenterLossOutputLayer, DenseLayer, OutputLayer,
)
from deeplearning4j_tpu.nn.conf.objdetect import (
    Yolo2OutputLayer, get_predicted_objects,
)
from deeplearning4j_tpu.nn.conf.pretrain import AutoEncoder
from deeplearning4j_tpu.nn.conf.variational import VariationalAutoencoder
from deeplearning4j_tpu.nn.conf.convolutional import ConvolutionLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.utils.gradient_check import check_gradients


def _net(layers, input_type, updater=None, seed=12345):
    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(updater or Sgd(0.1)).weight_init("xavier").list())
    for l in layers:
        b = b.layer(l)
    return MultiLayerNetwork(b.set_input_type(input_type).build()).init()


def _fd_check_layer_loss(layer, params, x, rng, eps=1e-6, tol=1e-3):
    """Finite-difference check of a layer's pretrain_loss in f64 (the
    GradientCheckUtil contract applied to the pretraining path)."""
    from jax.flatten_util import ravel_pytree
    with jax.enable_x64():
        p64 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a, np.float64)), params)
        x64 = jnp.asarray(np.asarray(x, np.float64))
        flat, unravel = ravel_pytree(p64)

        @jax.jit
        def loss(f):
            return layer.pretrain_loss(unravel(f), {}, x64, rng)

        analytic = np.asarray(jax.grad(loss)(flat))
        flat_np = np.asarray(flat)
        idx = np.random.default_rng(0).choice(
            len(flat_np), size=min(200, len(flat_np)), replace=False)
        for j in idx:
            fp = flat_np.copy(); fp[j] += eps
            fm = flat_np.copy(); fm[j] -= eps
            num = (float(loss(jnp.asarray(fp))) -
                   float(loss(jnp.asarray(fm)))) / (2 * eps)
            a = analytic[j]
            denom = max(abs(a), abs(num))
            if denom > 1e-8:
                assert abs(a - num) / denom < tol, (j, a, num)


# -------------------------------------------------------------------- VAE
@pytest.mark.parametrize("recon", ["bernoulli", "gaussian"])
def test_vae_pretrain_gradients(recon):
    vae = VariationalAutoencoder(
        n_in=6, n_out=3, encoder_layer_sizes=(8,), decoder_layer_sizes=(8,),
        reconstruction=recon, activation="tanh")
    rng = jax.random.key(0)
    params, _ = vae.init(rng, InputType.feed_forward(6))
    x = np.random.default_rng(1).random((5, 6)).astype(np.float32)
    _fd_check_layer_loss(vae, params, x, jax.random.key(42))


def test_vae_pretrain_fit_and_supervised():
    """Pretrain a VAE on synthetic data (ELBO improves), then use it as a
    feature layer in a supervised net (reference VAE-as-first-layer use)."""
    rng = np.random.default_rng(0)
    x = (rng.random((128, 12)) < 0.3).astype(np.float32)
    net = _net([VariationalAutoencoder(n_out=4, encoder_layer_sizes=(16,),
                                       decoder_layer_sizes=(16,)),
                OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
               InputType.feed_forward(12), updater=Adam(1e-2))
    vae = net.layers[0]
    loss0 = float(vae.pretrain_loss(net.params[0], {}, jnp.asarray(x),
                                    jax.random.key(1)))
    net.pretrain(DataSet(x, np.zeros((128, 2), np.float32)), num_epochs=60)
    loss1 = float(vae.pretrain_loss(net.params[0], {}, jnp.asarray(x),
                                    jax.random.key(1)))
    assert loss1 < loss0, (loss0, loss1)
    # supervised fine-tune on a separable task still works end to end
    y = np.eye(2, dtype=np.float32)[(x[:, 0] > 0.5).astype(int)]
    net.fit(DataSet(x, y), num_epochs=30)
    assert net.score() < 0.8
    out = net.output(x)
    assert out.shape == (128, 2)
    # reconstruction probability is finite and batch-shaped
    rp = vae.reconstruction_probability(net.params[0], jnp.asarray(x[:4]),
                                        jax.random.key(2))
    assert rp.shape == (4,) and bool(jnp.all(jnp.isfinite(rp)))


# ------------------------------------------------------------ AutoEncoder
@pytest.mark.parametrize("loss", ["mse", "xent"])
def test_autoencoder_pretrain_gradients(loss):
    ae = AutoEncoder(n_in=6, n_out=4, corruption_level=0.0, loss=loss,
                     activation="sigmoid")
    params, _ = ae.init(jax.random.key(0), InputType.feed_forward(6))
    x = np.random.default_rng(1).random((5, 6)).astype(np.float32)
    _fd_check_layer_loss(ae, params, x, None)


def test_autoencoder_denoising_pretrain():
    rng = np.random.default_rng(3)
    # data on a 3-dim manifold in 16-dim space
    basis = rng.standard_normal((3, 16)).astype(np.float32)
    x = jax.nn.sigmoid(rng.standard_normal((256, 3)).astype(np.float32) @ basis)
    x = np.asarray(x)
    net = _net([AutoEncoder(n_out=8, corruption_level=0.3, loss="mse"),
                OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
               InputType.feed_forward(16), updater=Adam(1e-2))
    ae = net.layers[0]
    l0 = float(ae.pretrain_loss(net.params[0], {}, jnp.asarray(x), None))
    net.pretrain_layer(0, DataSet(x, np.zeros((256, 2), np.float32)),
                       num_epochs=80)
    l1 = float(ae.pretrain_loss(net.params[0], {}, jnp.asarray(x), None))
    assert l1 < l0 * 0.7, (l0, l1)
    # encode/decode shapes
    h = ae.encode(net.params[0], jnp.asarray(x[:4]))
    z = ae.decode(net.params[0], h)
    assert h.shape == (4, 8) and z.shape == (4, 16)


# ------------------------------------------------------------- CenterLoss
def test_centerloss_gradients():
    net = _net([DenseLayer(n_out=5, activation="tanh"),
                CenterLossOutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent", lamda=0.1,
                                      gradient_check=True)],
               InputType.feed_forward(4))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
    assert check_gradients(net, DataSet(x, y))


def test_centerloss_training_pulls_features_to_centers():
    """Train: centers move off zero (EMA rule) and class features tighten
    around their centers (the center-loss objective)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((120, 6)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x[:, 0] > 0).astype(int)]
    net = _net([DenseLayer(n_out=4, activation="tanh"),
                CenterLossOutputLayer(n_out=2, activation="softmax",
                                      loss="mcxent", alpha=0.2, lamda=0.05)],
               InputType.feed_forward(6), updater=Sgd(0.5))
    ds = DataSet(x, y)
    net.fit(ds, num_epochs=60)
    centers = np.asarray(net.params[1]["cL"])
    assert np.abs(centers).max() > 1e-3          # EMA moved the centers
    # features of each class are closer to their own center
    feats = np.asarray(jax.nn.tanh(
        jnp.asarray(x) @ net.params[0]["W"] + net.params[0]["b"]))
    d_own = np.linalg.norm(feats - y @ centers, axis=1).mean()
    d_other = np.linalg.norm(feats - (1 - y) @ centers, axis=1).mean()
    assert d_own < d_other
    acc = (net.predict(x) == y.argmax(-1)).mean()
    assert acc > 0.9


def test_centerloss_serde_roundtrip():
    from deeplearning4j_tpu.nn.conf.layers import layer_from_dict
    layer = CenterLossOutputLayer(n_out=3, alpha=0.1, lamda=0.01)
    assert layer_from_dict(layer.to_dict()) == layer


# ------------------------------------------------------------------- YOLO
def _yolo_fixture(mb=2, H=4, W=4, B=2, C=3, seed=0):
    rng = np.random.default_rng(seed)
    preout = rng.standard_normal((mb, H, W, B * (5 + C))).astype(np.float32)
    labels = np.zeros((mb, H, W, 4 + C), np.float32)
    # one object per example, random cell, box ~1.5 grid units
    for e in range(mb):
        cy, cx = rng.integers(0, H), rng.integers(0, W)
        cls = rng.integers(0, C)
        w, h = rng.uniform(0.5, 2.0, 2)
        x1, y1 = cx + 0.5 - w / 2, cy + 0.5 - h / 2
        labels[e, cy, cx, 0:4] = [x1, y1, x1 + w, y1 + h]
        labels[e, cy, cx, 4 + cls] = 1.0
    return preout, labels


def test_yolo_loss_and_gradients():
    layer = Yolo2OutputLayer(boxes=((1.0, 1.0), (2.0, 1.5)))
    preout, labels = _yolo_fixture()
    loss = float(layer.compute_score(jnp.asarray(labels), jnp.asarray(preout)))
    assert np.isfinite(loss) and loss > 0
    # empty-label cells contribute only the no-object confidence term
    zero_labels = np.zeros_like(labels)
    loss0 = float(layer.compute_score(jnp.asarray(zero_labels),
                                      jnp.asarray(preout)))
    assert np.isfinite(loss0) and loss0 < loss
    # finite-difference check on the input gradient (f64). The confidence
    # target is stop_gradient(IoU) — a constant label, exactly like the
    # reference's labelConfidence — so xy/wh channels (which feed the IoU)
    # legitimately differ between autodiff and finite differences; they get a
    # loose tolerance, while conf/class channels must match tightly.
    with jax.enable_x64():
        p64 = jnp.asarray(np.asarray(preout, np.float64))
        l64 = jnp.asarray(np.asarray(labels, np.float64))
        score = jax.jit(lambda p: layer.compute_score(l64, p))
        g = np.asarray(jax.grad(score)(p64))
        flat = np.asarray(p64).ravel()
        rng = np.random.default_rng(1)
        per = 5 + 3
        for j in rng.choice(flat.size, 60, replace=False):
            eps = 1e-6
            fp = flat.copy(); fp[j] += eps
            fm = flat.copy(); fm[j] -= eps
            num = (float(score(jnp.asarray(fp.reshape(p64.shape))))
                   - float(score(jnp.asarray(fm.reshape(p64.shape))))) / (2 * eps)
            a = g.ravel()[j]
            denom = max(abs(a), abs(num))
            tol = 1e-3 if (j % per) >= 4 else 5e-2
            if denom > 1e-8:
                assert abs(a - num) / denom < tol, (j, a, num)


def test_yolo_activations_and_decoding():
    layer = Yolo2OutputLayer(boxes=((1.0, 1.0), (2.0, 1.5)))
    preout, _ = _yolo_fixture()
    acts = np.asarray(layer.output_activations(jnp.asarray(preout)))
    assert acts.shape == preout.shape
    a5 = acts.reshape(2, 4, 4, 2, 8)
    assert (a5[..., 0:2] >= 0).all() and (a5[..., 0:2] <= 1).all()   # xy
    assert (a5[..., 2:4] > 0).all()                                   # wh
    np.testing.assert_allclose(a5[..., 5:].sum(-1), 1.0, rtol=1e-5)   # softmax
    objs = get_predicted_objects(acts, n_boxes=2, threshold=0.0)
    assert len(objs) == 2 * 4 * 4 * 2
    assert all(0 <= o.predicted_class < 3 for o in objs)
    objs_none = get_predicted_objects(acts, n_boxes=2, threshold=1.1)
    assert objs_none == []


def test_tinyyolo_detection_trains():
    """The TinyYOLO detection config (unblocked by this module) runs a
    train step and the loss decreases."""
    from deeplearning4j_tpu.models.darknet import TinyYOLO
    boxes = [[1.0, 1.0], [1.5, 1.5]]
    model = TinyYOLO(num_classes=3, input_shape=(32, 32, 3),
                     updater=Adam(1e-4))
    conf = model.detection_conf(boxes)
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.random((2, 32, 32, 3), np.float32)
    # find the backbone's output grid from a probe
    probe = net.output(x)
    H, W = probe.shape[1], probe.shape[2]
    _, labels = _yolo_fixture(mb=2, H=H, W=W, B=2, C=3)
    ds = DataSet(x, labels)
    net.fit(ds)
    s0 = net.score()
    net.fit(ds, num_epochs=19)
    # box responsibility (argmax IoU) flips as boxes move, so descent is
    # non-monotone — require a solid overall reduction instead
    assert net.score() < 0.5 * s0, (s0, net.score())


# ------------------------------------------------------------------- RBM
def test_rbm_cd_gradient_is_free_energy_difference():
    """The autodiff gradient of pretrain_loss must equal the classic CD-k
    statistics: dL/dW = (vk^T p(h|vk) - v0^T p(h|v0)) / B with the SAME
    Gibbs sample vk (reference RBM.java contrastiveDivergence gradient
    assembly)."""
    from deeplearning4j_tpu.nn.conf.pretrain import RBM
    rbm = RBM(n_in=6, n_out=4, k=2)
    params, _ = rbm.init(jax.random.key(0), InputType.feed_forward(6))
    rng = np.random.default_rng(5)
    x = jnp.asarray((rng.random((16, 6)) > 0.5).astype(np.float32))
    key = jax.random.key(9)
    g = jax.grad(lambda p: rbm.pretrain_loss(p, {}, x, key))(params)
    vk = rbm.gibbs_chain(params, x, key)  # same key -> same chain
    ph0 = jax.nn.sigmoid(x @ params["W"] + params["b"])
    phk = jax.nn.sigmoid(vk @ params["W"] + params["b"])
    B = x.shape[0]
    expect_W = (jnp.asarray(vk).T @ phk - x.T @ ph0) / B
    expect_b = jnp.mean(phk - ph0, 0)
    expect_vb = jnp.mean(vk - x, 0)
    np.testing.assert_allclose(np.asarray(g["W"]), np.asarray(expect_W),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g["b"]), np.asarray(expect_b),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g["vb"]), np.asarray(expect_vb),
                               rtol=1e-4, atol=1e-5)


def test_rbm_pretrain_learns_data_distribution():
    """CD-1 pretraining on structured binary data must lower the data's
    free energy relative to noise and shrink one-step reconstruction
    error (the reference's RBM monitoring quantity)."""
    from deeplearning4j_tpu.nn.conf.pretrain import RBM
    rng = np.random.default_rng(11)
    # two prototype patterns + bit noise
    protos = np.array([[1, 1, 1, 1, 0, 0, 0, 0, 1, 1, 0, 0],
                       [0, 0, 0, 0, 1, 1, 1, 1, 0, 0, 1, 1]], np.float32)
    idx = rng.integers(0, 2, 512)
    x = protos[idx]
    flip = rng.random(x.shape) < 0.05
    x = np.where(flip, 1 - x, x).astype(np.float32)
    net = _net([RBM(n_out=8, k=1),
                OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
               InputType.feed_forward(12), updater=Adam(5e-2))
    rbm = net.layers[0]
    key = jax.random.key(3)
    re0 = float(rbm.reconstruction_error(net.params[0], jnp.asarray(x), key))
    noise = jnp.asarray((rng.random((512, 12)) > 0.5).astype(np.float32))
    net.pretrain_layer(0, DataSet(x, np.zeros((512, 2), np.float32)),
                       num_epochs=60)
    re1 = float(rbm.reconstruction_error(net.params[0], jnp.asarray(x), key))
    assert re1 < re0 * 0.6, (re0, re1)
    # data free energy must now sit clearly below random-noise free energy
    fe_data = float(jnp.mean(rbm.free_energy(net.params[0], jnp.asarray(x))))
    fe_noise = float(jnp.mean(rbm.free_energy(net.params[0], noise)))
    assert fe_data < fe_noise - 1.0, (fe_data, fe_noise)
    # supervised fine-tune end to end (forward = hidden activations)
    y = np.eye(2, dtype=np.float32)[idx]
    net.fit(DataSet(x, y), num_epochs=30)
    assert net.output(x[:4]).shape == (4, 2)
    assert net.score() < 0.5


def test_rbm_config_round_trip():
    from deeplearning4j_tpu.nn.conf.pretrain import RBM
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-2))
            .list()
            .layer(RBM(n_out=8, k=3, visible_unit="gaussian", sparsity=0.1))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(12)).build())
    js = conf.to_json()
    back = MultiLayerConfiguration.from_json(js)
    l0 = back.layers[0]
    assert type(l0).__name__ == "RBM"
    assert l0.k == 3 and l0.visible_unit == "gaussian" and l0.sparsity == 0.1

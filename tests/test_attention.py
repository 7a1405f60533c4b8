"""Self-attention / transformer layer tests (nn/conf/attention.py):
causality, padding-mask isolation, gradient check, JSON round-trip, and a
tiny causal LM that must learn a deterministic next-token rule end to end
(the long-context layer-API surface; kernels themselves are covered by the
ring/flash tests in tests/test_parallel.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import (
    InputType, MultiLayerConfiguration, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.conf.attention import (
    SelfAttentionLayer, TransformerEncoderBlock,
)
from deeplearning4j_tpu.nn.conf.recurrent import (
    EmbeddingSequenceLayer, RnnOutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Adam

B, T, D = 2, 12, 16


def _x(seed=0, b=B, t=T, d=D):
    return jnp.asarray(np.random.default_rng(seed)
                       .standard_normal((b, t, d)).astype(np.float32))


def _layer_params(layer, seed=0, d=D):
    return layer.init(jax.random.key(seed), InputType.recurrent(d, T))[0]


def test_self_attention_shapes_and_mixing():
    lay = SelfAttentionLayer(n_in=D, n_out=D, n_heads=4)
    p = _layer_params(lay)
    out, _ = lay.apply(p, {}, _x())
    assert out.shape == (B, T, D)
    # non-causal attention mixes information from later positions
    x2 = _x().at[:, -1, :].add(1.0)
    out2, _ = lay.apply(p, {}, x2)
    assert float(jnp.max(jnp.abs(out2[:, 0] - out[:, 0]))) > 1e-6


def test_causal_masking_blocks_future():
    lay = SelfAttentionLayer(n_in=D, n_out=D, n_heads=4, causal=True)
    p = _layer_params(lay)
    x = _x(1)
    out, _ = lay.apply(p, {}, x)
    # perturb the future: outputs at earlier positions must not move
    x2 = x.at[:, 7:, :].add(2.0)
    out2, _ = lay.apply(p, {}, x2)
    np.testing.assert_allclose(np.asarray(out[:, :7]),
                               np.asarray(out2[:, :7]), atol=1e-6)
    assert float(jnp.max(jnp.abs(out2[:, 7:] - out[:, 7:]))) > 1e-4


def test_padding_mask_isolates_and_zeroes():
    lay = SelfAttentionLayer(n_in=D, n_out=D, n_heads=2)
    p = _layer_params(lay)
    x = _x(2)
    mask = jnp.ones((B, T), jnp.float32).at[:, 8:].set(0.0)
    out, _ = lay.apply(p, {}, x, mask=mask)
    # masked positions emit zeros
    np.testing.assert_allclose(np.asarray(out[:, 8:]), 0.0, atol=1e-7)
    # changing PADDED content must not change unmasked outputs
    x2 = x.at[:, 8:, :].add(3.0)
    out2, _ = lay.apply(p, {}, x2, mask=mask)
    np.testing.assert_allclose(np.asarray(out[:, :8]),
                               np.asarray(out2[:, :8]), atol=1e-6)


def test_transformer_block_shapes_and_gradients():
    lay = TransformerEncoderBlock(n_in=D, n_out=D, n_heads=4, ff_size=32)
    p = _layer_params(lay)
    x = _x(3)
    out, _ = lay.apply(p, {}, x)
    assert out.shape == (B, T, D)

    @jax.jit
    def loss(pp):
        o, _ = lay.apply(pp, {}, x)
        return jnp.sum(o * o)

    g = jax.grad(loss)(p)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
    # central-difference spot check on one weight (f32: forward diff is
    # cancellation-noisy at this loss magnitude)
    eps = 1e-2
    W1 = p["ff1"]["W"]
    bump = jnp.zeros_like(W1).at[0, 0].set(eps)
    fd = (loss({**p, "ff1": {**p["ff1"], "W": W1 + bump}})
          - loss({**p, "ff1": {**p["ff1"], "W": W1 - bump}})) / (2 * eps)
    np.testing.assert_allclose(float(fd), float(g["ff1"]["W"][0, 0]),
                               rtol=2e-2)


def test_attention_config_json_round_trip():
    conf = (NeuralNetConfiguration.builder()
            .seed(5).updater(Adam(1e-3)).weight_init("xavier").list()
            .layer(SelfAttentionLayer(n_out=D, n_heads=4, causal=True))
            .layer(TransformerEncoderBlock(n_heads=4, ff_size=32,
                                           causal=True))
            .layer(RnnOutputLayer(n_out=5, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(D, T)).build())
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert type(back.layers[0]).__name__ == "SelfAttentionLayer"
    assert back.layers[0].causal and back.layers[0].n_heads == 4
    assert type(back.layers[1]).__name__ == "TransformerEncoderBlock"
    assert back.layers[1].ff_size == 32


def test_tiny_causal_transformer_lm_learns():
    """Next-token prediction on a deterministic cyclic vocabulary: after
    training, the causal transformer must beat 90% next-token accuracy
    (it only needs to attend to the previous token)."""
    vocab, t, width = 7, 16, 32
    rng = np.random.default_rng(4)
    starts = rng.integers(0, vocab, 64)
    ids = (starts[:, None] + np.arange(t + 1)[None, :]) % vocab
    x_ids = ids[:, :-1]
    y = np.eye(vocab, dtype=np.float32)[ids[:, 1:]]
    conf = (NeuralNetConfiguration.builder()
            .seed(9).updater(Adam(5e-3)).weight_init("xavier").list()
            .layer(EmbeddingSequenceLayer(n_in=vocab, n_out=width))
            .layer(TransformerEncoderBlock(n_heads=4, ff_size=64,
                                           causal=True))
            .layer(RnnOutputLayer(n_out=vocab, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(vocab, t)).build())
    net = MultiLayerNetwork(conf).init()
    ds = DataSet(x_ids.astype(np.int32), y)
    s0 = net.score_dataset(ds)
    net.fit(ds, num_epochs=150)
    assert net.score_dataset(ds) < s0 * 0.2
    pred = np.argmax(net.output(x_ids.astype(np.int32)), -1)
    acc = float(np.mean(pred[:, 1:] == ids[:, 2:]))  # skip cold position 0
    assert acc > 0.9, acc


def test_attention_bias_init_and_bias_regularization():
    """bias_init must reach the projection biases, and the nested q/b...
    layout must be visible to the framework's bias machinery (l2_bias)."""
    lay = SelfAttentionLayer(n_in=D, n_out=D, n_heads=4, bias_init=0.25)
    p = _layer_params(lay)
    np.testing.assert_allclose(np.asarray(p["q"]["b"]), 0.25)
    np.testing.assert_allclose(np.asarray(p["o"]["b"]), 0.25)
    from deeplearning4j_tpu.nn.conf.layers import _bias_keys
    assert set(_bias_keys(lay, p)) == {"q/b", "k/b", "v/b", "o/b"}
    blk = TransformerEncoderBlock(n_in=D, n_out=D, n_heads=4, ff_size=32,
                                  bias_init=0.5)
    pb = _layer_params(blk)
    np.testing.assert_allclose(np.asarray(pb["ff1"]["b"]), 0.5)
    assert "ff1/b" in _bias_keys(blk, pb) and "q/b" in _bias_keys(blk, pb)


def test_masked_steps_zero_after_activation():
    """Masked timesteps must emit exact zeros even with a non-zero-at-zero
    activation (sigmoid(0) = 0.5 would otherwise leak through)."""
    lay = SelfAttentionLayer(n_in=D, n_out=D, n_heads=2,
                             activation="sigmoid")
    p = _layer_params(lay)
    mask = jnp.ones((B, T), jnp.float32).at[:, 6:].set(0.0)
    out, _ = lay.apply(p, {}, _x(5), mask=mask)
    np.testing.assert_allclose(np.asarray(out[:, 6:]), 0.0, atol=1e-7)

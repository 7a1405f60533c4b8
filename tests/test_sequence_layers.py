"""The layers a hybrid linear-attention expert model needs (RMSNorm,
GatedFeedForward, KimiDeltaAttention, GatedDeltaNet,
MultiHeadLatentAttention, GatedAttention, RoutedExperts, TokenOutputLayer
and the ``sparse_mcxent`` loss), each against a plain form written out
here, on the CPU at small sizes in float32. The whole models against the
benchmark's references are in ``tests/benchmark/test_benchmark_kimi_linear.py``
and ``test_benchmark_qwen3_next.py``."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn import lossfunctions
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.attention import (GatedAttention,
                                                  MultiHeadLatentAttention,
                                                  blocked_causal_attention,
                                                  rotate_half_split)
from deeplearning4j_tpu.nn.conf.experts import (GatedFeedForward,
                                                RoutedExperts, grouped_matmul)
from deeplearning4j_tpu.nn.conf.layers import layer_from_dict, layer_to_dict
from deeplearning4j_tpu.nn.conf.linear_attention import (
    GatedDeltaNet, KimiDeltaAttention, causal_depthwise_conv, chunked_kda)
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Adam
from deeplearning4j_tpu.perf import pallas as pk

# float32 on the CPU: what differs between two orders of the same sums
TOL = 2e-5


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _keys(n, seed=0):
    return jax.random.split(jax.random.key(seed), n)


# --------------------------------------------------------------------- KDA
def _recurrence(q, k, v, g, b):
    """S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T, o_t = S_t^T q_t,
    one token at a time in numpy float64."""
    q, k, v, g, b = (np.asarray(a, np.float64) for a in (q, k, v, g, b))
    bsz, t, h, kd = q.shape
    out = np.zeros(v.shape)
    for n in range(bsz):
        for j in range(h):
            s = np.zeros((kd, v.shape[-1]))
            for i in range(t):
                s = s * np.exp(g[n, i, j])[:, None]
                kk = k[n, i, j]
                s = s + b[n, i, j] * np.outer(kk, v[n, i, j] - s.T @ kk)
                out[n, i, j] = s.T @ q[n, i, j]
    return out


@pytest.fixture(params=["xla", "pallas"])
def kda_impl(request):
    """The two executions of ``chunked_kda``: plain ``jax.numpy`` at the
    small heads the other tests use, and the Pallas kernels (interpreted
    on the CPU) at the smallest shape they take: heads of 128, one
    sequence. Yields the head size to draw inputs at, and holds the test
    to the execution it asked for by the ``kernel.*_kda_scan`` counters."""
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL

    def taken():
        counters = GLOBAL.as_dict().get("counters", {})
        return [counters.get(f"kernel.{impl}_kda_scan", 0)
                for impl in ("xla", "pallas")]

    before = taken()
    if request.param == "xla":
        with pk.override(enabled=False):
            yield 8
    else:
        with pk.override(enabled=True, interpret=True):
            yield 128
    rose = [b > a for a, b in zip(before, taken())]
    assert rose == [request.param == "xla", request.param == "pallas"]


def _kda_inputs(t, decay, seed=0, kd=8):
    ks = _keys(5, seed)
    shape = (2 if kd == 8 else 1, t, 2, kd)
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], shape)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], shape)
    g = -decay * jax.random.uniform(ks[3], shape)
    b = jax.random.uniform(ks[4], shape[:3])
    return q, k, v, g, b


@pytest.mark.parametrize("t,decay", [(64, 1.0), (70, 0.05), (130, 1.0),
                                     (33, 40.0), (128, 40.0)])
def test_chunked_kda_is_the_token_recurrence(t, decay, kda_impl):
    """Any length (not only multiples of the chunk) and any decay: at 40 a
    step a quotient of two exponentials would overflow, the channel-by-
    channel diagonal blocks do not."""
    args = _kda_inputs(t, decay, kd=kda_impl)
    got = chunked_kda(*args, chunk=64)
    want = _recurrence(*args)
    assert np.all(np.isfinite(got))
    assert np.max(np.abs(np.asarray(got) - want)) < TOL * max(
        1.0, np.max(np.abs(want)))


@pytest.mark.parametrize("alike", [0.5, 0.9, 1.0])
def test_chunked_kda_stays_the_recurrence_when_keys_point_the_same_way(
        alike, kda_impl):
    """Keys that are alike, little decay and b near 1 make (I + A)^-1 of a
    whole chunk grow like 2^64: an explicit inverse overflows float32 (it
    did, on the chip, in the second training step). Forward substitution
    over blocks of 8 rows is as stable as the recurrence itself."""
    ks = _keys(5, 4)
    kd = 16 if kda_impl == 8 else kda_impl
    shape = (1, 256 if kd == 16 else 192, 2, kd)
    k = alike * jax.random.normal(ks[0], (1, 1, 2, kd)) + (
        1 - alike) * jax.random.normal(ks[1], shape)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = 0.3 * jax.random.normal(ks[2], shape)
    v = jax.random.normal(ks[3], shape)
    g = -0.001 * jax.random.uniform(ks[4], shape)
    b = 0.95 + 0.05 * jax.random.uniform(ks[4], shape[:3])
    got = chunked_kda(q, k, v, g, b)
    want = _recurrence(q, k, v, g, b)
    assert np.max(np.abs(np.asarray(got) - want)) < 5e-5 * max(
        1.0, np.max(np.abs(want)))
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(chunked_kda(*a))),
                             argnums=range(5)))(q, k, v, g, b)
    assert all(np.all(np.isfinite(a)) for a in grads)


def test_chunked_kda_gradients_match_a_scan_over_tokens(kda_impl):
    args = _kda_inputs(70, 2.0, seed=3, kd=kda_impl)
    chunk = 32 if kda_impl == 8 else 64

    def scan_form(q, k, v, g, b):
        def step(s, inp):
            qt, kt, vt, gt, bt = inp
            s = s * jnp.exp(gt)[..., None]
            read = jnp.einsum("bhk,bhkv->bhv", kt, s)
            s = s + jnp.einsum("bhk,bhv->bhkv", kt,
                               bt[..., None] * (vt - read))
            return s, jnp.einsum("bhk,bhkv->bhv", qt, s)
        s0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]))
        _, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0)
                                            for a in (q, k, v, g, b)))
        return jnp.moveaxis(o, 0, 1)

    def scalar(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)))

    got = jax.jit(jax.grad(
        scalar(lambda *a: chunked_kda(*a, chunk=chunk, sub=8)),
        argnums=range(5)))(*args)
    want = jax.jit(jax.grad(scalar(scan_form), argnums=range(5)))(*args)
    for a, b in zip(got, want):
        assert np.all(np.isfinite(a))
        assert float(jnp.max(jnp.abs(a - b))) < TOL * max(
            1.0, float(jnp.max(jnp.abs(b))))


def test_causal_depthwise_conv_sees_no_future():
    x = jax.random.normal(_keys(1)[0], (1, 12, 3))
    w = jnp.arange(12.0).reshape(4, 3) / 10
    y = causal_depthwise_conv(x, w)
    for t in (0, 2, 7):
        want = sum(w[j] * (x[0, t - 3 + j] if t - 3 + j >= 0 else 0.0)
                   for j in range(4))
        assert np.allclose(y[0, t], want, atol=1e-6)
    bumped = causal_depthwise_conv(x.at[0, 8].add(1.0), w)
    assert np.allclose(bumped[0, :8], y[0, :8])


def test_kda_layer_masks_its_output_and_keeps_its_width():
    layer = KimiDeltaAttention(n_heads=2, head_dim=8, chunk=16)
    it = InputType.recurrent(12, 20)
    assert layer.output_type(it).size == 12
    params, state = layer.init(jax.random.key(0), it)
    x = jax.random.normal(jax.random.key(1), (2, 20, 12))
    mask = jnp.concatenate([jnp.ones((2, 15)), jnp.zeros((2, 5))], 1)
    apply = jax.jit(layer.apply)
    out, _ = apply(params, state, x, mask=mask)
    free, _ = apply(params, state, x)
    assert out.shape == (2, 20, 12)
    assert np.allclose(out[:, :15], free[:, :15], atol=1e-6)
    assert not np.any(np.asarray(out[:, 15:]))


# ----------------------------------------------------------- Gated DeltaNet
def _gdn_plain(layer, params, x, scan):
    """The layer's equations written out; ``scan(q, k, v, g, b)`` runs the
    recurrence with ``g`` ONE number a value head (batch, time, heads)."""
    bsz, t, _ = x.shape
    hk, hv, dh = layer.n_key_heads, layer.n_value_heads, layer.head_dim
    qkvz = x @ params["Wqkvz"]
    mixed = jax.nn.silu(causal_depthwise_conv(qkvz[..., :(2 * hk + hv) * dh],
                                              params["conv"]))
    z = qkvz[..., (2 * hk + hv) * dh:].reshape(bsz, t, hv, dh)
    q = mixed[..., :hk * dh].reshape(bsz, t, hk, dh)
    k = mixed[..., hk * dh:2 * hk * dh].reshape(bsz, t, hk, dh)
    v = mixed[..., 2 * hk * dh:].reshape(bsz, t, hv, dh)

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    # value head j reads q/k head j // (hv / hk)
    q = jnp.repeat(unit(q) / math.sqrt(dh), hv // hk, axis=2)
    k = jnp.repeat(unit(k), hv // hk, axis=2)
    ba = x @ params["Wba"]
    b = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(params["A_log"]) * jax.nn.softplus(
        ba[..., hv:] + params["dt_bias"])
    o = scan(q, k, v, g, b)
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + layer.eps)
    o = o * params["o_norm"] * jax.nn.silu(z)
    return o.reshape(bsz, t, hv * dh) @ params["Wo"]


def _scalar_decay_scan(q, k, v, g, b):
    """S_t = exp(g_t) S_{t-1} + b_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T,
    o_t = S_t^T q_t with ``lax.scan`` over time: differentiable."""
    def step(s, inp):
        qt, kt, vt, gt, bt = inp
        s = s * jnp.exp(gt)[..., None, None]
        s = s + jnp.einsum("bhk,bhv->bhkv", kt, bt[..., None] * (
            vt - jnp.einsum("bhk,bhkv->bhv", kt, s)))
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s)

    s0 = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:])
    _, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1)


def test_gated_delta_net_is_its_equations_token_by_token(kda_impl):
    """Output, every parameter's gradient and the input's against the
    equations with a scan over tokens and ONE decay a head; the same
    output from KDA's own recurrence (``_recurrence``, a decay a channel)
    given that decay in every channel; fewer key heads than value heads;
    a length that is no multiple of the chunk; a mask zeroes its steps."""
    dh = kda_impl
    layer = GatedDeltaNet(n_key_heads=1 if dh == 128 else 2,
                          n_value_heads=2 if dh == 128 else 4, head_dim=dh)
    bsz, t = (1, 70) if dh == 128 else (2, 70)
    params, state = layer.init(jax.random.key(0), InputType.recurrent(12, t))
    x = jax.random.normal(jax.random.key(1), (bsz, t, 12))

    def run(fn):
        def loss(params, x):
            o = fn(params, x)
            return jnp.sum(jnp.sin(o)), o
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
            params, x)

    got = run(lambda p, x: layer.apply(p, state, x)[0])
    want = run(lambda p, x: _gdn_plain(layer, p, x, _scalar_decay_scan))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) < 20 * TOL * max(
            1.0, float(jnp.max(jnp.abs(b))))
    per_channel = _gdn_plain(
        layer, params, x, lambda q, k, v, g, b: jnp.asarray(_recurrence(
            q, k, v, jnp.broadcast_to(g[..., None], q.shape), b),
            jnp.float32))
    assert float(jnp.max(jnp.abs(got[0][1] - per_channel))) < 20 * TOL
    assert layer.output_type(InputType.recurrent(12, t)).size == 12
    mask = jnp.ones((bsz, t)).at[0, t - 5:].set(0.0)
    masked, _ = jax.jit(layer.apply)(params, state, x, mask=mask)
    assert float(jnp.max(jnp.abs(masked[0, t - 5:]))) == 0.0
    with pytest.raises(ValueError, match="no multiple"):
        GatedDeltaNet(n_key_heads=3, n_value_heads=4).output_type(
            InputType.recurrent(12, t))


# --------------------------------------------------------------------- MLA
def _dense_causal(q, k, v):
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def _attention_counters():
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    counters = GLOBAL.as_dict().get("counters", {})
    return [counters.get(f"kernel.{impl}_blocked_attention", 0)
            for impl in ("xla", "pallas")]


class _AttentionImpl:
    """What a test of blocked attention draws at, and which execution it
    has to have taken by its end."""

    def __init__(self, name):
        self.name = name
        # q/k heads and v heads differ, as in latent attention; the
        # kernels take widths that are multiples of 64
        self.widths = (24, 16) if name == "xla" else (192, 128)
        self.rises = [name == "xla", name == "pallas"]

    def one_tile(self, only=True):
        """This test has a call of a single tile (``only``: has no
        other): ``jax.numpy`` serves it whatever the family says."""
        self.rises = [True, self.rises[1] and not only]


@pytest.fixture(params=["xla", "pallas"])
def attn_impl(request):
    """The two executions of ``blocked_causal_attention``: plain
    ``jax.numpy`` at small heads, and the Pallas kernels (interpreted on
    the CPU) at heads of 192 / 128. Holds the test to the execution it
    asked for by the ``kernel.*_blocked_attention`` counters."""
    impl = _AttentionImpl(request.param)
    before = _attention_counters()
    with pk.override(enabled=request.param == "pallas", interpret=True):
        yield impl
    rose = [b > a for a, b in zip(before, _attention_counters())]
    assert rose == impl.rises


def _attention_inputs(t, widths, batch=2, heads=3, dtype=jnp.float32):
    ks = _keys(3, 1)
    return (jax.random.normal(ks[0], (batch, heads, t, widths[0]), dtype),
            jax.random.normal(ks[1], (batch, heads, t, widths[0]), dtype),
            jax.random.normal(ks[2], (batch, heads, t, widths[1]), dtype))


def _out_and_grads(fn, q, k, v):
    def run(*a):
        o = fn(*a).astype(jnp.float32)
        return jnp.sum(jnp.sin(o)), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        run, (0, 1, 2), has_aux=True))(q, k, v)
    return (o,) + tuple(g.astype(jnp.float32) for g in grads)


@pytest.mark.parametrize("t,block", [(128, 32), (100, 32), (20, 32),
                                     (96, 96), (384, 128), (200, 128)])
def test_blocked_attention_is_the_full_score_matrix(t, block, attn_impl):
    """q/k heads and v heads that differ, a batch of two, lengths that are
    and are not multiples of the tile, one tile (always ``jax.numpy``) and
    up to three by three: forward and all three gradients."""
    if t <= block:
        attn_impl.one_tile()
    q, k, v = _attention_inputs(t, attn_impl.widths)
    got = _out_and_grads(lambda *a: blocked_causal_attention(*a, block),
                         q, k, v)
    want = _out_and_grads(_dense_causal, q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < TOL


@pytest.mark.parametrize("t,block", [(256, 128), (200, 64)])
def test_blocked_attention_in_bfloat16_stays_inside_the_plain_forms_gap(
        t, block):
    """bfloat16 at heads of 192 / 128: what the kernels round is what the
    ``jax.numpy`` form rounds, so their distance to the dense float32 form
    is held to the ``jax.numpy`` form's own (and a half), output and each
    gradient by its norm."""
    q, k, v = _attention_inputs(t, (192, 128), dtype=jnp.bfloat16)
    want = _out_and_grads(_dense_causal, *(a.astype(jnp.float32)
                                           for a in (q, k, v)))
    before = _attention_counters()

    def gaps():
        got = _out_and_grads(lambda *a: blocked_causal_attention(*a, block),
                             q, k, v)
        return [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                for a, b in zip(got, want)]

    with pk.override(enabled=False):
        plain = gaps()
    with pk.override(enabled=True, interpret=True):
        kernels = gaps()
    assert _attention_counters() == [before[0] + 1, before[1] + 1]
    assert all(0 < g < 0.02 for g in plain), plain
    for g, p in zip(kernels, plain):
        assert g < 1.5 * p, (kernels, plain)


def _mla_layer(widths, block):
    nope = 2 * widths[0] // 3
    return MultiHeadLatentAttention(
        n_heads=2, nope_dim=nope, rope_dim=widths[0] - nope,
        v_dim=widths[1], kv_rank=16, block=block)


def test_mla_layer_counts_the_path_it_took(attn_impl):
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    attn_impl.one_tile(only=False)
    block = 16 if attn_impl.name == "xla" else 128
    layer = _mla_layer(attn_impl.widths, block)
    t = 2 * block + block // 2
    it = InputType.recurrent(12, t)
    params, state = layer.init(jax.random.key(0), it)
    x = jax.random.normal(jax.random.key(1), (1, t, 12))
    before = dict(GLOBAL.as_dict().get("counters", {}))
    kernels = _attention_counters()
    apply = jax.jit(layer.apply)
    out, _ = apply(params, state, x)
    apply(params, state, x[:, :block])
    after = GLOBAL.as_dict()["counters"]
    assert out.shape == (1, t, 12)
    assert after["attention.mla_blocked"] == before.get(
        "attention.mla_blocked", 0) + 1
    assert after["attention.mla_single_tile"] == before.get(
        "attention.mla_single_tile", 0) + 1
    # the single tile is plain jax.numpy under either family setting
    served = attn_impl.name == "pallas"
    assert _attention_counters() == [kernels[0] + 2 - served,
                                     kernels[1] + served]


def test_mla_layer_is_the_dense_form_and_masks_its_output(attn_impl):
    """The layer against its own equations written out with a dense score
    matrix, output and the gradients of every parameter and of the input,
    at a length that is no multiple of the tile; a features mask zeroes
    the masked steps."""
    from deeplearning4j_tpu.nn.conf.normalization import rms_norm
    block = 16 if attn_impl.name == "xla" else 128
    layer = _mla_layer(attn_impl.widths, block)
    t, h = 2 * block + 3, layer.n_heads
    nope, rope, vd = layer.nope_dim, layer.rope_dim, layer.v_dim
    params, state = layer.init(jax.random.key(2), InputType.recurrent(12, t))
    x = jax.random.normal(jax.random.key(3), (2, t, 12))

    def dense(params, x):
        q = (x @ params["Wq"]).reshape(2, t, h, nope + rope)
        kva = x @ params["Wkva"]
        c = rms_norm(kva[..., :layer.kv_rank], params["kv_norm"], layer.eps)
        kvb = (c @ params["Wkvb"]).reshape(2, t, h, nope + vd)
        k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
            kva[:, :, None, layer.kv_rank:], (2, t, h, rope))], -1)
        o = _dense_causal(*(a.transpose(0, 2, 1, 3)
                            for a in (q, k, kvb[..., nope:])))
        return o.transpose(0, 2, 1, 3).reshape(2, t, h * vd) @ params["Wo"]

    def run(fn):
        def loss(params, x):
            o = fn(params, x)
            return jnp.sum(jnp.sin(o)), o
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
            params, x)

    got = run(lambda p, x: layer.apply(p, state, x)[0])
    want = run(dense)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) < 20 * TOL
    mask = jnp.ones((2, t)).at[1, t - 5:].set(0.0)
    masked, _ = jax.jit(layer.apply)(params, state, x, mask=mask)
    assert float(jnp.max(jnp.abs(masked[1, t - 5:]))) == 0.0
    assert float(jnp.max(jnp.abs(masked[0] - got[0][1][0]))) < TOL


# --------------------------------------------------------- gated attention
def test_rotation_leaves_the_other_widths_and_position_zero_alone():
    x = jax.random.normal(jax.random.key(0), (2, 9, 3, 256))
    got = rotate_half_split(x, jnp.arange(9), 64, 1e7)
    assert np.array_equal(np.asarray(got[..., 64:]), np.asarray(x[..., 64:]))
    assert np.array_equal(np.asarray(got[:, 0]), np.asarray(x[:, 0]))
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :64] - x[:, 1:, :, :64]))) > 0.1
    # width j turns with width j + 32 by t * theta^(-2j / 64)
    j, t = 5, 7
    angle = t * 1e7 ** (-2 * j / 64)
    a, b = x[1, t, 2, j], x[1, t, 2, j + 32]
    assert float(got[1, t, 2, j]) == pytest.approx(
        float(a * math.cos(angle) - b * math.sin(angle)), abs=1e-5)
    assert float(got[1, t, 2, j + 32]) == pytest.approx(
        float(b * math.cos(angle) + a * math.sin(angle)), abs=1e-5)
    # a turn: norms stay, and q . k depends on the distance alone
    assert float(jnp.max(jnp.abs(jnp.linalg.norm(got, axis=-1)
                                 - jnp.linalg.norm(x, axis=-1)))) < 1e-4
    q = jnp.broadcast_to(x[:1, :1], (1, 9, 3, 256))
    k = jnp.broadcast_to(x[1:, :1], (1, 9, 3, 256))
    rq, rk = (rotate_half_split(a, jnp.arange(9), 64, 1e7) for a in (q, k))
    near = jnp.sum(rq[0, 3] * rk[0, 1], -1)
    far = jnp.sum(rq[0, 8] * rk[0, 6], -1)
    assert float(jnp.max(jnp.abs(near - far))) < 1e-3
    # bfloat16 in, bfloat16 out, turned in float32
    assert rotate_half_split(x.astype(jnp.bfloat16), jnp.arange(9), 64,
                             1e7).dtype == jnp.bfloat16


def _gattn_layer(impl, block):
    wide = impl.name == "pallas"          # the kernels take heads of 64 up
    return GatedAttention(n_heads=4, n_kv_heads=2, head_dim=64 if wide else 16,
                          rotary_dim=16 if wide else 4, rope_theta=1e4,
                          block=block)


def test_gated_attention_is_full_heads_with_k_and_v_repeated(attn_impl):
    """The layer against its equations written out with a dense score
    matrix over FOUR full heads whose k and v are the two k/v heads
    repeated: output and the gradients of every parameter (dk and dv
    summed over the group) and of the input, at a length that is no
    multiple of the tile; a features mask zeroes the masked steps."""
    block = 16 if attn_impl.name == "xla" else 128
    layer = _gattn_layer(attn_impl, block)
    t, h, hkv, dh = 2 * block + 3, layer.n_heads, layer.n_kv_heads, \
        layer.head_dim
    params, state = layer.init(jax.random.key(2), InputType.recurrent(12, t))
    params = {k: (0.3 * jax.random.normal(jax.random.key(7), v.shape)
                  if k.endswith("_norm") else v) for k, v in params.items()}
    x = jax.random.normal(jax.random.key(3), (2, t, 12))

    def dense(params, x):
        qg = (x @ params["Wq"]).reshape(2, t, h, 2 * dh)
        q, gate = qg[..., :dh], qg[..., dh:]
        k = (x @ params["Wk"]).reshape(2, t, hkv, dh)
        v = (x @ params["Wv"]).reshape(2, t, hkv, dh)

        def normed(a, w):
            a = a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + layer.eps)
            return rotate_half_split(a * (1.0 + w), jnp.arange(t),
                                     layer.rotary_dim, layer.rope_theta)

        q, k = normed(q, params["q_norm"]), normed(k, params["k_norm"])
        k, v = (jnp.repeat(a, h // hkv, axis=2) for a in (k, v))
        o = _dense_causal(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)))
        o = o.transpose(0, 2, 1, 3) * jax.nn.sigmoid(gate)
        return o.reshape(2, t, h * dh) @ params["Wo"]

    def run(fn):
        def loss(params, x):
            o = fn(params, x)
            return jnp.sum(jnp.sin(o)), o
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
            params, x)

    got = run(lambda p, x: layer.apply(p, state, x)[0])
    want = run(dense)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) < 20 * TOL
    assert float(jnp.max(jnp.abs(got[1][0]["Wk"]))) > 0
    mask = jnp.ones((2, t)).at[1, t - 5:].set(0.0)
    masked, _ = jax.jit(layer.apply)(params, state, x, mask=mask)
    assert float(jnp.max(jnp.abs(masked[1, t - 5:]))) == 0.0
    assert float(jnp.max(jnp.abs(masked[0] - got[0][1][0]))) < TOL


def test_gated_attention_counts_the_path_it_took(attn_impl):
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    attn_impl.one_tile(only=False)
    block = 16 if attn_impl.name == "xla" else 128
    layer = _gattn_layer(attn_impl, block)
    t = 2 * block + block // 2
    params, state = layer.init(jax.random.key(0), InputType.recurrent(12, t))
    x = jax.random.normal(jax.random.key(1), (1, t, 12))
    before = dict(GLOBAL.as_dict().get("counters", {}))
    apply = jax.jit(layer.apply)
    out, _ = apply(params, state, x)
    apply(params, state, x[:, :block])
    after = GLOBAL.as_dict()["counters"]
    assert out.shape == (1, t, 12)
    for name in ("attention.gqa_blocked", "attention.gqa_single_tile"):
        assert after[name] == before.get(name, 0) + 1
    for bad in (dict(n_heads=3, n_kv_heads=2), dict(rotary_dim=5),
                dict(head_dim=8, rotary_dim=16)):
        with pytest.raises(ValueError):
            GatedAttention(**bad).output_type(InputType.recurrent(12, t))


# -------------------------------------------------------------------- loss
@pytest.mark.parametrize("t,block,masked,biased,scaled", [
    (64, 16, False, False, False), (50, 16, False, False, False),
    (50, 16, True, False, False), (10, 16, True, False, False),
    # a bias; a loss that is scaled and summed with a penalty (an upstream
    # cotangent of 0.37); a block longer than the sequence, unmasked
    (64, 16, False, True, False), (50, 16, True, True, True),
    (64, 16, False, False, True), (10, 1024, False, True, True)])
def test_blocked_loss_is_the_plain_cross_entropy(t, block, masked, biased,
                                                 scaled):
    ks = _keys(5, 2)
    x = jax.random.normal(ks[0], (3, t, 12))
    w = jax.random.normal(ks[1], (12, 30)) * 0.3
    b = 0.2 * jax.random.normal(ks[4], (30,)) if biased else None
    ids = jax.random.randint(ks[2], (3, t), 0, 30)
    mask = ((jax.random.uniform(ks[3], (3, t)) > 0.3).astype(jnp.float32)
            if masked else None)

    def around(loss, x, w):
        return (0.37 * loss + 0.1 * jnp.sum(x * x) + 0.2 * jnp.sum(w * w)
                if scaled else loss)

    def plain(x, w, b):
        onehot = jax.nn.one_hot(ids, 30)
        z = x @ w if b is None else x @ w + b
        return around(lossfunctions.score("mcxent", onehot, z, "softmax",
                                          mask), x, w)

    def blocked(x, w, b):
        return around(lossfunctions.blocked_sparse_mcxent(x, w, b, ids, mask,
                                                          block), x, w)

    wrt = (0, 1, 2) if biased else (0, 1)
    want, g_want = jax.value_and_grad(plain, wrt)(x, w, b)
    got, g_got = jax.value_and_grad(blocked, wrt)(x, w, b)
    assert abs(float(got - want)) < 1e-5
    # not differentiated (``score``): the forward-only loop, the same number
    assert abs(float(blocked(x, w, b) - want)) < 1e-5
    z = x @ w if b is None else x @ w + b
    assert abs(float(around(lossfunctions.score(
        "sparse_mcxent", ids, z, "softmax", mask), x, w) - want)) < 1e-5
    for a, e in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(a - e))) < 1e-5


def _walk(jp):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in jp.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _block_loops(jp, blocks, block, classes):
    """[products a body] of the loops over ``blocks`` blocks whose body
    forms or reads a (block, classes) array in a product."""
    found = []
    for eqn in _walk(jp):
        if eqn.primitive.name != "scan" or eqn.params["length"] != blocks:
            continue
        found.append(sum(
            1 for e in _walk(eqn.params["jaxpr"].jaxpr)
            if e.primitive.name == "dot_general"
            and any(tuple(v.aval.shape)[-2:] == (block, classes)
                    for v in list(e.invars) + list(e.outvars))))
    return [n for n in found if n]


def _blocked_loss_case(which):
    """(loss of (x, w), x, w, blocks, block, classes): 8 blocks of 32
    steps over 64 classes; the looped loss 2 passes of them."""
    w = 0.1 * jnp.ones((8, 64))
    ids = jnp.zeros((1, 256), jnp.int32)
    if which == "plain":
        return (lambda x, w: lossfunctions.blocked_sparse_mcxent(
            x, w, None, ids, None, 32)), jnp.ones((1, 256, 8)), w, 8
    wg, bg = 0.1 * jnp.ones((8, 1)), jnp.zeros((1,))
    return (lambda x, w: lossfunctions.blocked_exit_weighted_mcxent(
        x, w, None, wg, bg, ids, None, 32, 0.05)), jnp.ones((2, 1, 256, 8)), \
        w, 16


@pytest.mark.parametrize("which", ["plain", "exit_weighted"])
def test_blocked_loss_never_builds_the_sequence_logits(which):
    """No array of (time x classes) floats in the jaxpr of loss and
    gradient, nor one of all the passes' blocks: the largest is one
    block's."""
    loss, x, w, _ = _blocked_loss_case(which)
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, w)
    assert not any(v.aval.shape[-1:] == (64,)
                   and math.prod(v.aval.shape) > 32 * 64
                   for eqn in _walk(jaxpr.jaxpr) for v in eqn.outvars)


@pytest.mark.parametrize("which", ["plain", "exit_weighted"])
def test_blocked_loss_is_one_loop_of_three_products(which):
    """Loss and gradients come out of ONE loop over the blocks that forms
    a block's logits once: three products a body (logits, the states'
    gradient, the head's), where autodiff through a rematerialised body
    made two loops and four products. Not differentiated it is one loop of
    one product, and the counters say which was traced."""
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    loss, x, w, blocks = _blocked_loss_case(which)

    def counters():
        return {k: GLOBAL.counters("loss.").get(k, 0) for k in
                ("loss.blocked_one_pass", "loss.blocked_forward_only")}

    before = counters()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(x, w)
    assert _block_loops(jaxpr.jaxpr, blocks, 32, 64) == [3]
    after = counters()
    assert after == {"loss.blocked_one_pass":
                     before["loss.blocked_one_pass"] + 1,
                     "loss.blocked_forward_only":
                     before["loss.blocked_forward_only"]}
    plain = jax.make_jaxpr(loss)(x, w)
    assert _block_loops(plain.jaxpr, blocks, 32, 64) == [1]
    # no gradient: nothing of the head's or the states' size is made
    assert not any(tuple(v.aval.shape)[-2:] in ((8, 64), (32, 8))
                   for e in _walk(plain.jaxpr) if e.primitive.name == "scan"
                   for v in e.outvars)
    assert counters() == {"loss.blocked_one_pass":
                          after["loss.blocked_one_pass"],
                          "loss.blocked_forward_only":
                          after["loss.blocked_forward_only"] + 1}
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(loss, (x, w), (x, w))


def _token_graph():
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    g = GraphBuilder()
    g.add_inputs("ids")
    g.add_layer("embed", EmbeddingSequenceLayer(n_in=30, n_out=12), "ids")
    g.add_layer("norm", RMSNorm(), "embed")
    g.add_layer("head", TokenOutputLayer(n_out=30, time_block=16), "norm")
    g.set_outputs("head")
    g.set_input_types(InputType.recurrent(30, 40))
    return ComputationGraph(dataclasses.replace(
        g.build(), updater=Adam(1e-2))).init()


def test_a_token_graph_s_step_keeps_the_loss_scope_and_counts_its_loop():
    """The compiled train step carries ``loss.blocked`` forward and
    backward (what ``benchmark/scope_table.py`` files the loss under), the
    step program traced the one-pass loop and ``score`` the forward-only
    one."""
    net = _token_graph()
    ids = np.random.default_rng(0).integers(0, 30, (2, 41)).astype(np.int32)
    ds = DataSet(ids[:, :-1], ids[:, 1:])
    net.fit(ds)
    assert net.compile_watch.counters("loss.") == {"loss.blocked_one_pass": 1}
    first = net.score()
    assert abs(net.score_dataset(ds) - first) < 0.1 * first
    assert net.compile_watch.counters("loss.") == {
        "loss.blocked_one_pass": 1, "loss.blocked_forward_only": 1}

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    x = jax.ShapeDtypeStruct((2, 40), jnp.int32)
    text = net._get_jitted("train").lower(
        struct(net.params), struct(net.state), struct(net.opt_state),
        struct(net._rng), [x], [x], None, None).compile().as_text()
    under = [o for o in re.findall(r'op_name="([^"]*)"', text)
             if "loss.blocked" in o]
    assert any("transpose(" in o for o in under)
    assert any("transpose(" not in o for o in under)
    assert len(re.findall(r"\bwhile\(", text)) == 1


# ------------------------------------------------------------------ experts
def _experts(held, offset=0, shared=8, total=8, top_k=2, softmax=False):
    """Kimi's router (sigmoid scores x 2.446), or with ``softmax`` the
    Qwen3-Next family's: softmax scores, no scale, the shared expert
    behind a sigmoid gate."""
    if softmax:
        return RoutedExperts(n_experts=total, experts_held=held,
                             expert_offset=offset, top_k=top_k, expert_size=8,
                             shared_size=shared, router_activation="softmax",
                             shared_gate=bool(shared))
    return RoutedExperts(n_experts=total, experts_held=held,
                         expert_offset=offset, top_k=top_k, expert_size=8,
                         shared_size=shared, scaling=2.446)


def _plain_routed(layer, params, x, bias):
    """The masked loop over the held experts."""
    s = (jax.nn.softmax(x @ params["Wr"], -1)
         if layer.router_activation == "softmax"
         else jax.nn.sigmoid(x @ params["Wr"]))
    _, idx = jax.lax.top_k(s + bias, layer.top_k)
    chosen = jnp.take_along_axis(s, idx, -1)
    w = layer.scaling * chosen / jnp.sum(chosen, -1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(layer.experts_held):
        weight = jnp.sum(jnp.where(idx == layer.expert_offset + e, w, 0.0),
                         -1)
        hidden = jax.nn.silu(x @ params["Wgate"][e]) * (x @ params["Wup"][e])
        y = y + weight[..., None] * (hidden @ params["Wdown"][e])
    if layer.shared_size:
        shared = (jax.nn.silu(x @ params["Sgate"]) * (x @ params["Sup"])) \
            @ params["Sdown"]
        if layer.shared_gate:
            shared = shared * jax.nn.sigmoid(x @ params["Wsg"])
        y = y + shared
    return y


@pytest.mark.parametrize("softmax", [False, True], ids=["sigmoid", "softmax"])
@pytest.mark.parametrize("held,offset", [(8, 0), (4, 0), (2, 6)])
def test_routed_experts_are_the_masked_loop(held, offset, softmax):
    layer = _experts(held, offset, softmax=softmax)
    it = InputType.recurrent(12, 24)
    params, state = layer.init(jax.random.key(0), it)
    x = jax.random.normal(jax.random.key(1), (2, 24, 12))
    out, new = layer.apply(params, state, x)
    want = _plain_routed(layer, params, x, state["bias"])
    assert float(jnp.max(jnp.abs(out - want))) < TOL
    got = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(
        layer.apply(p, state, x)[0]))))(params)
    ref = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(_plain_routed(
        layer, p, x, state["bias"])))))(params)
    for key in ref:
        assert float(jnp.max(jnp.abs(got[key] - ref[key]))) < TOL, key
    assert int(new["pairs_dropped"]) == 0
    assert int(new["pairs_held"]) == int(jnp.sum(new["expert_tokens"]))


def test_grouped_matmul_leaves_rows_past_the_groups_zero():
    rows = jnp.ones((16, 4))
    weights = jnp.stack([jnp.eye(4) * (g + 1) for g in range(3)])
    out = grouped_matmul(rows, weights, jnp.array([3, 0, 5], jnp.int32))
    assert np.allclose(out[:3], 1.0) and np.allclose(out[3:8], 3.0)
    assert not np.any(np.asarray(out[8:]))


@pytest.mark.parametrize("where", ["all_on_one_held", "none_on_any_held",
                                   "all_on_all_held"])
def test_no_pair_is_dropped_at_any_imbalance(where):
    """A router pushed by its bias: every token on one held expert (48
    pairs on it, 24 times the even load), none on any, or all ``top_k``
    slots of every token on held experts (the worst case the buffer is
    sized for). The counters say what happened and the result is still the
    masked loop's."""
    layer = _experts(held=2, offset=0, shared=0)
    it = InputType.recurrent(12, 24)
    params, state = layer.init(jax.random.key(0), it)
    bias = {"all_on_one_held": jnp.zeros(8).at[1].set(10.0),
            "none_on_any_held": jnp.zeros(8).at[:2].set(-10.0),
            "all_on_all_held": jnp.zeros(8).at[:2].set(10.0)}[where]
    state = dict(state, bias=bias)
    x = jax.random.normal(jax.random.key(1), (2, 24, 12))
    out, new = layer.apply(params, state, x)
    tokens = np.asarray(new["expert_tokens"])
    if where == "all_on_one_held":
        assert tokens[1] == 48 and int(new["pairs_held"]) >= 48
    elif where == "none_on_any_held":
        assert tokens.tolist() == [0, 0] and not np.any(np.asarray(out))
    else:
        assert tokens.tolist() == [48, 48] and int(new["pairs_held"]) == 96
    assert int(new["pairs_dropped"]) == 0
    assert float(jnp.max(jnp.abs(out - _plain_routed(layer, params, x,
                                                     bias)))) < TOL
    again, newer = layer.apply(params, new, x)
    assert np.array_equal(np.asarray(newer["expert_tokens"]), 2 * tokens)


@pytest.mark.parametrize("push", [0.0, 10.0], ids=["first_tier",
                                                    "worst_case_tier"])
def test_both_buffer_tiers_give_the_masked_loop(push):
    """1,200 pairs: the first tier computes 384 sorted slots (four times
    the 75 pairs an even router sends to 2 of 32 experts, in row tiles of
    128), enough for what this router sends them; a router pushed
    onto the held experts (1,200 pairs on them) takes the worst-case tier.
    Same result, same gradients, nothing dropped."""
    layer = _experts(held=2, offset=4, shared=0, total=32)
    it = InputType.recurrent(12, 300)
    params, state = layer.init(jax.random.key(0), it)
    state = dict(state, bias=jnp.zeros(32).at[4:6].set(push))
    x = jax.random.normal(jax.random.key(1), (2, 300, 12))
    out, new = layer.apply(params, state, x)
    held = int(new["pairs_held"])
    assert (held <= 384) == (push == 0.0) and int(new["pairs_dropped"]) == 0
    assert held == (1200 if push else int(jnp.sum(new["expert_tokens"])))
    want = _plain_routed(layer, params, x, state["bias"])
    assert float(jnp.max(jnp.abs(out - want))) < TOL * max(
        1.0, float(jnp.max(jnp.abs(want))))
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(
        layer.apply(p, state, x)[0])), (0, 1)))(params, x)
    ref = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(_plain_routed(
        layer, p, x, state["bias"]))), (0, 1)))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        assert float(jnp.max(jnp.abs(a - b))) < TOL * max(
            1.0, float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("slots,held,experts,window", [
    (8192 * 8, 8, 256, 8192),         # a 32nd: four times the even share
    (8192 * 10, 32, 512, 20480),      # a 16th
    (16384 * 8, 16, 64, 65536),       # a quarter: twice the share, half the slots
    (1200, 2, 8, 600), (1200, 3, 8, 900),
    (1200, 4, 8, 1200), (1200, 8, 8, 1200),   # from a half on: every slot
], ids=["32nd", "16th", "quarter_16k", "quarter", "three_eighths", "half",
        "all"])
def test_the_window_is_four_shares_and_two_from_a_quarter_on(slots, held,
                                                            experts, window):
    from deeplearning4j_tpu.nn.conf.experts import _window_slots
    assert _window_slots(slots, held, experts) == window


@pytest.mark.parametrize("m,groups,k,n,tiles", [
    (8192, 8, 2304, 1024, (128, 1152, 512)),      # Kimi: gate / up
    (8192, 8, 1024, 2304, (128, 1024, 384)),      # Kimi: down
    (20480, 32, 2048, 512, (128, 1024, 512)),     # Qwen: gate / up
    (20480, 32, 512, 2048, (128, 512, 512)),      # Qwen: down
    (65536, 16, 2304, 896, (512, 1152, 896)),     # 4,096 rows a group: dense
    (65536, 16, 896, 2304, (512, 896, 768)),
    (65536, 8, 2304, 1024, (512, 1152, 512)),
    (65536 + 128, 16, 2304, 896, (128, 1152, 128)),   # no multiple of 512
    (32768, 8, 2048, 1792, (512, 1024, 896)),     # LFM2: 1792 = 2 x 896
    (32768, 8, 1792, 2048, (512, 896, 512)),
    (8192, 8, 2048, 768, (128, 1024, 384)),       # JoyAI: gate / up
    (8192, 8, 768, 2048, (128, 768, 512)),        # JoyAI: down
], ids=["kimi_up", "kimi_down", "qwen_up", "qwen_down", "dense_up",
        "dense_down", "dense_1024", "ragged_rows", "lfm2_up", "lfm2_down",
        "joyai_up", "joyai_down"])
def test_the_grouped_products_tiles_follow_the_rows_a_group(
        monkeypatch, m, groups, k, n, tiles):
    """Which tiles ``grouped_matmul`` hands the megablox kernel: the
    siblings' windows (1,024 and 640 rows a group) keep theirs, a window of
    2,048 rows a group or more takes 512 rows and a width whole where it
    fits. Read from the call itself, the kernel replaced."""
    from jax.experimental.pallas.ops.tpu import megablox
    seen = {}

    def gmm(rows, weights, sizes, dtype, tiling):
        seen["tiles"] = tiling(rows.shape[0], rows.shape[1],
                               weights.shape[2])
        return jnp.zeros((rows.shape[0], weights.shape[2]), dtype)

    monkeypatch.setattr(megablox, "gmm", gmm)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.eval_shape(grouped_matmul, jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
                   jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16),
                   jax.ShapeDtypeStruct((groups,), jnp.int32))
    assert seen["tiles"] == tiles


@pytest.mark.parametrize("most", [512, 896, 1152])
def test_a_tile_is_the_largest_128_multiple_that_divides_the_width(most):
    """``_fit`` over every width 128..4608 in steps of 128: a multiple of
    128 that divides the width and is at most the cap, and no larger such
    number exists (1792 under 1152 is 896, not the 256 a list of literal
    tiles gave)."""
    from deeplearning4j_tpu.nn.conf.experts import _fit
    for x in range(128, 4608 + 1, 128):
        t = _fit(x, most)
        assert t % 128 == 0 and x % t == 0 and t <= most
        assert not [u for u in range(t + 128, most + 1, 128) if x % u == 0]
    assert _fit(1792, 1152) == 896 and _fit(100, 1152) == 128


@pytest.mark.parametrize("push", [0.0, 10.0], ids=["first_tier",
                                                    "worst_case_tier"])
def test_a_quarter_share_runs_half_the_slots_or_all_of_them(push):
    """2 of 8 experts held, 1,200 pairs: four times the even share is every
    slot, so the window is HALF of them (640 in row tiles of 128) and the
    usual load (300 pairs) fits it; a router pushed onto the held experts
    (1,200 pairs) takes both windows. The masked loop's result and
    gradients either way, the tier counted, nothing dropped."""
    layer = _experts(held=2, offset=2, shared=0, softmax=True)
    it = InputType.recurrent(12, 300)
    params, state = layer.init(jax.random.key(0), it)
    state = dict(state, bias=jnp.zeros(8).at[2:4].set(push))
    x = jax.random.normal(jax.random.key(1), (2, 300, 12))
    out, new = layer.apply(params, state, x)
    held = int(new["pairs_held"])
    assert (held <= 640) == (push == 0.0) and int(new["pairs_dropped"]) == 0
    assert int(new["steps_every_window"]) == (1 if push else 0)
    want = _plain_routed(layer, params, x, state["bias"])
    assert float(jnp.max(jnp.abs(out - want))) < TOL * max(
        1.0, float(jnp.max(jnp.abs(want))))
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(
        layer.apply(p, state, x)[0])), (0, 1)))(params, x)
    ref = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(_plain_routed(
        layer, p, x, state["bias"]))), (0, 1)))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        assert float(jnp.max(jnp.abs(a - b))) < TOL * max(
            1.0, float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize(
    "tokens,total,held,offset,top_k,push,ratio,softmax", [
        (24, 8, 8, 0, 3, 0.0, 1, False),      # 144 slots in one window of 256
        (64, 8, 8, 0, 1, 0.0, 1, True),
        (256, 8, 2, 2, 1, 0.0, 2, True),      # a quarter held: half the slots
        (256, 8, 2, 2, 1, 10.0, 2, True),
        (128, 8, 2, 6, 3, 10.0, 2, False),    # 768 slots, windows of 384
        (256, 32, 2, 4, 3, 0.0, 4, False),    # 1,536 slots, windows of 384
        (250, 32, 2, 4, 3, 10.0, 4, True),    # 1,500 slots: 4 x 384, padded
        (512, 32, 1, 31, 1, 0.0, 8, False),   # 1,024 slots, windows of 128
        (512, 32, 1, 5, 1, 10.0, 8, True),
    ], ids=["all_held_k3_padded", "all_held_k1", "ratio2_k1",
            "ratio2_k1_every_window", "ratio2_k3_every_window", "ratio4_k3",
            "ratio4_k3_padded_every_window", "ratio8_k1",
            "ratio8_k1_every_window"])
def test_the_gather_form_of_the_dispatch_is_the_scatter_form(
        monkeypatch, tokens, total, held, offset, top_k, push, ratio,
        softmax):
    """The same layer, seed and operands under both forms of the dispatch
    (the rule's constant moved out of the way): output, the gradients of
    the input, the router and the three expert leaves, and the counters.
    The forms differ only in the order in which a token's ``top_k`` float32
    terms are added."""
    from deeplearning4j_tpu.nn.conf import experts as module
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    layer = _experts(held, offset, shared=0, total=total, top_k=top_k,
                     softmax=softmax)
    it = InputType.recurrent(12, tokens)
    params, state = layer.init(jax.random.key(0), it)
    state = dict(state,
                 bias=jnp.zeros(total).at[offset:offset + held].set(push))
    x = jax.random.normal(jax.random.key(1), (2, tokens, 12))
    slots = 2 * tokens * top_k
    window = -(-module._window_slots(slots, held, total) // 128) * 128
    assert round(slots / window) == ratio
    assert (-(-slots // window) * window > slots) == (slots % window > 0)

    def both(p, x):
        out, new = layer.apply(p, state, x)
        return jnp.sum(jnp.sin(out)), (out, new)

    got = {}
    for form, constant in (("gather", float("inf")), ("scatter", 0.0)):
        monkeypatch.setattr(module, "DISPATCH_GATHER_RATIO", constant)
        before = GLOBAL.counter(f"moe.dispatch_{form}")
        got[form] = jax.jit(jax.value_and_grad(
            both, (0, 1), has_aux=True))(params, x)
        assert GLOBAL.counter(f"moe.dispatch_{form}") > before
    (_, (out_g, new_g)), grads_g = got["gather"]
    (_, (out_s, new_s)), grads_s = got["scatter"]
    assert int(new_g["pairs_dropped"]) == int(new_s["pairs_dropped"]) == 0
    assert np.array_equal(np.asarray(new_g["expert_tokens"]),
                          np.asarray(new_s["expert_tokens"]))
    many = int(new_g["pairs_held"]) > window
    assert many == bool(push) or window >= slots
    assert int(new_g["steps_every_window"]) == int(
        new_s["steps_every_window"]) == int(many and window < slots)
    want = _plain_routed(layer, params, x, state["bias"])
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    assert float(jnp.max(jnp.abs(out_g - want))) < TOL * scale
    assert float(jnp.max(jnp.abs(out_g - out_s))) < TOL * scale
    for name in ("Wr", "Wgate", "Wup", "Wdown"):
        a, b = grads_g[0][name], grads_s[0][name]
        assert float(jnp.max(jnp.abs(a - b))) < TOL * max(
            1.0, float(jnp.max(jnp.abs(b)))), name
    assert float(jnp.max(jnp.abs(grads_g[1] - grads_s[1]))) < TOL * max(
        1.0, float(jnp.max(jnp.abs(grads_s[1]))))


@pytest.mark.parametrize("rows,d,itemsize,blocks", [
    (65536, 2304, 2, 3),      # a window's rows, bfloat16: 3 x 768 columns
    (16384, 2304, 4, 2),      # the tokens' float32 cotangent: 2 x 1152
    (16384, 2304, 2, 1),      # the tokens' rows: whole
    (24576, 2304, 2, 1),      # the largest operand read fast on the chip
    (28672, 2304, 2, 2),      # the smallest read slow
    (65536, 2048, 2, 4), (10 ** 6, 2304, 2, 18), (10 ** 6, 100, 4, 1),
], ids=["window_bf16", "tokens_f32", "tokens_bf16", "fast", "slow",
        "width_2048", "one_tile_at_least", "no_lane_tiles"])
def test_a_large_gather_operand_goes_in_equal_column_blocks(rows, d,
                                                            itemsize, blocks):
    from deeplearning4j_tpu.nn.conf import experts as module
    got = module._column_blocks(rows, d, itemsize)
    assert len(got) == blocks and got[0][0] == 0 and got[-1][1] == d
    assert all(b[0] == a[1] for a, b in zip(got, got[1:]))
    assert len({hi - lo for lo, hi in got}) == 1
    if blocks > 1:
        assert (got[0][1] % 128 == 0 and rows * got[0][1] * itemsize
                <= module.GATHER_OPERAND_BYTES) or got[0][1] == 128


def test_the_column_blocks_gather_what_the_whole_width_does(monkeypatch):
    """``_sum_pairs`` and the combine's backward rule in three blocks of 128
    columns against the whole width: the same rows to the bit."""
    from deeplearning4j_tpu.nn.conf import experts as module
    rows = jax.random.normal(jax.random.key(0), (40, 384))
    rank = jax.random.permutation(jax.random.key(1), 48).reshape(16, 3)
    weight = jax.random.normal(jax.random.key(2), (16, 3))
    dy = jax.random.normal(jax.random.key(3), (16, 384))
    slots = module._inverse(rank.reshape(-1))[:40]
    kept = (rows, weight, slots, rank, jnp.int32(0), jnp.int32(30))

    def both():
        inside, at = module._in_window(rank, 0, 40, 30)
        return (module._sum_pairs(rows, at, weight),
                *module._combine_bwd(kept, dy)[:2])

    whole = both()
    monkeypatch.setattr(module, "GATHER_OPERAND_BYTES", 16 * 128 * 4)
    assert len(module._column_blocks(40, 384, 4)) == 3
    assert len(module._column_blocks(16, 384, 4)) == 3
    blocked = both()
    for a, b in zip(whole[:2], blocked):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # a weight's cotangent is a row dot added block by block
    assert float(jnp.max(jnp.abs(whole[2] - blocked[2]))) < TOL * float(
        jnp.max(jnp.abs(whole[2])))


def test_the_rank_is_the_inverse_of_the_sort():
    from deeplearning4j_tpu.nn.conf.experts import _in_window, _inverse
    key = jax.random.randint(jax.random.key(3), (300,), 0, 5)
    order = jnp.argsort(key, stable=True)
    rank = _inverse(order)
    assert rank.dtype == jnp.int32
    assert np.array_equal(np.asarray(order[rank]), np.arange(300))
    assert np.array_equal(np.asarray(rank[order]), np.arange(300))
    # the pairs a window of 128 from slot 128 holds, 200 of the slots held
    inside, at = _in_window(rank, 128, 128, 200)
    assert np.array_equal(np.sort(np.asarray(rank[inside])),
                          np.arange(128, 200))
    assert np.array_equal(np.asarray(at[inside]),
                          np.asarray(rank[inside]) - 128)
    assert int(jnp.min(at)) >= 0 and int(jnp.max(at)) <= 127


@pytest.mark.parametrize("tokens,top_k,total,held,form", [
    (16384, 8, 64, 16, "gather"),     # mellum2_train_16k_ep4share: ratio 2
    (8192, 10, 512, 32, "scatter"),   # qwen3_next_train_8k_ep16share: 4
    (8192, 8, 256, 8, "scatter"),     # kimi_linear_train_8k_ep32share: 8
    (8192, 8, 16, 16, "gather"),      # every expert held: one chip's user
    (8192, 8, 16, 8, "gather"),       # a half
    (1200, 1, 8, 3, "gather"),        # three eighths: 1,200 over 1,024
    (600, 2, 32, 2, "scatter"),       # 1,200 over 384
], ids=["mellum_cell", "qwen_cell", "kimi_cell", "all_held", "half",
        "three_eighths", "sixteenth"])
def test_the_form_of_the_dispatch_follows_the_shapes_alone(tokens, top_k,
                                                           total, held, form):
    """Slots over window against ONE constant, read at trace time from the
    two counters: nothing but the layer's shapes decides, and the Kimi and
    Qwen cells' shapes keep the scatter form."""
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL

    def counts():
        return {f: GLOBAL.counter(f"moe.dispatch_{f}")
                for f in ("gather", "scatter")}

    layer = RoutedExperts(n_experts=total, experts_held=held, top_k=top_k,
                          expert_size=8)
    it = InputType.recurrent(16, tokens)
    params, state = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), it))
    before = counts()
    jax.eval_shape(layer.apply, params, state,
                   jax.ShapeDtypeStruct((1, tokens, 16), jnp.float32))
    other = "scatter" if form == "gather" else "gather"
    after = counts()
    assert after[form] == before[form] + 1
    assert after[other] == before[other]


@pytest.mark.parametrize("push,took", [(0.0, 0), (10.0, 1)],
                         ids=["under_the_window", "over_the_window"])
def test_the_layer_counts_the_steps_that_took_every_window(push, took):
    """``steps_every_window`` reads 0 for a step whose held pairs fit the
    first window (384 slots here) and 1 for one whose pairs pass it (1,200
    on a router pushed onto the held experts), step after step; a layer
    whose one window is the worst case has no second tier to count."""
    layer = _experts(held=2, offset=4, shared=0, total=32)
    it = InputType.recurrent(12, 300)
    params, state = layer.init(jax.random.key(0), it)
    assert state["steps_every_window"].dtype == jnp.int32 \
        and state["steps_every_window"].shape == ()
    state = dict(state, bias=jnp.zeros(32).at[4:6].set(push))
    x = jax.random.normal(jax.random.key(1), (2, 300, 12))
    _, new = layer.apply(params, state, x)
    assert (int(new["pairs_held"]) > 384) == bool(took)
    assert int(new["steps_every_window"]) == took
    _, again = jax.jit(layer.apply)(params, new, x)
    assert int(again["steps_every_window"]) == 2 * took
    # all experts held: one window of tokens x top_k slots, nothing to count
    whole = _experts(held=8, shared=0)
    p, st = whole.init(jax.random.key(0), InputType.recurrent(12, 24))
    _, st = whole.apply(p, st, jax.random.normal(jax.random.key(1),
                                                 (2, 24, 12)))
    assert int(st["steps_every_window"]) == 0 and int(st["pairs_held"]) == 96


def test_the_dropped_counter_sees_a_window_that_did_not_run(monkeypatch):
    """``pairs_dropped`` is held pairs less the rows that the windows which
    ran gave to the grouped products, not arithmetic that is 0 whatever
    runs: a layer whose choice of tier is broken (always the first window,
    here by a ``lax.cond`` that takes its first branch) reads the 816 of
    1,200 pairs it left out, and its result is off."""
    from deeplearning4j_tpu.nn.conf import experts as module
    layer = _experts(held=2, offset=4, shared=0, total=32)
    it = InputType.recurrent(12, 300)
    params, state = layer.init(jax.random.key(0), it)
    state = dict(state, bias=jnp.zeros(32).at[4:6].set(10.0))
    x = jax.random.normal(jax.random.key(1), (2, 300, 12))
    monkeypatch.setattr(module.lax, "cond",
                        lambda pred, first, second, *ops: first(*ops))
    out, new = layer.apply(params, state, x)
    assert int(new["pairs_held"]) == 1200
    assert int(new["pairs_dropped"]) == 1200 - 384
    want = _plain_routed(layer, params, x, state["bias"])
    assert float(jnp.max(jnp.abs(out - want))) > 100 * TOL


def test_a_router_or_gate_the_layer_does_not_know_is_refused():
    it = InputType.recurrent(12, 24)
    with pytest.raises(ValueError, match="router_activation"):
        RoutedExperts(router_activation="tanh").output_type(it)
    with pytest.raises(ValueError, match="shared_gate"):
        RoutedExperts(shared_gate=True, shared_size=0).output_type(it)
    # the softmax scores of one token add up to one over ALL experts, and
    # the weights of its chosen experts to one (no scale)
    layer = _experts(held=2, offset=2, softmax=True)
    params, state = layer.init(jax.random.key(0), it)
    w, idx = layer.route(jax.random.normal(jax.random.key(1), (24, 12)),
                         params["Wr"], state["bias"])
    assert w.shape == idx.shape == (24, 2)
    assert float(jnp.max(jnp.abs(jnp.sum(w, -1) - 1.0))) < 1e-6
    assert int(jnp.max(idx)) > 3          # experts this share does not hold


@pytest.mark.parametrize("softmax", [False, True], ids=["sigmoid", "softmax"])
def test_the_shares_add_up_to_the_uncut_layer(softmax):
    """Four shares of two experts each (offsets 0, 2, 4, 6), the shared
    expert (with its gate, where it has one) counted once (on the first
    share), give what the layer that holds all eight gives."""
    whole = _experts(held=8, softmax=softmax)
    it = InputType.recurrent(12, 24)
    params, state = whole.init(jax.random.key(0), it)
    x = jax.random.normal(jax.random.key(1), (2, 24, 12))
    want, _ = whole.apply(params, state, x)
    total = jnp.zeros_like(want)
    for offset in (0, 2, 4, 6):
        share = _experts(held=2, offset=offset, shared=8 if offset == 0 else 0,
                         softmax=softmax)
        own = {k: (v[offset:offset + 2] if k in ("Wgate", "Wup", "Wdown")
                   else v) for k, v in params.items()
               if share.shared_size or not (k.startswith("S") or k == "Wsg")}
        part, _ = share.apply(own, share.init(jax.random.key(0), it)[1], x)
        total = total + part
    assert float(jnp.max(jnp.abs(total - want))) < TOL


# ------------------------------------------------------- the framework's side
LAYERS = [
    RMSNorm(eps=1e-6),
    RMSNorm(eps=1e-6, zero_centered=True),
    GatedDeltaNet(n_key_heads=2, n_value_heads=4, head_dim=8, chunk=32),
    GatedAttention(n_heads=4, n_kv_heads=2, head_dim=16, rotary_dim=4,
                   rope_theta=1e7, block=32),
    RoutedExperts(n_experts=16, experts_held=4, top_k=3, expert_size=8,
                  shared_size=8, router_activation="softmax",
                  shared_gate=True),
    GatedFeedForward(ff_size=24, remat="full"),
    KimiDeltaAttention(n_heads=2, head_dim=8, low_rank=4, chunk=32),
    MultiHeadLatentAttention(n_heads=2, nope_dim=8, rope_dim=4, v_dim=8,
                             kv_rank=16, block=32),
    RoutedExperts(n_experts=16, experts_held=4, expert_offset=8, top_k=3,
                  expert_size=8, shared_size=8, scaling=2.446),
    TokenOutputLayer(n_out=30, time_block=16),
]


@pytest.mark.parametrize("layer", LAYERS, ids=[
    f"{i}-{type(l).__name__}" for i, l in enumerate(LAYERS)])
def test_config_round_trip(layer):
    import json
    again = layer_from_dict(json.loads(json.dumps(layer_to_dict(layer))))
    assert again == layer and type(again) is type(layer)


def _mln(t=None):
    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(Adam(learning_rate=3e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=30, n_out=12))
            .layer(RMSNorm())
            .layer(KimiDeltaAttention(n_heads=2, head_dim=8, chunk=16,
                                      remat="full"))
            .layer(RMSNorm())
            .layer(MultiHeadLatentAttention(n_heads=2, nope_dim=8,
                                            rope_dim=4, v_dim=8, kv_rank=16,
                                            block=16))
            .layer(RoutedExperts(n_experts=8, experts_held=4, top_k=2,
                                 expert_size=8, shared_size=8,
                                 remat="full"))
            .layer(GatedFeedForward(ff_size=24))
            .layer(TokenOutputLayer(n_out=30, time_block=16))
            .set_input_type(InputType.recurrent(30, t)).build())
    return conf


def test_shape_inference_and_validation_take_the_new_layers():
    conf = _mln(40)
    issues = conf.validate(eval_shape_check=True, batch=2,
                           labels_shape=(2, 40))
    assert not [i for i in issues if i.severity == "error"], issues
    bad = conf.validate(labels_shape=(2, 40, 30), raise_on_error=False)
    assert any(i.rule == "labels-shape" for i in bad)


def test_a_multilayer_network_learns_a_copy_task_through_them():
    net = MultiLayerNetwork(_mln()).init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 30, (4, 41)).astype(np.int32)
    ids[:, 1::2] = ids[:, 0:-1:2]            # every odd id repeats the last
    ds = DataSet(ids[:, :-1], ids[:, 1:])
    net.fit(ds)
    first = net.score()
    for _ in range(60):
        net.fit(ds)
    assert net.score() < 0.8 * first
    assert net.output(ids[:, :-1]).shape == (4, 40, 30)
    experts = [s for s in net.state if "expert_tokens" in s]
    assert len(experts) == 1
    assert int(experts[0]["pairs_dropped"]) == 0
    assert int(jnp.sum(experts[0]["expert_tokens"])) == int(
        experts[0]["pairs_held"])


def test_the_counters_cost_a_turn_of_fit_no_device_program_and_no_sync():
    """The routed layers' counters ride in the step's own state: the
    dispatches of a fit are the train steps and nothing else, the registry
    does not move until it is scraped, and a scrape reads what the device
    counted."""
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    net = MultiLayerNetwork(_mln()).init()
    obs.watch_moe(obs.get_registry(), net)
    ids = np.random.default_rng(1).integers(0, 30, (2, 33)).astype(np.int32)
    ds = DataSet(ids[:, :-1], ids[:, 1:])
    net.fit(ds)                                   # compiles

    def value(name):
        m = obs.get_registry().metric(name)
        return 0.0 if m is None else m.value

    def on_device(what="pairs_held"):
        return int([s for s in net.state if "expert_tokens" in s][0][what])

    obs.get_registry().collect()
    device0, tier0 = on_device(), on_device("steps_every_window")
    every0 = value("moe_every_window_steps_total")
    held0, by_key0 = value("moe_tokens_held_total"), {
        k: v["dispatches"] for k, v in GLOBAL.as_dict()["by_key"].items()}
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(3):
            net.fit(ds)
    by_key = {k: v["dispatches"] for k, v in
              GLOBAL.as_dict()["by_key"].items()}
    moved = {k: v - by_key0.get(k, 0) for k, v in by_key.items()
             if v != by_key0.get(k, 0)}
    assert moved == {"train": 3}
    assert value("moe_tokens_held_total") == held0      # nothing pushed
    assert value("moe_every_window_steps_total") == every0
    obs.get_registry().collect()
    assert on_device() > device0
    assert value("moe_tokens_held_total") - held0 == on_device() - device0
    # the tier counter rides the same way: half the experts of 8 held and a
    # window of four times the even share is the worst case, so one window
    assert value("moe_every_window_steps_total") - every0 \
        == on_device("steps_every_window") - tier0 == 0
    assert value("moe_dropped_tokens_total") == 0.0
    gauges = [n for n in obs.get_registry().names()
              if n.startswith("moe_expert_tokens_")]
    assert len(gauges) >= 4


def _routed_graph(ffn):
    """ids (2 x 300 of 30) -> embedding -> ``ffn`` -> blocked token loss."""
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    g = GraphBuilder()
    g.add_inputs("ids")
    g.add_layer("embed", EmbeddingSequenceLayer(n_in=30, n_out=12), "ids")
    g.add_layer("ffn", ffn, "embed")
    g.add_layer("head", TokenOutputLayer(n_out=30, time_block=16), "ffn")
    g.set_outputs("head")
    g.set_input_types(InputType.recurrent(30, 300))
    return ComputationGraph(dataclasses.replace(
        g.build(), updater=Adam(1e-3))).init()


def test_a_scrape_publishes_the_steps_that_took_every_window():
    """``moe_every_window_steps_total`` is the layers' ``steps_every_window``
    summed, moved at scrape time only: three steps of a network whose routed
    layer is pushed past its first window move it by three."""
    net = _routed_graph(_experts(held=2, offset=4, shared=0, total=32))
    net.state = dict(net.state, ffn=dict(
        net.state["ffn"], bias=jnp.zeros(32).at[4:6].set(10.0)))
    reg = obs.MetricsRegistry()
    obs.watch_moe(reg, net)
    reg.collect()
    before = reg.metric("moe_every_window_steps_total").value
    ids = np.random.default_rng(1).integers(0, 30, (2, 301)).astype(np.int32)
    ds = DataSet(ids[:, :-1], ids[:, 1:])
    for _ in range(3):
        net.fit(ds)
    assert reg.metric("moe_every_window_steps_total").value == before
    reg.collect()
    assert reg.metric("moe_every_window_steps_total").value - before == 3.0
    assert reg.metric("moe_dropped_tokens_total").value == 0.0
    assert int(net.state["ffn"]["steps_every_window"]) == 3


def test_a_checkpoint_from_before_the_tier_counter_restores_with_it_at_zero(
        tmp_path):
    """A zip whose coefficients lack ``steps_every_window`` (written before
    the layer counted the tier) restores: every leaf it holds comes back,
    the counter reads 0. A TRAINED leaf that is missing is still refused."""
    import io
    import zipfile
    from deeplearning4j_tpu.utils.serialization import (restore,
                                                        write_model)
    net = MultiLayerNetwork(_mln()).init()
    ids = np.random.default_rng(1).integers(0, 30, (2, 33)).astype(np.int32)
    net.fit(DataSet(ids[:, :-1], ids[:, 1:]))
    new, old, broken = (str(tmp_path / n) for n in ("new.zip", "old.zip",
                                                    "broken.zip"))
    write_model(net, new)

    def rewrite(path, drop):
        with zipfile.ZipFile(new) as src, zipfile.ZipFile(path, "w") as dst:
            for item in src.namelist():
                data = src.read(item)
                if item == "coefficients.npz":
                    arrays = dict(np.load(io.BytesIO(data)))
                    gone = [k for k in arrays if k.endswith(drop)]
                    assert len(gone) == 1, gone
                    del arrays[gone[0]]
                    buf = io.BytesIO()
                    np.savez(buf, **arrays)
                    data = buf.getvalue()
                dst.writestr(item, data)

    rewrite(old, "/steps_every_window")
    back = restore(old)
    experts = [s for s in back.state if "expert_tokens" in s][0]
    assert int(experts["steps_every_window"]) == 0
    assert int(experts["pairs_held"]) == int(
        [s for s in net.state if "expert_tokens" in s][0]["pairs_held"]) > 0
    for a, b in zip(jax.tree_util.tree_leaves(back.params),
                    jax.tree_util.tree_leaves(net.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    back.fit(DataSet(ids[:, :-1], ids[:, 1:]))          # and trains on
    rewrite(broken, "/Wr")
    with pytest.raises(ValueError, match="Missing array"):
        restore(broken)


def _owners(names):
    """(owners counted, the unowned instructions' primitives)."""
    import collections
    from deeplearning4j_tpu.obs.owners import owner_of
    return collections.Counter(owner_of(n) for n in names), sorted(
        {n.rsplit("/", 1)[-1] for n in names if owner_of(n) is None})


@pytest.mark.parametrize("total,form", [(32, "scatter"), (8, "gather")],
                         ids=["scatter_form", "gather_form"])
def test_a_routed_graph_s_step_has_an_owner_for_all_it_emitted(
        step_op_names, total, form):
    """The optimizer's instructions lie under ``optim.update``; what jax
    emitted without any owner is megablox's group bookkeeping alone (none
    on the CPU, where ``lax.ragged_dot`` runs). Under either form of the
    dispatch (a 16th held, a quarter held): the gather form's backward
    rules carry ``moe.dispatch`` as the scatter form's transposes do."""
    net = _routed_graph(RoutedExperts(
        n_experts=total, experts_held=2, expert_offset=4, top_k=2,
        expert_size=8, shared_size=8, remat="full"))
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    before = GLOBAL.counter(f"moe.dispatch_{form}")
    x = jax.ShapeDtypeStruct((2, 300), jnp.int32)
    names = step_op_names(net, [x], [x])
    assert GLOBAL.counter(f"moe.dispatch_{form}") > before
    backward = [n.rsplit("/", 1)[-1] for n in names
                if "transpose(" in n and "/moe.dispatch/" in n]
    assert "gather" in backward
    assert ("scatter-add" in backward) == (form == "scatter")
    owners, unowned = _owners(names)
    assert any("/optim.update/" in n for n in names)
    assert unowned == [], unowned
    assert {"optim", "loss", "RoutedExperts", "EmbeddingSequenceLayer"} \
        <= set(owners)
    # both tiers of the window are the layer's
    assert any("RoutedExperts:ffn" in n and "branch_1_fun" in n
               for n in names)


def test_a_looped_graph_s_step_has_an_owner_for_all_it_emitted(
        step_op_names):
    """Inside the scan's body too: the residual adds are their vertices',
    the exits the loss's."""
    from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
    from deeplearning4j_tpu.nn.conf.graph import (ElementWiseVertex,
                                                  GraphBuilder, LoopVertex)
    from deeplearning4j_tpu.nn.conf.recurrent import (
        ExitWeightedTokenOutputLayer)
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    b = GraphBuilder()
    b.add_inputs("h")
    b.add_layer("n1", RMSNorm(), "h")
    b.add_layer("attn", RotaryAttention(n_heads=2, head_dim=8, block=8),
                "n1")
    b.add_vertex("add1", ElementWiseVertex("add"), "h", "attn")
    b.add_layer("ffn", GatedFeedForward(ff_size=32), "add1")
    b.add_vertex("add2", ElementWiseVertex("add"), "add1", "ffn")
    b.set_outputs("add2")
    b.set_input_types(InputType.recurrent(16, 24))
    g = GraphBuilder()
    g.add_inputs("ids")
    g.add_layer("embed", EmbeddingSequenceLayer(n_in=11, n_out=16), "ids")
    g.add_layer("loop", LoopVertex(body=b.build(), steps=3), "embed")
    g.add_layer("head", ExitWeightedTokenOutputLayer(
        n_out=11, time_block=8, entropy_weight=0.05), "loop")
    g.set_outputs("head")
    g.set_input_types(InputType.recurrent(11, 24))
    net = ComputationGraph(dataclasses.replace(
        g.build(), updater=Adam(1e-2))).init()
    x = jax.ShapeDtypeStruct((2, 24), jnp.int32)
    names = step_op_names(net, [x], [x])
    owners, unowned = _owners(names)
    assert any("/optim.update/" in n for n in names)
    assert unowned == [], unowned
    assert {"optim", "loss", "LoopVertex", "RotaryAttention", "RMSNorm",
            "GatedFeedForward", "ElementWiseVertex"} <= set(owners)


# ------------------------------------------------ the Qwen3-Next family's side
def test_a_zero_centred_norm_scales_by_one_plus_its_weight():
    it = InputType.recurrent(12, 5)
    plain, centred = RMSNorm(eps=1e-6), RMSNorm(eps=1e-6, zero_centered=True)
    (pp, _), (pc, _) = plain.init(None, it), centred.init(None, it)
    assert set(pc) == {"w"} and float(jnp.max(jnp.abs(pc["w"]))) == 0.0
    x = jax.random.normal(jax.random.key(0), (2, 5, 12))
    w = 0.3 * jax.random.normal(jax.random.key(1), (12,))
    got, _ = centred.apply({"w": w}, {}, x)
    want, _ = plain.apply({"g": 1.0 + w}, {}, x)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    by_hand = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + w)
    assert float(jnp.max(jnp.abs(got - by_hand))) < 1e-6
    # the same gradient for w as for g: Adam without decay takes one step
    dw = jax.grad(lambda w: jnp.sum(jnp.sin(centred.apply({"w": w}, {}, x)[0])))(w)
    dg = jax.grad(lambda g: jnp.sum(jnp.sin(plain.apply({"g": g}, {}, x)[0])))(
        1.0 + w)
    assert float(jnp.max(jnp.abs(dw - dg))) < 1e-6


def _qwen_mln(t=None):
    return (NeuralNetConfiguration.builder().seed(5)
            .updater(Adam(learning_rate=3e-3)).list()
            .layer(EmbeddingSequenceLayer(n_in=30, n_out=12))
            .layer(RMSNorm(zero_centered=True))
            .layer(GatedDeltaNet(n_key_heads=1, n_value_heads=2, head_dim=8,
                                 chunk=16, remat="full"))
            .layer(RMSNorm(zero_centered=True))
            .layer(GatedAttention(n_heads=4, n_kv_heads=2, head_dim=8,
                                  rotary_dim=4, block=16, remat="full"))
            .layer(RoutedExperts(n_experts=8, experts_held=4, top_k=2,
                                 expert_size=8, shared_size=8,
                                 router_activation="softmax",
                                 shared_gate=True))
            .layer(TokenOutputLayer(n_out=30, time_block=16))
            .set_input_type(InputType.recurrent(30, t)).build())


def test_a_network_of_the_qwen_layers_validates_and_learns_a_copy_task():
    issues = _qwen_mln(40).validate(eval_shape_check=True, batch=2,
                                    labels_shape=(2, 40))
    assert not [i for i in issues if i.severity == "error"], issues
    net = MultiLayerNetwork(_qwen_mln()).init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 30, (4, 41)).astype(np.int32)
    ids[:, 1::2] = ids[:, 0:-1:2]            # every odd id repeats the last
    ds = DataSet(ids[:, :-1], ids[:, 1:])
    net.fit(ds)
    first = net.score()
    for _ in range(60):
        net.fit(ds)
    assert net.score() < 0.8 * first
    experts = [s for s in net.state if "expert_tokens" in s]
    assert len(experts) == 1 and int(experts[0]["pairs_dropped"]) == 0

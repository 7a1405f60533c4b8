"""A sliding window in ``blocked_causal_attention`` (both executions), the
band's tables, the YaRN-scaled rotation, and ``RotaryAttention``'s new
fields at their defaults and in a compiled ``Mellum2`` step.

CPU, float32; the Pallas kernels run in interpret mode."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.attention import (
    RotaryAttention, blocked_causal_attention, rotate_half_split,
    yarn_inv_freq)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.perf import pallas as pk
from deeplearning4j_tpu.perf.pallas import attention as kernels

TOL = 2e-5


def _plain(q, k, v, window=None):
    """The whole score matrix with an explicit mask."""
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = cols <= rows
    if window is not None:
        keep &= cols > rows - window
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v)


def _inputs(t, width, heads=2, batch=1):
    ks = jax.random.split(jax.random.key(t + width), 3)
    return tuple(jax.random.normal(k, (batch, heads, t, width)) for k in ks)


def _out_and_grads(fn, q, k, v):
    def run(*a):
        o = fn(*a)
        return jnp.sum(jnp.sin(o)), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        run, (0, 1, 2), has_aux=True))(q, k, v)
    return (o,) + grads


def _counters():
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    c = GLOBAL.as_dict().get("counters", {})
    return [c.get(f"kernel.{impl}_blocked_attention", 0)
            for impl in ("xla", "pallas")]


@pytest.fixture(params=["xla", "pallas"])
def impl(request):
    """The two executions: plain ``jax.numpy`` at heads of 16, the kernels
    (interpreted) at heads of 64; held to the one asked for by the
    ``kernel.*_blocked_attention`` counters."""
    before = _counters()
    pallas = request.param == "pallas"
    with pk.override(enabled=pallas, interpret=True), \
            jax.default_matmul_precision("highest"):
        yield 64 if pallas else 16
    rose = [b > a for a, b in zip(before, _counters())]
    assert rose == [not pallas, pallas]


# tiles of 128 (t = 640 and 600 -> 640: the kernels' own tile is 128 too)
@pytest.mark.parametrize("t,window", [
    (640, 50),       # smaller than a tile
    (640, 128),      # a tile
    (640, 256),      # two tiles: the cell's ratio
    (640, 300),      # not a multiple of the tile
    (600, 200),      # a padded length
    (640, 1),        # the query's own key alone
    (640, 639),      # all but the first key of the last query
])
def test_a_window_is_the_plain_masked_softmax(t, window, impl):
    q, k, v = _inputs(t, impl)
    got = _out_and_grads(lambda *a: blocked_causal_attention(
        *a, 128, window), q, k, v)
    want = _out_and_grads(lambda *a: _plain(*a, window), q, k, v)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) < TOL


@pytest.mark.parametrize("window", [640, 641, 10_000])
def test_a_window_of_at_least_the_length_is_no_window(window, impl):
    q, k, v = _inputs(640, impl)
    got = _out_and_grads(lambda *a: blocked_causal_attention(
        *a, 128, window), q, k, v)
    want = _out_and_grads(lambda *a: blocked_causal_attention(*a, 128),
                          q, k, v)
    for a, b in zip(got, want):
        assert bool(jnp.all(a == b))


def test_rows_without_a_key_in_the_band_s_oldest_tile_are_exact(impl):
    """Window two tiles: the LAST row of a query tile sees no key of the
    band's oldest tile (``exp(-inf - -inf)`` if the fold began there), the
    FIRST sees all but one."""
    q, k, v = _inputs(640, impl)
    got = blocked_causal_attention(q, k, v, 128, 256)
    want = _plain(q, k, v, 256)
    for row in (255, 383, 511, 639, 256, 384, 512):
        assert bool(jnp.all(jnp.isfinite(got[:, :, row])))
        assert float(jnp.max(jnp.abs(got[:, :, row] - want[:, :, row]))) < TOL


# ------------------------------------------------------------ the tables
def _kept(i, j, tile, window):
    """Whether tile pair (i, j) holds a position the mask keeps."""
    t = np.arange(i * tile, (i + 1) * tile)[:, None]
    u = np.arange(j * tile, (j + 1) * tile)[None, :]
    return bool(np.any((u <= t) & (u > t - window)))


@pytest.mark.parametrize("n,tile,window", [
    (32, 512, 1024), (8, 128, 50), (8, 128, 128), (8, 128, 129),
    (8, 128, 300), (8, 128, 1), (8, 128, 1024), (5, 128, 257)])
@pytest.mark.parametrize("by_query", [True, False])
def test_the_band_s_tables_list_the_pairs_with_a_kept_position(
        n, tile, window, by_query):
    back, far_from = kernels._band(window, tile)
    qi, kj = (np.asarray(a) for a in kernels._pairs(n, by_query, back))
    listed = list(zip(qi.tolist(), kj.tolist()))
    want = {(i, j) for i in range(n) for j in range(n)
            if _kept(i, j, tile, window)}
    assert len(listed) == len(set(listed)) and set(listed) == want
    # grouped: a query tile's pairs lie together, its diagonal first; a
    # key tile's together, its diagonal first
    own = qi if by_query else kj
    assert all(own[s] <= own[s + 1] for s in range(len(own) - 1))
    starts = [s for s in range(len(own)) if s == 0 or own[s - 1] != own[s]]
    assert all(qi[s] == kj[s] for s in starts)
    # the pairs that cross the window's far edge: some position masked
    # that the causal mask alone would keep
    for i, j in listed:
        t = np.arange(i * tile, (i + 1) * tile)[:, None]
        u = np.arange(j * tile, (j + 1) * tile)[None, :]
        crosses = bool(np.any((u <= t) & (u <= t - window)))
        assert crosses == (i - j >= far_from), (i, j)


def test_the_cell_s_band_is_93_pairs_of_528():
    back, _ = kernels._band(1024, 512)
    assert back == 2
    for by_query in (True, False):
        assert kernels._pairs(32, by_query, back)[0].shape == (93,)
        assert kernels._pairs(32, by_query)[0].shape == (528,)
    assert kernels._pairs(16, True, back)[0].shape == (45,)


@pytest.mark.parametrize("n", [1, 2, 5, 16])
def test_the_triangle_s_tables_are_what_they_were(n):
    qi, kj = kernels._pairs(n, by_query=True)
    assert list(zip(qi.tolist(), kj.tolist())) == [
        (i, j) for i in range(n) for j in range(i + 1)]
    qi, kj = kernels._pairs(n, by_query=False)
    assert list(zip(qi.tolist(), kj.tolist())) == [
        (i, j) for j in range(n) for i in range(j, n)]
    assert kernels._band(None, 512) is None


def test_supported_takes_the_window():
    q = jax.ShapeDtypeStruct((1, 4, 1024, 128), jnp.float32)
    with pk.override(enabled=True, interpret=True):
        assert kernels.supported(q, q, q, 512)
        assert kernels.supported(q, q, q, 512, 100)
        assert not kernels.supported(q, q, q, 512, 0)


# ---------------------------------------------------- the scaled rotation
PUBLISHED = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
             "original_max_position_embeddings": 8192, "beta_fast": 32,
             "beta_slow": 1, "attention_factor": 1.2772588722239782}


def test_yarn_frequencies_of_the_published_block_by_hand():
    """dim 128, base 5e5, factor 16 over 8192: c(32) = 128 ln(8192 / 64 pi)
    / (2 ln 5e5) = 18.08, c(1) = 128 ln(8192 / 2 pi) / (2 ln 5e5) = 34.98,
    so low 18 and high 35: pairs 0-18 keep theta^(-2j/128), pairs 35-63
    are divided by 16, and pair j between is blended at (j - 18) / 17."""
    ln = math.log
    assert math.floor(128 * ln(8192 / (64 * math.pi)) / (2 * ln(5e5))) == 18
    assert math.ceil(128 * ln(8192 / (2 * math.pi)) / (2 * ln(5e5))) == 35
    f = np.asarray(yarn_inv_freq(128, 5e5, 16, 8192, 32, 1), np.float64)
    plain = 5e5 ** (-2.0 * np.arange(64) / 128)
    assert f.shape == (64,) and f.dtype == np.float64
    np.testing.assert_allclose(f[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(f[35:], plain[35:] / 16, rtol=1e-6)
    assert f[0] == 1.0
    assert f[63] == pytest.approx(5e5 ** (-126 / 128) / 16, rel=1e-6)
    # pair 26: ramp (26 - 18) / 17
    r = 8 / 17
    assert f[26] == pytest.approx((1 - r) * plain[26] + r * plain[26] / 16,
                                  rel=1e-6)
    assert np.all(np.diff(f) < 0)
    assert 0.1 * ln(16) + 1 == pytest.approx(1.2772588722239782, rel=1e-15)


def test_a_scaled_rotation_is_the_frequencies_and_the_factor():
    x = jax.random.normal(jax.random.key(0), (1, 9, 2, 16))
    pos = jnp.arange(9)
    freq = yarn_inv_freq(16, 5e5, 16, 64)
    got = rotate_half_split(x, pos, 16, 5e5, freq, 1.25)
    angle = np.arange(9)[:, None] * np.asarray(freq)[None, :]
    cos, sin = (1.25 * f(angle)[:, None, :] for f in (np.cos, np.sin))
    x1, x2 = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    # the defaults are the plain rotation, bit for bit
    plain = 5e5 ** (-jnp.arange(8, dtype=jnp.float32) * 2.0 / 16)
    assert bool(jnp.all(rotate_half_split(x, pos, 16, 5e5)
                        == rotate_half_split(x, pos, 16, 5e5, plain, 1.0)))


def test_the_layer_reads_the_attention_factor_or_makes_it():
    layer = RotaryAttention(head_dim=128, rope_scaling=PUBLISHED)
    freq, factor = layer._rotation()
    assert factor == 1.2772588722239782 and freq.shape == (64,)
    without = {k: v for k, v in PUBLISHED.items() if k != "attention_factor"}
    assert RotaryAttention(head_dim=128, rope_scaling=without)._rotation()[
        1] == pytest.approx(1.2772588722239782, rel=1e-12)
    assert RotaryAttention(rope_scaling={"rope_type": "default",
                                         "rope_theta": 1e4})._rotation() == (
        None, 1.0)
    with pytest.raises(NotImplementedError):
        RotaryAttention(rope_scaling={"rope_type": "linear", "factor": 2}
                        ).output_type(InputType.recurrent(8, 4))


# ------------------------------------------------------------- the layer
def test_the_new_fields_at_their_defaults_leave_the_layer_as_it_was():
    """Ouro's layer: the same leaves, and the output of the parent's lines
    (projections, the plain rotation, ``blocked_causal_attention`` without
    a window) bit for bit."""
    layer = RotaryAttention(n_heads=4, n_kv_heads=2, head_dim=16, block=32,
                            rope_theta=1e6)
    assert (layer.window, layer.rope_scaling, layer.qk_norm) == (0, None,
                                                                 False)
    it = InputType.recurrent(24, 100)
    params, state = layer.init(jax.random.key(0), it)
    assert set(params) == {"Wq", "Wk", "Wv", "Wo"} and state == {}
    x = jax.random.normal(jax.random.key(1), (2, 100, 24))
    got, _ = layer.apply(params, state, x)

    q = (x @ params["Wq"]).reshape(2, 100, 4, 16)
    k = (x @ params["Wk"]).reshape(2, 100, 2, 16)
    v = (x @ params["Wv"]).reshape(2, 100, 2, 16)
    q, k = (rotate_half_split(a, jnp.arange(100), 16, 1e6) for a in (q, k))
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    k, v = (jnp.repeat(a, 2, axis=1) for a in (k, v))
    o = blocked_causal_attention(q, k, v, 32)
    want = o.transpose(0, 2, 1, 3).reshape(2, 100, 64) @ params["Wo"]
    assert bool(jnp.all(got == want))
    # a window that reaches past the sequence is no window
    wide = dataclasses.replace(layer, window=100)
    assert bool(jnp.all(wide.apply(params, state, x)[0] == got))


def test_the_layer_with_every_new_field_is_the_written_out_form():
    layer = RotaryAttention(
        n_heads=4, n_kv_heads=2, head_dim=16, block=32, rope_theta=5e5,
        window=40, qk_norm=True, eps=1e-6,
        rope_scaling=dict(PUBLISHED, original_max_position_embeddings=64))
    it = InputType.recurrent(24, 100)
    params, _ = layer.init(jax.random.key(0), it)
    assert set(params) == {"Wq", "Wk", "Wv", "Wo", "q_norm", "k_norm"}
    assert bool(jnp.all(params["q_norm"] == 1.0))
    params = dict(params, q_norm=1.0 + 0.1 * jnp.arange(16.0),
                  k_norm=1.0 - 0.02 * jnp.arange(16.0))
    x = jax.random.normal(jax.random.key(1), (2, 100, 24))
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(params, {}, x)

        def head_norm(a, w):
            return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True)
                                     + 1e-6) * w

        q = head_norm((x @ params["Wq"]).reshape(2, 100, 4, 16),
                      params["q_norm"])
        k = head_norm((x @ params["Wk"]).reshape(2, 100, 2, 16),
                      params["k_norm"])
        v = (x @ params["Wv"]).reshape(2, 100, 2, 16)
        freq = yarn_inv_freq(16, 5e5, 16, 64)
        q, k = (rotate_half_split(a, jnp.arange(100), 16, 5e5, freq,
                                  1.2772588722239782) for a in (q, k))
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        o = _plain(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1), 40)
        want = o.transpose(0, 2, 1, 3).reshape(2, 100, 64) @ params["Wo"]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_the_layer_counts_its_window_and_survives_the_configuration_s_json():
    from deeplearning4j_tpu.nn.conf.graph import (
        ComputationGraphConfiguration)
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL

    def count(name):
        return GLOBAL.as_dict().get("counters", {}).get(name, 0)

    layer = RotaryAttention(n_heads=2, head_dim=8, block=16, window=8)
    params, _ = layer.init(jax.random.key(0), InputType.recurrent(12, 40))
    before = [count("attention.rotary_windowed"),
              count("attention.rotary_blocked")]
    layer.apply(params, {}, jnp.zeros((1, 40, 12)))
    dataclasses.replace(layer, window=0).apply(params, {},
                                               jnp.zeros((1, 40, 12)))
    assert [count("attention.rotary_windowed"),
            count("attention.rotary_blocked")] == [before[0] + 1,
                                                   before[1] + 2]
    conf = _mellum(layers=4).conf()
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    for i in range(4):
        assert again.vertices[f"l{i}_attn"] == conf.vertices[f"l{i}_attn"]


# ------------------------------------------------------- the model's step
def _mellum(layers=4, **kw):
    from deeplearning4j_tpu.models import Mellum2
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    config = {
        "attention_bias": False, "head_dim": 64, "hidden_size": 32,
        "intermediate_size": 48, "layer_types": kinds * 2,
        "mlp_layer_types": ["sparse"] * 7 + ["dense"],
        "moe_intermediate_size": 16, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 8,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
        "rope_parameters": {
            "full_attention": dict(PUBLISHED,
                                   original_max_position_embeddings=64),
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 256, "tie_word_embeddings": False,
        "use_sliding_window": True, "vocab_size": 50}
    return Mellum2(config, layers=layers, experts_held=4, expert_offset=2,
                   sequence_length=640, attention_block=128, loss_block=128,
                   **kw)


def test_the_builder_reads_each_layer_s_type():
    from deeplearning4j_tpu.nn.conf.experts import (GatedFeedForward,
                                                    RoutedExperts)
    conf = _mellum(layers=8).conf()
    attn = [conf.vertices[f"l{i}_attn"][0] for i in range(8)]
    assert [a.window for a in attn] == [256, 256, 256, 0] * 2
    assert [a.rope_scaling is not None for a in attn] == [
        False, False, False, True] * 2
    assert all(a.qk_norm and (a.n_heads, a.n_kv_heads) == (4, 2)
               for a in attn)
    ffn = [conf.vertices[f"l{i}_ffn"][0] for i in range(8)]
    assert all(isinstance(f, RoutedExperts) for f in ffn[:7])
    assert isinstance(ffn[7], GatedFeedForward) and ffn[7].ff_size == 48
    assert (ffn[0].n_experts, ffn[0].experts_held, ffn[0].expert_offset,
            ffn[0].top_k, ffn[0].shared_size, ffn[0].router_activation) == (
        8, 4, 2, 2, 0, "softmax")
    plain = _mellum(qk_norm=False, router_activation="sigmoid").conf()
    assert not plain.vertices["l0_attn"][0].qk_norm
    assert plain.vertices["l0_ffn"][0].router_activation == "sigmoid"


def test_a_compiled_step_has_an_owner_for_all_and_the_band_kernels_their_scope(
        step_op_names):
    """Every instruction jax emits into a ``Mellum2`` step has an owner
    (``obs/owners.py``), and what the band kernels (here their interpreted
    bodies) lower to carries the layer's marker and ``rattn.attend``,
    forward and backward: the per-layer readers find them by it."""
    import collections

    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.obs.owners import owner_of
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL

    def count(name):
        return GLOBAL.as_dict().get("counters", {}).get(name, 0)

    before = (_counters(), count("attention.rotary_windowed"))
    with pk.override(enabled=True, interpret=True):
        net = ComputationGraph(_mellum().conf()).init()
        x = jax.ShapeDtypeStruct((1, 640), jnp.int32)
        names = step_op_names(net, [x], [x])
    # four layers, each traced once; three of them with a window
    assert _counters() == [before[0][0], before[0][1] + 4]
    assert count("attention.rotary_windowed") == before[1] + 3
    owners = collections.Counter(owner_of(n) for n in names)
    unowned = sorted({n.rsplit("/", 1)[-1] for n in names
                      if owner_of(n) is None})
    assert unowned == [], unowned
    assert {"optim", "loss", "RotaryAttention", "RoutedExperts", "RMSNorm",
            "ElementWiseVertex", "EmbeddingSequenceLayer"} <= set(owners)
    for kernel, way in (("mla_attend_fwd", "jvp("),
                        ("mla_attend_bwd", "transpose(")):
        for layer in ("l0_attn", "l3_attn"):      # a band, the triangle
            mine = [n for n in names if kernel in n
                    and f"RotaryAttention:{layer})" in n]
            assert len(mine) > 20, (kernel, layer, len(mine))
            assert all("rattn.attend" in n and way in n for n in mine)
    for scope in ("rattn.qk_norm", "rattn.rope"):
        under = [n for n in names if scope in n and "RotaryAttention:" in n]
        assert any("transpose(" in n for n in under), scope
        assert any("transpose(" not in n for n in under), scope

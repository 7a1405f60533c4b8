"""``Mamba2Mixer`` (a state-space token mixer) and what the Granite 4.0-H
line needs beside it: ``chunked_ssd`` against the token-by-token recurrence
(chunks, ragged lengths, decays near 0 and near 1, groups; forward and
gradients), the layer against the benchmark reference's mixer, a
right-padded batch, serialisation; ``RotaryAttention``'s two new fields
(no rotation, a softmax scale that is not ``1 / sqrt(d)``) and that the
layer at their defaults lowers to the text it lowered to; the
``GraniteHybrid`` builder's parameter counts, its round trip and what it
raises on; and the owners of a step that holds all of it."""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.models import GraniteHybrid
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import (RotaryAttention,
                                                  blocked_causal_attention,
                                                  rotate_half_split)
from deeplearning4j_tpu.nn.conf.graph import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.layers import (Layer, apply_layer,
                                               layer_from_dict, layer_to_dict)
from deeplearning4j_tpu.nn.conf.state_space import (PROJECTION_KEPT,
                                                    Mamba2Mixer, chunked_ssd)
from deeplearning4j_tpu.nn.graph import ComputationGraph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the public keys of ibm-granite/granite-4.0-h-micro's config.json
PUBLIC = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_size": 2048,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "num_attention_heads": 32,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
TINY = {**PUBLIC, "hidden_size": 32, "mamba_n_heads": 4, "mamba_d_head": 16,
        "mamba_d_state": 8, "mamba_chunk_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "shared_intermediate_size": 48,
        "vocab_size": 29}


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference of the Granite configuration: its
    ``recurrence`` runs the scan token by token, its ``mixer`` is the
    layer's equations in float32."""
    spec = importlib.util.spec_from_file_location(
        "granite_reference_for_layer_tests", os.path.join(
            ROOT, "benchmark", "references",
            "granite_4p0_h_micro_pp4_vp8.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ----------------------------------------------------------------- the scan
def _operands(t, groups, decay, seed=0, b=2, h=4, p=8, n=16):
    """Seeded operands of a scan whose steps decay ``near_zero`` (the state
    all but forgotten a step), ``near_one`` (all but kept) or ``mixed``
    (steps of both kinds in one head)."""
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (b, t, h, p))
    bm = jax.random.normal(k[1], (b, t, groups, n))
    cm = jax.random.normal(k[2], (b, t, groups, n))
    dt = jnp.exp(jax.random.uniform(k[3], (b, t, h), minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    rate = {"near_zero": -400.0, "near_one": -1e-3, "mixed": -1.0}[decay]
    a = rate * jnp.exp(jax.random.uniform(k[4], (h,), minval=-0.5,
                                          maxval=0.5))
    if decay == "mixed":                 # long steps among short ones
        dt = jnp.where(jax.random.bernoulli(k[5], 0.2, dt.shape), 30.0 * dt,
                       dt)
    return x, dt, a, bm, cm


@pytest.mark.parametrize("t,chunk,groups,decay", [
    (37, 4, 1, "mixed"), (64, 16, 2, "mixed"), (48, 16, 1, "near_zero"),
    (50, 16, 2, "near_one"), (512, 256, 1, "mixed"), (300, 256, 2, "mixed"),
    (7, 256, 1, "near_one")])
def test_the_chunked_scan_is_the_recurrence(ref, t, chunk, groups, decay):
    """Forward and every operand's gradient, in float32, at lengths that
    are and are not multiples of the chunk."""
    args = _operands(t, groups, decay)
    probe = jax.random.normal(jax.random.key(9), args[0].shape)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * probe)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.recurrence)(*args)
        got = jax.jit(lambda *a: chunked_ssd(*a, chunk=chunk))(*args)
        g_want = jax.jit(jax.grad(loss(ref.recurrence),
                                  argnums=(0, 1, 2, 3, 4)))(*args)
        g_got = jax.jit(jax.grad(
            loss(lambda *a: chunked_ssd(*a, chunk=chunk)),
            argnums=(0, 1, 2, 3, 4)))(*args)
    scale = float(jnp.max(jnp.abs(want)))
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * max(scale, 1.0)
    for name, a, b in zip("x dt A B C".split(), g_got, g_want):
        top = max(float(jnp.max(jnp.abs(b))), 1e-6)
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * top, name


def test_the_scan_takes_bfloat16_and_refuses_heads_no_group_divides():
    x, dt, a, bm, cm = _operands(32, 1, "mixed")
    low = chunked_ssd(x.astype(jnp.bfloat16), dt, a, bm.astype(jnp.bfloat16),
                      cm.astype(jnp.bfloat16), chunk=8)
    full = chunked_ssd(x, dt, a, bm, cm, chunk=8)
    assert low.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(low - full))) < 0.05 * float(
        jnp.max(jnp.abs(full)))
    with pytest.raises(ValueError):
        chunked_ssd(x, dt, a, jnp.zeros((2, 32, 3, 16)),
                    jnp.zeros((2, 32, 3, 16)), chunk=8)


# ---------------------------------------------------------------- the layer
def _layer_and_leaves(d=12, groups=1, seed=0, **fields):
    layer = Mamba2Mixer(n_heads=4, head_dim=6, state_size=8, n_groups=groups,
                        chunk=8, **fields)
    params, _ = layer.init(jax.random.key(seed), InputType.recurrent(d, 20))
    # leaves that start at a constant get a seeded spread, so that every
    # gradient leaf is exercised away from its special point
    k = jax.random.split(jax.random.key(seed + 1), 3)
    if "conv_b" in params:
        params["conv_b"] = 0.1 * jax.random.normal(k[0],
                                                   params["conv_b"].shape)
    params["D"] = 1.0 + 0.1 * jax.random.normal(k[1], params["D"].shape)
    params["norm"] = 1.0 + 0.1 * jax.random.normal(k[2],
                                                   params["norm"].shape)
    return layer, params


def _dims(layer, d):
    inner = layer.n_heads * layer.head_dim
    return {"d": d, "ssm_heads": layer.n_heads,
            "ssm_head_dim": layer.head_dim, "groups": layer.n_groups,
            "state": layer.state_size, "inner": inner,
            "conv_cols": inner + 2 * layer.n_groups * layer.state_size,
            "eps": layer.eps}


@pytest.mark.parametrize("groups,conv_bias", [(1, True), (2, True),
                                              (1, False)])
def test_forward_and_every_gradient_leaf_follow_the_reference(ref, groups,
                                                              conv_bias):
    d = 12
    layer, params = _layer_and_leaves(d, groups, conv_bias=conv_bias)
    assert ("conv_b" in params) == conv_bias
    x = jax.random.normal(jax.random.key(3), (2, 20, d))
    probe = jax.random.normal(jax.random.key(4), (2, 20, d))
    m = _dims(layer, d)
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.jit(jax.value_and_grad(
            lambda p, xx: jnp.sum(ref.mixer(m, p, xx, "highest") * probe),
            argnums=(0, 1)))(params, x)
        got, g_got = jax.jit(jax.value_and_grad(
            lambda p, xx: jnp.sum(layer.apply(p, {}, xx)[0] * probe),
            argnums=(0, 1)))(params, x)
    assert abs(float(got - want)) < 1e-4 * max(abs(float(want)), 1.0)
    assert set(g_got[0]) == set(params)
    for leaf in params:
        top = max(float(jnp.max(jnp.abs(g_want[0][leaf]))), 1e-6)
        assert float(jnp.max(jnp.abs(g_got[0][leaf] - g_want[0][leaf]))) \
            < 2e-4 * top, leaf
    assert float(jnp.max(jnp.abs(g_got[1] - g_want[1]))) < 2e-4 * float(
        jnp.max(jnp.abs(g_want[1])))


def test_the_gate_comes_before_the_norm(monkeypatch):
    from deeplearning4j_tpu.nn.conf import state_space

    layer, p = _layer_and_leaves()
    x = jax.random.normal(jax.random.key(5), (1, 20, 12))
    first = layer.apply(p, {}, x)[0]
    monkeypatch.setattr(
        state_space, "gated_norm",
        lambda y, gate, weight, eps: state_space.rms_norm(y, weight, eps)
        * gate)
    other = layer.apply(p, {}, x)[0]
    assert float(jnp.max(jnp.abs(first - other))) > 1e-3


def test_an_input_changed_at_step_t_moves_no_output_before_t():
    layer, p = _layer_and_leaves()
    x = jax.random.normal(jax.random.key(6), (1, 20, 12))
    moved = x.at[0, 11].add(1.0)
    a, b = (layer.apply(p, {}, v)[0] for v in (x, moved))
    assert float(jnp.max(jnp.abs(a[0, :11] - b[0, :11]))) == 0.0
    assert float(jnp.max(jnp.abs(a[0, 11:] - b[0, 11:]))) > 0.0


def test_a_right_padded_batch_is_exact_and_masked_steps_are_zero():
    layer, p = _layer_and_leaves()
    x = jax.random.normal(jax.random.key(4), (2, 20, 12))
    mask = jnp.asarray(np.arange(20)[None, :] < np.array([[20], [13]]),
                       jnp.float32)
    padded = layer.apply(p, {}, x, mask=mask)[0]
    alone = layer.apply(p, {}, x[1:, :13])[0]
    assert float(jnp.max(jnp.abs(padded[1, :13] - alone[0]))) < 1e-5
    assert float(jnp.max(jnp.abs(padded[1, 13:]))) == 0.0
    assert float(jnp.max(jnp.abs(padded[0] - layer.apply(p, {}, x)[0][0]))) \
        == 0.0


def test_the_layer_is_registered_serialises_and_validates():
    layer = Mamba2Mixer(n_heads=8, head_dim=4, n_groups=2, conv_bias=False,
                        keep_projection=False, remat="full")
    assert layer_from_dict(layer_to_dict(layer)) == layer
    it = InputType.recurrent(12, 7)
    assert layer.output_type(it) == it
    with pytest.raises(ValueError):
        Mamba2Mixer(n_heads=4, n_groups=3).output_type(it)
    with pytest.raises(ValueError):
        Mamba2Mixer(conv_size=0).output_type(it)


def test_a_rematerialised_layer_keeps_its_wide_product_where_it_says_so():
    """``u W_in`` is made once a step under ``remat="full"`` with
    ``keep_projection`` and twice without it (or under
    ``"nothing_saveable"``); ``remat_kept_bytes`` is its size."""
    from deeplearning4j_tpu.perf.fusion import kept_names

    x = jax.random.normal(jax.random.key(2), (1, 16, 12))

    def wide_products(layer):
        _, p = _layer_and_leaves()
        jaxpr = jax.make_jaxpr(jax.grad(lambda pp, xx: jnp.sum(apply_layer(
            layer, pp, {}, xx, train=True, rng=None, mask=None,
            name="mix")[0])))(p, x)
        columns = p["Win"].shape[1]

        def count(jp):
            n = 0
            for eqn in jp.eqns:
                if (eqn.primitive.name == "dot_general"
                        and eqn.outvars[0].aval.shape == (1, 16, columns)):
                    n += 1
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    n += count(sub)
            return n

        return count(jaxpr.jaxpr)

    kept, _ = _layer_and_leaves(remat="full")
    dropped, _ = _layer_and_leaves(remat="full", keep_projection=False)
    nothing, _ = _layer_and_leaves(remat="nothing_saveable")
    assert kept_names(kept) == PROJECTION_KEPT
    assert kept_names(dropped) == kept_names(nothing) == ()
    assert wide_products(kept) == 1
    assert wide_products(dropped) == wide_products(nothing) == 2
    it = InputType.recurrent(12, 16)
    # 2 x 24 inner columns, 2 x 8 of B and C, 4 of dt
    assert kept.remat_kept_bytes(it, jnp.bfloat16) == 16 * (48 + 16 + 4) * 2


# ------------------------------------------------- attention without rotation
def _apply_before_this_pr(layer, params, x):
    """``RotaryAttention.apply`` as it stood before the two fields (no
    q/k norm, no mask: what the lowered text below needs)."""
    bsz, t, _ = x.shape
    h, dh = layer.n_heads, layer.head_dim
    hkv = layer.n_kv_heads or h
    q = (x @ params["Wq"]).reshape(bsz, t, h, dh)
    k = (x @ params["Wk"]).reshape(bsz, t, hkv, dh)
    v = (x @ params["Wv"]).reshape(bsz, t, hkv, dh)
    with jax.named_scope("rattn.rope"):
        positions = jnp.arange(t)
        inv_freq, factor = layer._rotation()
        q, k = (rotate_half_split(a, positions, dh, layer.rope_theta,
                                  inv_freq, factor) for a in (q, k))
    window = layer.window if 0 < layer.window < t else None
    with jax.named_scope("rattn.attend"):
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        if h != hkv:
            k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
        o = blocked_causal_attention(q, k, v, layer.block, window)
    return o.transpose(0, 2, 1, 3).reshape(bsz, t, h * dh) @ params["Wo"]


@pytest.mark.parametrize("window", [0, 8])
def test_the_layer_at_the_new_fields_defaults_lowers_to_the_text_it_did(
        window):
    layer = RotaryAttention(n_heads=4, n_kv_heads=2, head_dim=8, block=8,
                            window=window)
    assert layer.position_embedding == "rope" and layer.softmax_scale == 0.0
    it = InputType.recurrent(16, 24)
    params, _ = layer.init(jax.random.key(0), it)
    assert sorted(params) == ["Wk", "Wo", "Wq", "Wv"]
    x = jax.ShapeDtypeStruct((2, 24, 16), jnp.float32)

    def lowered(fn):
        return jax.jit(jax.grad(lambda p, xx: jnp.sum(fn(p, xx)))).lower(
            params, x).as_text()

    assert lowered(lambda p, xx: layer.apply(p, {}, xx)[0]) == lowered(
        lambda p, xx: _apply_before_this_pr(layer, p, xx))


def test_attention_without_positions_at_a_scale_of_its_own(ref):
    """Against the plain masked softmax of the benchmark's reference
    (``m_a q k^T``, the whole row), several tiles, grouped heads; the
    counters say no rotation ran."""
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL

    layer = RotaryAttention(n_heads=4, n_kv_heads=2, head_dim=8, block=8,
                            position_embedding="nope", softmax_scale=1 / 64)
    params, _ = layer.init(jax.random.key(0), InputType.recurrent(16, 24))
    assert sorted(params) == ["Wk", "Wo", "Wq", "Wv"]
    x = jax.random.normal(jax.random.key(1), (2, 24, 16))
    m = {"heads": 4, "kv_heads": 2, "head_dim": 8, "m_a": 1 / 64}
    before = GLOBAL.counter("attention.nope")
    with jax.default_matmul_precision("highest"):
        got = layer.apply(params, {}, x)[0]
        want = ref.attention(m, params, x, "highest")
    assert GLOBAL.counter("attention.nope") == before + 1
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # the scale matters, and so does the missing rotation
    for other in (dataclasses.replace(layer, softmax_scale=0.0),
                  dataclasses.replace(layer, position_embedding="rope")):
        assert float(jnp.max(jnp.abs(
            other.apply(params, {}, x)[0] - want))) > 1e-4
    # shifting the sequence shifts the output: nothing knows a position but
    # the causal mask
    moved = layer.apply(params, {}, x[:, 4:])[0]
    assert moved.shape == (2, 20, 16)
    with pytest.raises(ValueError):
        RotaryAttention(position_embedding="alibi").output_type(
            InputType.recurrent(16, 24))
    with pytest.raises(ValueError):
        RotaryAttention(softmax_scale=-1.0).output_type(
            InputType.recurrent(16, 24))


# -------------------------------------------------------------- the builder
def _count(conf) -> int:
    """Parameters of a configuration from its shapes alone (nothing is
    drawn: the published size is 3.2 billion)."""
    types = conf.vertex_input_types()
    total = 0
    for name, (obj, _) in conf.vertices.items():
        if isinstance(obj, Layer):
            shapes = jax.eval_shape(
                lambda k, o=obj, it=types[name][0]: o.init(k, it)[0],
                jax.random.key(0))
            total += sum(math.prod(a.shape)
                         for a in jax.tree_util.tree_leaves(shapes))
    return total


@pytest.mark.parametrize("layers,rows,want", [
    (40, 100352, 3_191_396_096), (10, 12544, 772_160_448)])
def test_the_builder_counts_the_published_model_and_the_cut(layers, rows,
                                                            want):
    conf = GraniteHybrid(PUBLIC, layers=layers, vocab_rows=rows,
                         sequence_length=8192).conf()
    assert _count(conf) == want
    kinds = [type(conf.vertices[f"l{i}_ssm" if f"l{i}_ssm" in conf.vertices
                                else f"l{i}_attn"][0]).__name__
             for i in range(10)]
    assert kinds == ["Mamba2Mixer"] * 5 + ["RotaryAttention"] + [
        "Mamba2Mixer"] * 4


def test_the_builder_wires_the_four_multipliers_and_ties_the_head():
    conf = GraniteHybrid(TINY, layers=6, sequence_length=16,
                         projections_kept=2).conf()
    conf.validate()
    scales = {n: v[0].scale for n, v in conf.vertices.items()
              if type(v[0]).__name__ == "ScaleVertex"}
    assert scales["embed_scale"] == 12 and scales["logit_scale"] == 1 / 8
    assert all(scales[f"l{i}_{b}_scale"] == 0.22 for i in range(6)
               for b in ("mix", "ffn"))
    assert len(scales) == 2 + 2 * 6
    assert conf.vertices["head"][0].tied_to == "embed"
    assert list(conf.vertices["head"][1]) == ["logit_scale"]
    attn = conf.vertices["l5_attn"][0]
    assert (attn.position_embedding, attn.softmax_scale, attn.n_kv_heads) \
        == ("nope", 1 / 64, 2)
    keeps = [conf.vertices[f"l{i}_ssm"][0].keep_projection for i in range(5)]
    assert keeps == [True, True, False, False, False]
    again = ComputationGraphConfiguration.from_dict(conf.to_dict())
    assert again.to_dict() == conf.to_dict()
    untied = GraniteHybrid(TINY, layers=6, sequence_length=16,
                           tied=False).conf()
    assert _count(untied) - _count(conf) == 29 * 32


def test_the_builder_raises_on_what_it_does_not_build():
    GraniteHybrid(TINY)
    for key, value in [("num_local_experts", 8),
                       ("position_embedding_type", "rope"),
                       ("mamba_proj_bias", True), ("attention_bias", True),
                       ("layer_types", ["mamba", "sliding_attention"] * 20)]:
        with pytest.raises(NotImplementedError):
            GraniteHybrid({**TINY, key: value})
    with pytest.raises(ValueError):
        GraniteHybrid({**TINY, "mamba_expand": 3}).conf()


def test_a_tiny_model_trains_through_fit_and_scores_what_its_output_says():
    from deeplearning4j_tpu.datasets.dataset import DataSet

    net = ComputationGraph(GraniteHybrid(TINY, layers=6,
                                         sequence_length=16).conf()).init()
    assert net.params["head"] == {}
    ids = np.asarray(jax.random.randint(jax.random.key(0), (4, 17), 0, 29))
    ds = DataSet(ids[:, :-1], ids[:, 1:])
    first = float(net.score_dataset(ds))
    probs = np.asarray(net.output(ids[:, :-1])[0])
    picked = np.take_along_axis(probs, ids[:, 1:, None], -1)[..., 0]
    assert abs(first - float(-np.log(picked).mean())) < 1e-4
    for _ in range(20):
        net.fit(ds)
    assert float(net.score_dataset(ds)) < first - 0.1
    for counter in ("ssm.mamba2", "attention.nope", "head.tied",
                    "kernel.xla_ssd_scan"):
        assert net.compile_watch.counter(counter) > 0, counter


# ------------------------------------------------------------------ owners
def test_a_step_with_all_of_it_has_an_owner_for_all_it_emitted(
        step_op_names):
    """None of the mixer's operations lies outside ``Mamba2Mixer:<name>``;
    its five scopes are there forward and backward; the scale vertices'
    multiplies are owned."""
    from deeplearning4j_tpu.obs.owners import owner_of

    net = ComputationGraph(GraniteHybrid(TINY, layers=6,
                                         sequence_length=16).conf()).init()
    x = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    names = step_op_names(net, [x], [x])
    assert [n for n in names if owner_of(n) is None] == []
    owners = {owner_of(n) for n in names}
    assert {"Mamba2Mixer", "RotaryAttention", "GatedFeedForward",
            "TokenOutputLayer", "EmbeddingSequenceLayer", "loss",
            "optim"} <= owners
    for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
                  "ssm.out_proj"):
        mine = [n for n in names if scope in n]
        assert mine and all("Mamba2Mixer:l" in n for n in mine), scope
        assert any("transpose(" in n for n in mine), scope
        assert any("transpose(" not in n for n in mine), scope
    assert not [n for n in names if "rattn.rope" in n]

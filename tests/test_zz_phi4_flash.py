"""What Phi-4-mini-flash-reasoning (SambaY) needs of the program, layer by
layer against the benchmark's plain reference on seeded weights (small
sizes, CPU, float32): ``Mamba1Mixer`` with its scan output handed on,
``GatedMemoryUnit``, ``DifferentialAttention`` under a window, causal and
over another layer's keys and values, ``LayerNorm``; the graph's wiring of
values a layer hands on beside its output (the gradient of the layer that
made a value is the SUM over its readers: cut one and it is not),
validation and the memory report of that wiring, the ``Phi4Flash``
builder's round trip, what it derives and raises on, and the two hand
counts."""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import Phi4Flash
from deeplearning4j_tpu.models.phi4_flash import derive_layer_types
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import (DifferentialAttention,
                                                  differential_lambda_init)
from deeplearning4j_tpu.nn.conf.graph import (ComputationGraphConfiguration,
                                              GraphBuilder)
from deeplearning4j_tpu.nn.conf.layers import (apply_layer, layer_from_dict,
                                               layer_to_dict)
from deeplearning4j_tpu.nn.conf.normalization import LayerNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.nn.conf.state_space import (GatedMemoryUnit,
                                                    Mamba1Mixer)
from deeplearning4j_tpu.nn.graph import ComputationGraph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the public keys of microsoft/Phi-4-mini-flash-reasoning's config.json
PUBLIC = {
    "hidden_size": 2560, "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "mb_per_layer": 2, "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": 200064,
}
TINY = {**PUBLIC, "hidden_size": 32, "intermediate_size": 48,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "sliding_window": 8, "vocab_size": 29}
CUT = [14, 15, 16, 17, 18, 19]
T = 21                      # no multiple of the chunk (8) or the tile (8)


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference of the configuration."""
    spec = importlib.util.spec_from_file_location(
        "phi4_flash_reference", os.path.join(
            ROOT, "benchmark", "references", "phi4_mini_flash_pp6_vp8.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def m():
    """The reference's ``dims`` at the tiny widths."""
    return {"d": 32, "heads": 4, "kv_heads": 2, "head_dim": 8, "inner": 64,
            "state": 16, "taps": 4, "rank": 2, "ff": 48, "eps": 1e-5,
            "window": 8}


def _draw(layer, width=32, seed=0):
    """A layer's leaves, every one random (biases and vectors too)."""
    params, _ = layer.init(jax.random.key(seed),
                           InputType.recurrent(width, T))
    keys = jax.random.split(jax.random.key(seed + 1), len(params))
    out = {}
    for key, (name, a) in zip(keys, sorted(params.items())):
        noise = 0.3 * jax.random.normal(key, a.shape)
        out[name] = a + noise if a.ndim == 1 else a
    return out


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1.0)


def _x(width=32, seed=3):
    return jax.random.normal(jax.random.key(seed), (2, T, width))


@pytest.mark.parametrize("share", [False, True])
def test_the_mamba_1_mixer_follows_the_reference(ref, m, share):
    layer = Mamba1Mixer(chunk=8, share_scan=share)
    p, x = _draw(layer), _x()
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(p, {}, x)
        want, memory = ref.mamba(m, p, x, "highest")
    if share:
        got, values = got
        assert set(values) == {"scan"} == set(layer.shared_values(
            InputType.recurrent(32, T)))
        _close(values["scan"], memory)
        assert layer.shared_values(InputType.recurrent(32, T))[
            "scan"].size == 64
    else:
        assert layer.shared_values(InputType.recurrent(32, T)) == {}
    _close(got, want)
    assert set(p) == {"Win", "conv", "conv_b", "Wx", "Wdt", "dt_bias",
                      "A_log", "D", "Wout"}
    assert p["A_log"].shape == (64, 16) and p["Wdt"].shape == (2, 64)


def test_the_gated_memory_unit_follows_the_reference(ref, m):
    layer = GatedMemoryUnit(memory_size=64)
    p, x = _draw(layer), _x()
    memory = _x(64, seed=5)
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(p, {}, x, memory=memory)
        want = ref.gated_memory_unit(m, p, x, memory, "highest")
    _close(got, want)
    assert set(p) == {"W1", "W2"} and layer.extra_inputs == ("memory",)


@pytest.mark.parametrize("kind", ["window", "causal", "shares", "kv_from"])
def test_the_differential_attention_follows_the_reference(ref, m, kind):
    layer = DifferentialAttention(
        n_heads=4, n_kv_heads=2, head_dim=8, layer_index=17, block=8,
        window=8 if kind == "window" else 0, share_kv=kind == "shares",
        kv_from="l17_attn" if kind == "kv_from" else "")
    p, x = _draw(layer), _x()
    kv = _x(32, seed=7) if kind == "kv_from" else None
    extra = {"kv": kv} if kind == "kv_from" else {}
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(p, {}, x, **extra)
        want, want_kv = ref.differential_attention(
            m, p, x, 17, 8 if kind == "window" else None, "highest", kv=kv)
    if kind == "shares":
        got, values = got
        _close(values["kv"], want_kv)
    _close(got, want)
    own = {"Wq", "bq"} if kind == "kv_from" else {"Wqkv", "bqkv"}
    assert set(p) == own | {"Wo", "bo", "subln", "lambda_q1", "lambda_k1",
                            "lambda_q2", "lambda_k2"}
    assert layer.extra_inputs == (("kv",) if kind == "kv_from" else ())


def test_the_window_changes_the_output_and_lambda_init_reads_the_index(ref):
    wide = DifferentialAttention(n_heads=4, n_kv_heads=2, head_dim=8, block=8)
    p, x = _draw(wide), _x()
    narrow = dataclasses.replace(wide, window=8)
    assert float(jnp.max(jnp.abs(wide.apply(p, {}, x)[0]
                                 - narrow.apply(p, {}, x)[0]))) > 1e-3
    # the first 8 queries see the same keys either way
    _close(wide.apply(p, {}, x)[0][:, :8], narrow.apply(p, {}, x)[0][:, :8])
    later = dataclasses.replace(wide, layer_index=19)
    assert float(jnp.max(jnp.abs(wide.apply(p, {}, x)[0]
                                 - later.apply(p, {}, x)[0]))) > 1e-3
    for i in (0, 15, 17, 31):
        assert differential_lambda_init(i) == pytest.approx(
            0.8 - 0.6 * math.exp(-0.3 * i)) == pytest.approx(
            ref.lambda_init(i))


def test_layer_norm_follows_the_reference_in_float32_statistics(ref):
    layer = LayerNorm(eps=1e-5)
    p, x = _draw(layer), 3.0 + _x()
    _close(layer.apply(p, {}, x)[0],
           ref.layer_norm(x, p["weight"], p["bias"], 1e-5))
    low = layer.apply(p, {}, x.astype(jnp.bfloat16))[0]
    assert low.dtype == jnp.bfloat16
    _close(low.astype(jnp.float32), ref.layer_norm(
        x.astype(jnp.bfloat16).astype(jnp.float32), p["weight"], p["bias"],
        1e-5), tol=2e-2)
    assert set(p) == {"weight", "bias"}


@pytest.mark.parametrize("layer", [
    Mamba1Mixer(chunk=8, share_scan=True), GatedMemoryUnit(memory_size=64),
    DifferentialAttention(n_heads=4, n_kv_heads=2, head_dim=8, window=8,
                          layer_index=15),
    DifferentialAttention(n_heads=4, n_kv_heads=2, head_dim=8,
                          kv_from="l17_attn"),
    LayerNorm(eps=1e-5)], ids=lambda l: type(l).__name__ + str(
        getattr(l, "kv_from", "")))
def test_a_layer_survives_its_dictionary(layer):
    assert layer_from_dict(layer_to_dict(layer)) == layer


@pytest.mark.parametrize("bad", [
    DifferentialAttention(n_heads=3, n_kv_heads=2, head_dim=8),
    DifferentialAttention(n_heads=4, n_kv_heads=2, head_dim=8, window=-1),
    DifferentialAttention(n_heads=4, n_kv_heads=2, head_dim=8,
                          kv_from="a", share_kv=True),
    Mamba1Mixer(chunk=0), GatedMemoryUnit()],
    ids=["odd_heads", "window", "reads_and_shares", "chunk", "memory_size"])
def test_what_a_layer_refuses(bad):
    with pytest.raises(ValueError):
        bad.output_type(InputType.recurrent(32, T))


# ------------------------------------------------------------ the wiring
@pytest.fixture(scope="module")
def tiny():
    """The cut's graph at the tiny widths, and a batch."""
    zoo = Phi4Flash(TINY, layer_indices=CUT, sequence_length=T,
                    attention_block=8, loss_block=16, scan_chunk=8)
    net = ComputationGraph(zoo.conf()).init(seed=5)
    ids = np.random.default_rng(1).integers(0, 29, (2, T + 1)).astype(
        np.int32)
    return zoo, net, ids[:, :-1], ids[:, 1:]


def _grads(net, x, y):
    def loss(params):
        return net._loss_fn(params, net.state, [jnp.asarray(x)],
                            [jnp.asarray(y)], None, None, None)[0]
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(loss))(net.params)


def _flat(net):
    return {f"{v}/{k}": a for v, leaves in net.params.items()
            for k, a in leaves.items()}


@pytest.fixture(scope="module")
def reader_gradients(tiny, ref):
    """The program's gradient whole, with one reader's cotangent cut (the
    memory unit's, the cross-attention's), and the reference's."""
    zoo, net, x, y = tiny
    cfg = {**TINY, "num_hidden_layers": 6, "layer_indices": CUT,
           "published": {"num_hidden_layers": 32, "vocab_size": 29},
           "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
           "mamba_dt_rank": 2, "attention_bias": True,
           "mamba_proj_bias": False, "mlp_order": "gate|up",
           "head_pairing": "adjacent", "lambda_init": "0.8-0.6exp(-0.3i)"}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda p: ref.loss(
            cfg, p, jnp.asarray(x), jnp.asarray(y))))(_flat(net))
    out = {"whole": _grads(net, x, y), "reference": want}
    for cut, owner, key in (("memory", GatedMemoryUnit, "memory"),
                            ("kv", DifferentialAttention, "kv")):
        sound = owner.apply

        def deaf(self, params, state, x_, _sound=sound, _key=key, **kw):
            if kw.get(_key) is not None:
                kw[_key] = jax.lax.stop_gradient(kw[_key])
            return _sound(self, params, state, x_, **kw)

        owner.apply = deaf
        try:
            net._jitted = {}
            out[cut] = _grads(net, x, y)
        finally:
            owner.apply = sound
    return out


MADE_BY = {"memory": ("l16_ssm", ["Win", "conv", "conv_b", "Wx", "Wdt",
                                  "dt_bias", "A_log", "D"]),
           "kv": ("l17_attn", ["Wqkv", "bqkv"])}


@pytest.mark.parametrize("value,leaf", [(v, leaf) for v, (_, leaves)
                                        in MADE_BY.items()
                                        for leaf in leaves])
def test_a_maker_s_gradient_is_the_sum_over_its_readers(reader_gradients,
                                                        value, leaf):
    """Layer 16's scan leaves are read through its own gate and through
    layer 18's memory unit, layer 17's ``W_qkv`` through its own attention
    and through layer 19's: the whole gradient is the reference's (one
    function of every leaf), and with one reader's cotangent cut it is
    not."""
    vertex, _ = MADE_BY[value]
    whole = np.asarray(reader_gradients["whole"][vertex][leaf])
    want = np.asarray(reader_gradients["reference"][f"{vertex}/{leaf}"])
    cut = np.asarray(reader_gradients[value][vertex][leaf])
    scale = np.max(np.abs(want))
    assert np.max(np.abs(whole - want)) < 3e-4 * scale
    assert np.max(np.abs(cut - want)) > 1e-2 * scale
    if value == "kv":
        # the memory's cotangent goes to layer 16, below this layer: cutting
        # it leaves layer 17's gradient as it was
        spared = np.asarray(reader_gradients["memory"][vertex][leaf])
        assert np.max(np.abs(spared - want)) < 3e-4 * scale


def test_a_value_s_readers_do_not_reach_its_maker_s_output_stage(
        reader_gradients):
    """``W_out`` of layer 16 and ``W_o`` of layer 17 lie AFTER the values
    they hand on: no reader's cotangent reaches them."""
    for cut, vertex, leaf in (("memory", "l16_ssm", "Wout"),
                              ("kv", "l17_attn", "Wo")):
        np.testing.assert_allclose(
            np.asarray(reader_gradients[cut][vertex][leaf]),
            np.asarray(reader_gradients["whole"][vertex][leaf]),
            rtol=1e-5, atol=1e-9)


def test_the_builder_s_graph_survives_its_dictionary(tiny):
    zoo, net, x, y = tiny
    conf = zoo.conf()
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again == conf and again.to_dict() == conf.to_dict()
    assert conf.vertices["l18_gmu"][1] == ("l18_ln1", "l16_ssm.scan")
    assert conf.vertices["l19_attn"][1] == ("l19_ln1", "l17_attn.kv")
    assert conf.producer_of("l16_ssm.scan") == "l16_ssm"
    assert conf.producer_of("l17_attn.kv") == "l17_attn"
    assert conf.producer_of("l17_attn") == "l17_attn"
    order = conf.topological_order()
    assert order.index("l16_ssm") < order.index("l18_gmu")
    assert order.index("l17_attn") < order.index("l19_attn")
    types = conf.vertex_input_types()
    assert [t.size for t in types["l18_gmu"]] == [32, 64]
    assert [t.size for t in types["l19_attn"]] == [32, 32]
    twin = ComputationGraph(again).init(seed=5)
    np.testing.assert_array_equal(
        np.asarray(twin.output(x)[0]), np.asarray(net.output(x)[0]))


def test_validation_knows_the_wiring(tiny):
    zoo = tiny[0]
    conf = zoo.conf()
    assert conf.validate() == []
    assert conf.validate(eval_shape_check=True) == []

    def rewired(name, *inputs):
        vertices = dict(conf.vertices)
        vertices[name] = (vertices[name][0], tuple(inputs))
        return dataclasses.replace(conf, vertices=vertices)

    def codes(c):
        return {i.rule for i in c.validate(raise_on_error=False)}

    # a value no layer hands on, a reader short of an input, the wrong
    # layer's value, a value of the wrong width
    assert "unknown-value" in codes(rewired("l18_gmu", "l18_ln1",
                                            "l14_ssm.scan"))
    assert "unknown-input" in codes(rewired("l18_gmu", "l18_ln1",
                                            "nowhere.scan"))
    assert "layer-inputs" in codes(rewired("l18_gmu", "l18_ln1"))
    assert "layer-inputs" in codes(rewired("l19_attn", "l19_ln1"))
    assert "extra-input" in codes(rewired("l19_attn", "l19_ln1",
                                          "l16_ssm.scan"))
    assert "extra-input" in codes(rewired("l18_gmu", "l18_ln1",
                                          "l17_attn.kv"))


def test_the_memory_report_counts_a_shared_value_once(tiny):
    zoo = tiny[0]
    report = zoo.conf().memory_report(minibatch=1)
    by_name = {r.name: r for r in report.layers}
    out = T * 32 * 4
    assert by_name["l14_ssm"].activation_bytes_per_example == out
    assert by_name["l16_ssm"].activation_bytes_per_example == out + T * 64 * 4
    assert by_name["l17_attn"].activation_bytes_per_example == out + out
    # the readers hold their own output alone
    assert by_name["l18_gmu"].activation_bytes_per_example == out
    assert by_name["l19_attn"].activation_bytes_per_example == out


def test_a_value_handed_on_under_rematerialisation_and_a_mask(tiny):
    """``apply_layer`` through ``jax.checkpoint`` returns the value beside
    the output, and a features mask zeroes the output at masked steps."""
    layer = Mamba1Mixer(chunk=8, share_scan=True, remat="full")
    p, x = _draw(layer), _x()
    plain = dataclasses.replace(layer, remat=None)
    (out, values), _ = apply_layer(layer, p, {}, x, train=True, rng=None,
                                   mask=None, name="l16_ssm")
    (want, want_values), _ = plain.apply(p, {}, x)
    _close(out, want)
    _close(values["scan"], want_values["scan"])
    mask = jnp.arange(T)[None, :] < jnp.array([[T], [T - 5]])
    (masked, _), _ = plain.apply(p, {}, x, mask=mask)
    assert float(jnp.max(jnp.abs(masked[1, T - 5:]))) == 0.0
    _close(masked[0], want[0])


def test_a_second_input_reaches_a_layer_by_its_keyword():
    """The one mechanism by hand: a two-layer graph whose second layer
    reads the first's value."""
    g = GraphBuilder()
    g.add_inputs("ids")
    g.add_layer("embed", EmbeddingSequenceLayer(n_in=11, n_out=16), "ids")
    g.add_layer("ssm", Mamba1Mixer(chunk=4, share_scan=True), "embed")
    g.add_layer("gmu", GatedMemoryUnit(memory_size=32), "ssm", "ssm.scan")
    g.add_layer("head", TokenOutputLayer(n_out=11), "gmu")
    g.set_outputs("head")
    g.set_input_types(InputType.recurrent(11, 6))
    net = ComputationGraph(g.build()).init(seed=1)
    ids = np.arange(12).reshape(2, 6) % 11
    assert net.output(ids)[0].shape == (2, 6, 11)
    grads = _grads(net, ids, (ids + 1) % 11)
    assert float(jnp.max(jnp.abs(grads["ssm"]["A_log"]))) > 0


# ----------------------------------------------------------- the builder
def test_the_layer_kinds_are_derived_from_the_public_keys():
    kinds = derive_layer_types(PUBLIC)
    assert kinds[:16] == ["mamba", "swa"] * 8
    assert kinds[16:18] == ["mamba_memory", "full_shared"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    zoo = Phi4Flash(TINY, layer_indices=CUT)
    conf = zoo.conf()
    kinds = {n: type(obj).__name__ for n, (obj, _) in conf.vertices.items()}
    assert [kinds[v] for v in ("l14_ssm", "l15_attn", "l16_ssm", "l17_attn",
                               "l18_gmu", "l19_attn")] == [
        "Mamba1Mixer", "DifferentialAttention", "Mamba1Mixer",
        "DifferentialAttention", "GatedMemoryUnit", "DifferentialAttention"]
    attn = {i: conf.vertices[f"l{i}_attn"][0] for i in (15, 17, 19)}
    assert [a.window for a in attn.values()] == [8, 0, 0]
    assert [a.layer_index for a in attn.values()] == [15, 17, 19]
    assert [a.share_kv for a in attn.values()] == [False, True, False]
    assert attn[19].kv_from == "l17_attn"
    assert conf.vertices["l16_ssm"][0].share_scan
    assert not conf.vertices["l14_ssm"][0].share_scan
    assert conf.vertices["head"][0].tied_to == "embed"


@pytest.mark.parametrize("change,indices,error", [
    ({"mb_per_layer": 3}, None, NotImplementedError),
    ({"sliding_window": 0}, None, NotImplementedError),
    ({"mlp_bias": True}, None, NotImplementedError),
    ({"tie_word_embeddings": False}, None, NotImplementedError),
    ({}, [18, 19], ValueError),                  # two readers, no maker
    ({"layer_types": ["conv"] * 32}, None, NotImplementedError),
    ({"layer_types": ["mamba"]}, None, ValueError)],
    ids=["mb_per_layer", "no_window", "mlp_bias", "untied", "no_maker",
         "unknown_kind", "wrong_count"])
def test_what_the_builder_raises_on(change, indices, error):
    with pytest.raises(error):
        Phi4Flash({**TINY, **change}, layer_indices=indices).conf()


def _count(zoo) -> int:
    net = ComputationGraph(zoo.conf())
    drawn = jax.eval_shape(lambda k: net._draw(k)[0], jax.random.key(0))
    return sum(math.prod(a.shape) for leaves in drawn.values()
               for a in leaves.values())


@pytest.mark.parametrize("what,want", [("whole", 3_852_562_944),
                                       ("cut", 697_094_272)])
def test_the_hand_counts(what, want):
    """The whole model from its public keys: 8 (Mamba + window) pairs, one
    (Mamba + full) pair, 7 (memory unit + cross-attention) pairs and the
    tied table of 200,064 x 2,560: the card's "3.8B". The cut: published
    layers 14-19 over 25,008 rows. No array is made (``eval_shape``)."""
    if what == "whole":
        assert _count(Phi4Flash(PUBLIC)) == want
        assert want == (9 * 119_895_040 + 9 * 98_322_304 + 7 * 104_867_840
                        + 7 * 91_766_144 + 200_064 * 2560 + 2 * 2560)
    else:
        assert _count(Phi4Flash(PUBLIC, layer_indices=CUT,
                                vocab_rows=25_008)) == want

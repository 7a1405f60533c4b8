"""``GatedShortConv`` (the LFM2 line's token mixer) and a head tied to the
embedding (``TokenOutputLayer.tied_to``): the layer alone against the
benchmark reference's lines (forward and every gradient leaf), causality
and the zero history of the first steps, a right-padded batch; the tied
leaf's two gradient terms, its one optimizer state, a save / load round
trip, the parameter count; the three zoo builders that used to raise on
``tie_word_embeddings``; and the owners of a step that holds both."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex, GraphBuilder
from deeplearning4j_tpu.nn.conf.layers import layer_from_dict, layer_to_dict
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.nn.conf.short_conv import (GatedShortConv,
                                                   causal_depthwise_conv)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.optimize.updaters import Adam

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference of the LFM2 configuration: its
    ``short_conv`` is the layer's equations in float32."""
    spec = importlib.util.spec_from_file_location(
        "lfm2_reference_for_layer_tests", os.path.join(
            ROOT, "benchmark", "references", "lfm2_8b_a1b_ep4.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _layer_and_leaves(d=12, taps=3, seed=0):
    layer = GatedShortConv(taps=taps)
    params, _ = layer.init(jax.random.key(seed), InputType.recurrent(d, 20))
    return layer, params


# ------------------------------------------------------------ the new layer
def test_the_one_copy_of_the_taps_serves_both_users():
    from deeplearning4j_tpu.nn.conf import linear_attention, short_conv
    assert linear_attention.causal_depthwise_conv is \
        short_conv.causal_depthwise_conv


@pytest.mark.parametrize("taps", [3, 4, 1])
def test_forward_and_every_gradient_leaf_follow_the_reference(ref, taps):
    d = 12
    layer, params = _layer_and_leaves(d, taps)
    assert {k: v.shape for k, v in params.items()} == {
        "Win": (d, 3 * d), "w": (taps, d), "Wout": (d, d)}
    x = jax.random.normal(jax.random.key(1), (2, 20, d))
    m = {"d": d, "taps": taps}

    def program(p, x):
        return layer.apply(p, {}, x)[0]

    def reference(p, x):
        return ref.short_conv(m, {"c/" + k: v for k, v in p.items()}, "c/",
                              x, "highest")

    def run(fn):
        def loss(p, x):
            out = fn(p, x)
            return jnp.sum(jnp.sin(out)), out
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
            params, x)

    with jax.default_matmul_precision("highest"):
        got, want = run(program), run(reference)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * max(
            1.0, float(jnp.max(jnp.abs(b))))
    (_, _), (dp, dx) = got
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in (*dp.values(), dx))


def test_the_equations_by_hand_and_the_zero_history_of_the_first_steps():
    d = 6
    layer, p = _layer_and_leaves(d)
    x = jax.random.normal(jax.random.key(2), (1, 5, d))
    with jax.default_matmul_precision("highest"):
        out = np.asarray(layer.apply(p, {}, x)[0])[0]
        bcu = np.asarray(x[0] @ p["Win"])
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    z, w = b * u, np.asarray(p["w"])
    for t in range(5):
        mixed = sum(w[j] * z[t - 2 + j] for j in range(3) if t - 2 + j >= 0)
        np.testing.assert_allclose(
            out[t], (c[t] * mixed) @ np.asarray(p["Wout"]), rtol=2e-5,
            atol=1e-6)
    # step 0 reads one tap, step 1 two: nothing stands before the start
    np.testing.assert_allclose(
        out[0], (c[0] * w[2] * z[0]) @ np.asarray(p["Wout"]), rtol=2e-5,
        atol=1e-6)


def test_an_input_changed_at_step_t_moves_no_output_before_t():
    layer, p = _layer_and_leaves()
    x = jax.random.normal(jax.random.key(3), (2, 20, 12))
    out = layer.apply(p, {}, x)[0]
    bumped = layer.apply(p, {}, x.at[0, 8].add(1.0))[0]
    moved = np.asarray(jnp.max(jnp.abs(bumped - out), -1))
    assert np.all(moved[0, :8] == 0) and np.all(moved[1] == 0)
    assert np.all(moved[0, 8:11] > 0)          # three taps: steps 8, 9, 10
    assert np.all(moved[0, 11:] == 0)


def test_a_right_padded_batch_is_exact_and_masked_steps_are_zero():
    layer, p = _layer_and_leaves()
    x = jax.random.normal(jax.random.key(4), (2, 20, 12))
    mask = jnp.asarray(np.arange(20)[None, :] < np.array([[20], [13]]),
                       jnp.float32)
    padded = layer.apply(p, {}, x, mask=mask)[0]
    alone = layer.apply(p, {}, x[1:, :13])[0]
    assert float(jnp.max(jnp.abs(padded[1, :13] - alone[0]))) < 1e-6
    assert float(jnp.max(jnp.abs(padded[1, 13:]))) == 0.0
    assert float(jnp.max(jnp.abs(padded[0] - layer.apply(p, {}, x)[0][0]))) \
        == 0.0


def test_the_layer_is_registered_serialises_and_validates():
    layer = GatedShortConv(taps=4, remat="full")
    assert layer_from_dict(layer_to_dict(layer)) == layer
    it = InputType.recurrent(12, 7)
    assert layer.output_type(it) == it
    with pytest.raises(ValueError):
        GatedShortConv(taps=0).output_type(it)


# ------------------------------------------------------------ the tied head
def _graph(tied=True, d=16, vocab=23, t=24, block=8):
    g = GraphBuilder()
    g.add_inputs("ids")
    g.add_layer("embed", EmbeddingSequenceLayer(n_in=vocab, n_out=d), "ids")
    g.add_layer("n1", RMSNorm(), "embed")
    g.add_layer("mix", GatedShortConv(remat="full"), "n1")
    g.add_vertex("add", ElementWiseVertex("add"), "embed", "mix")
    g.add_layer("final_norm", RMSNorm(), "add")
    g.add_layer("head", TokenOutputLayer(
        n_out=vocab, time_block=block, tied_to="embed" if tied else ""),
        "final_norm")
    g.set_outputs("head")
    g.set_input_types(InputType.recurrent(vocab, t))
    return dataclasses.replace(g.build(), updater=Adam(1e-2))


def _batch(vocab=23, t=24, seed=0):
    ids = np.random.default_rng(seed).integers(0, vocab, (2, t + 1)).astype(
        np.int32)
    return ids[:, :-1], ids[:, 1:]


def test_a_tied_head_owns_no_matrix_and_the_leaf_is_counted_once():
    tied, untied = (ComputationGraph(_graph(flag)).init()
                    for flag in (True, False))
    assert tied.params["head"] == {} and set(untied.params["head"]) == {"W"}
    assert untied.num_params() - tied.num_params() == 16 * 23
    assert tied.num_params() == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(tied.params))
    # the analytic report (no network) counts the table once too
    by_name = {r.name: r.num_params
               for r in _graph(True).memory_report(minibatch=2).layers}
    assert (by_name["embed"], by_name["head"]) == (16 * 23, 0)
    assert sum(by_name.values()) == tied.num_params()
    # Adam's state: moments for the one leaf under ``embed``, none for the head
    assert jax.tree.leaves(tied.opt_state["head"]) == [] or all(
        a.ndim == 0 for a in jax.tree.leaves(tied.opt_state["head"]))
    moments = [a for a in jax.tree.leaves(tied.opt_state["embed"])
               if a.ndim == 2]
    assert [a.shape for a in moments] == [(23, 16)] * 2
    assert tied.compile_watch.counter("head.tied") == 0     # nothing traced
    x, y = _batch()
    out = tied.output(x)[0]
    assert out.shape == (2, 24, 23)
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-5)
    assert tied.compile_watch.counter("head.tied") == 1


def test_the_embedding_s_gradient_is_the_sum_of_the_gather_s_and_the_head_s(
        monkeypatch):
    net = ComputationGraph(_graph()).init()
    x, y = _batch()
    table = net.params["embed"]["W"]
    sound_tied = TokenOutputLayer.tied_params
    sound_gather = EmbeddingSequenceLayer.apply

    def grad_of_table(head_reads, gather_reads):
        """d loss / d table with each use reading the table as given
        (``jax.lax.stop_gradient`` cuts a use's term)."""
        def tied_params(self, params, other):
            return sound_tied(self, params, {"W": head_reads(other["W"])})

        def gather(self, params, state, x, **kw):
            return sound_gather(self, {"W": gather_reads(params["W"])},
                                state, x, **kw)

        def loss(table):
            params = {**net.params, "embed": {"W": table}}
            return net._loss_fn(params, net.state, [jnp.asarray(x)],
                                [jnp.asarray(y)], None, None, None)[0]

        monkeypatch.setattr(TokenOutputLayer, "tied_params", tied_params)
        monkeypatch.setattr(EmbeddingSequenceLayer, "apply", gather)
        return jax.jit(jax.grad(loss))(table)

    same, cut = (lambda w: w), jax.lax.stop_gradient
    with jax.default_matmul_precision("highest"):
        both = grad_of_table(same, same)
        head_only = grad_of_table(same, cut)
        gather_only = grad_of_table(cut, same)
        neither = grad_of_table(cut, cut)
    assert float(jnp.max(jnp.abs(neither))) == 0.0
    for part in (head_only, gather_only):
        assert float(jnp.max(jnp.abs(part))) > 1e-4
    assert float(jnp.max(jnp.abs(both - (head_only + gather_only)))) \
        < 1e-6 * float(jnp.max(jnp.abs(both)))
    # the gather's term touches only the rows that were looked up
    rows = np.zeros(23, bool)
    rows[np.unique(x)] = True
    assert np.all(np.asarray(jnp.max(jnp.abs(gather_only), -1))[~rows] == 0)


def test_a_tied_graph_takes_the_untied_graph_s_steps_from_the_same_matrix():
    """One Adam state on the one leaf: where the untied graph's head starts
    at the embedding transposed, both see the same loss, and the tied
    leaf's first gradient is the sum of the untied graph's two."""
    tied = ComputationGraph(_graph(True)).init()
    untied = ComputationGraph(_graph(False)).init()
    shared = {k: v for k, v in tied.params.items() if k != "head"}
    untied.params = {**jax.tree.map(jnp.copy, shared),
                     "head": {"W": jnp.copy(tied.params["embed"]["W"].T)}}
    x, y = _batch()

    def grads(net):
        def loss(params):
            return net._loss_fn(params, net.state, [jnp.asarray(x)],
                                [jnp.asarray(y)], None, None, None)[0]
        return jax.jit(jax.value_and_grad(loss))(net.params)

    with jax.default_matmul_precision("highest"):
        (lt, gt), (lu, gu) = grads(tied), grads(untied)
    assert abs(float(lt) - float(lu)) < 1e-6 * abs(float(lu))
    want = gu["embed"]["W"] + gu["head"]["W"].T
    assert float(jnp.max(jnp.abs(gt["embed"]["W"] - want))) < 1e-6 * float(
        jnp.max(jnp.abs(want)))
    for name in ("mix", "n1", "final_norm"):
        for k in gt[name]:
            assert float(jnp.max(jnp.abs(gt[name][k] - gu[name][k]))) < 1e-6


def test_a_tied_graph_learns_saves_and_loads(tmp_path):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.utils import serialization

    net = ComputationGraph(_graph()).init()
    x, y = _batch()
    first = net.score_dataset(DataSet(x, y))
    for _ in range(30):
        net.fit(DataSet(x, y))
    assert net.score_dataset(DataSet(x, y)) < 0.7 * first
    path = str(tmp_path / "tied.zip")
    serialization.write_model(net, path)
    again = serialization.restore_computation_graph(path)
    assert again.params["head"] == {}
    assert again.vertices["head"][0].tied_to == "embed"
    np.testing.assert_array_equal(np.asarray(again.params["embed"]["W"]),
                                  np.asarray(net.params["embed"]["W"]))
    np.testing.assert_allclose(again.output(x)[0], net.output(x)[0],
                               rtol=1e-6, atol=1e-7)
    # and goes on training: the restored optimizer state fits the one leaf
    again.fit(DataSet(x, y))
    assert again.num_params() == net.num_params()


@pytest.mark.parametrize("case", ["unknown_vertex", "wrong_shape", "stack"])
def test_validation_names_a_head_tied_to_nothing_it_can_read(case):
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.analysis.validation import ConfigValidationError

    if case == "stack":
        conf = (NeuralNetConfiguration.builder().list()
                .layer(EmbeddingSequenceLayer(n_in=9, n_out=8))
                .layer(TokenOutputLayer(n_out=9, tied_to="embed"))
                .set_input_type(InputType.recurrent(9, 6)).build())
    else:
        conf = _graph()
        head, inputs = conf.vertices["head"]
        head = dataclasses.replace(
            head, **({"tied_to": "nowhere"} if case == "unknown_vertex"
                     else {"n_out": 24}))
        conf = dataclasses.replace(
            conf, vertices={**conf.vertices, "head": (head, inputs)})
    with pytest.raises(ConfigValidationError) as raised:
        conf.validate()
    assert any(i.rule == "tied-head" for i in raised.value.issues)


# ------------------------------------------- the builders that used to raise
def _tiny(name):
    layer_types = ["sliding_attention", "full_attention"]
    if name == "Lfm2Moe":
        return dict(
            hidden_size=16, num_attention_heads=2, num_key_value_heads=1,
            conv_L_cache=3, conv_bias=False, intermediate_size=32,
            moe_intermediate_size=8, num_experts=4, num_experts_per_tok=2,
            num_dense_layers=1, num_hidden_layers=3, norm_eps=1e-5,
            layer_types=["conv", "full_attention", "conv"],
            norm_topk_prob=True, use_expert_bias=True, rope_theta=1e6,
            routed_scaling_factor=1, vocab_size=19)
    if name == "Mellum2":
        return dict(
            hidden_size=16, num_attention_heads=2, num_key_value_heads=1,
            head_dim=8, intermediate_size=32, moe_intermediate_size=8,
            num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
            num_hidden_layers=2, layer_types=layer_types,
            mlp_layer_types=["sparse", "dense"], rms_norm_eps=1e-6,
            sliding_window=4, use_sliding_window=True, vocab_size=19,
            rope_parameters={k: {"rope_type": "default", "rope_theta": 1e4}
                             for k in layer_types})
    if name == "Ouro":
        return dict(hidden_size=16, num_attention_heads=2,
                    num_key_value_heads=2, head_dim=8, intermediate_size=32,
                    num_hidden_layers=1, total_ut_steps=2, rms_norm_eps=1e-6,
                    rope_theta=1e4, vocab_size=19)
    return dict(hidden_size=16, num_attention_heads=2, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=8,
                q_lora_rank=8, rope_theta=1e4, rope_interleave=True,
                rms_norm_eps=1e-6, intermediate_size=32,
                moe_intermediate_size=8, n_routed_experts=4,
                n_shared_experts=1, num_experts_per_tok=2,
                routed_scaling_factor=1.0, scoring_func="sigmoid",
                topk_method="noaux_tc", norm_topk_prob=True, n_group=1,
                topk_group=1, first_k_dense_replace=1, moe_layer_freq=1,
                num_hidden_layers=2, num_nextn_predict_layers=1,
                vocab_size=19)


@pytest.mark.parametrize("name", ["Mellum2", "Ouro", "JoyAIFlash", "Lfm2Moe"])
def test_a_builder_ties_the_head_where_the_config_says_so(name):
    """``tie_word_embeddings`` reaches ``tied_to``: the head draws no
    matrix, the network holds one (vocabulary, width) leaf, and a step of
    ``fit`` moves it."""
    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.datasets.dataset import DataSet

    config = _tiny(name)
    build = getattr(models, name)
    kw = dict(sequence_length=12, attention_block=4, loss_block=4)
    untied = ComputationGraph(build(
        {**config, "tie_word_embeddings": False}, **kw).conf()).init()
    tied = ComputationGraph(build(
        {**config, "tie_word_embeddings": True}, **kw).conf()).init()
    assert tied.vertices["head"][0].tied_to == "embed"
    assert untied.vertices["head"][0].tied_to == ""
    assert "W" not in tied.params["head"] and "W" in untied.params["head"]
    assert untied.num_params() - tied.num_params() == 16 * 19
    tables = [a for a in jax.tree.leaves(tied.params) if a.shape == (19, 16)]
    assert len(tables) == 1
    before = np.asarray(tables[0])
    x, y = _batch(19, 12)
    tied.fit(DataSet(x, y))
    assert np.isfinite(tied.score())
    assert np.max(np.abs(np.asarray(tied.params["embed"]["W"]) - before)) > 0
    assert tied.compile_watch.counter("head.tied") == 1


def test_lfm2_raises_on_what_it_does_not_build():
    from deeplearning4j_tpu.models import Lfm2Moe

    good = _tiny("Lfm2Moe")
    Lfm2Moe(good).conf().validate()
    for key, value in [("conv_bias", True), ("norm_topk_prob", False),
                       ("use_expert_bias", False),
                       ("layer_types", ["conv", "sliding_attention", "conv"])]:
        with pytest.raises(NotImplementedError):
            Lfm2Moe({**good, key: value})


# ------------------------------------------------------------------ owners
def test_a_step_with_both_has_an_owner_for_all_it_emitted(step_op_names):
    """None of the short convolution's operations lies outside
    ``GatedShortConv:<name>``; its three scopes are there forward and
    backward; the transposed table is the head's."""
    from deeplearning4j_tpu.obs.owners import owner_of
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL

    net = ComputationGraph(_graph()).init()
    x = jax.ShapeDtypeStruct((2, 24), jnp.int32)
    # a ``lower`` outside a watched call counts on the process's watch
    before = {k: GLOBAL.counter(k) for k in ("conv.gated_short", "head.tied")}
    names = step_op_names(net, [x], [x])
    assert GLOBAL.counter("conv.gated_short") > before["conv.gated_short"]
    assert GLOBAL.counter("head.tied") == before["head.tied"] + 1
    assert [n for n in names if owner_of(n) is None] == []
    owners = {owner_of(n) for n in names}
    assert {"GatedShortConv", "TokenOutputLayer", "EmbeddingSequenceLayer",
            "loss", "optim"} <= owners
    for scope in ("sconv.in_proj", "sconv.gate_conv", "sconv.out_proj"):
        mine = [n for n in names if scope in n]
        assert mine and all("GatedShortConv:mix" in n for n in mine), scope
        assert any("transpose(" in n for n in mine), scope
        assert any("transpose(" not in n for n in mine), scope

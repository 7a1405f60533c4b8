"""What a rematerialised layer keeps (``Layer.remat_keeps``, joined to the
layer's ``jax.checkpoint`` policy by ``apply_layer``): the delta-rule
layers keep their scan's output and chunk states, so ``kda_scan_fwd`` runs
once a layer and step; every other layer type names nothing and gets the
policy it always got. CPU, the kernels forced and interpreted. Tail-sorted
(``test_zz_``): interpret mode is slow."""

import collections
import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import linear_attention as la
from deeplearning4j_tpu.nn.conf.attention import (
    GatedAttention, MultiHeadLatentAttention, RotaryAttention)
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, apply_layer
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.perf import compile_watch, fusion
from deeplearning4j_tpu.perf import pallas as pk
from deeplearning4j_tpu.perf.pallas import kda as kda_kernels

_T, _WIDTH = 128, 32
_LAYERS = {
    "kda": la.KimiDeltaAttention(n_heads=2, head_dim=128, low_rank=8),
    # a q/k head serves two value heads
    "gdn": la.GatedDeltaNet(n_key_heads=1, n_value_heads=2, head_dim=128),
}
_SCOPES = {"kda": ("KimiDeltaAttention", "kda.scan"),
           "gdn": ("GatedDeltaNet", "gdn.scan")}


def _loss_of(layer):
    it = InputType.recurrent(_WIDTH, _T)
    params, state = layer.init(jax.random.key(0), it)
    x = jax.random.normal(jax.random.key(1), (1, _T, _WIDTH))

    def loss(p, xx):
        out, _ = apply_layer(layer, p, state, xx, train=True, rng=None,
                             mask=None, name="mix")
        return jnp.sum(jnp.sin(out))

    return loss, params, x


def _kernels(jaxpr, found=None):
    """Pallas calls by kernel name, through every nested jaxpr."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _kernels(inner, found)
    return found


def _lowered_gradient(f, params, x) -> str:
    """The lowered text of ``jax.grad(f)``, whatever ``f`` is called."""
    text = jax.jit(jax.grad(f)).lower(params, x).as_text()
    return re.sub(r"^module @jit_\w+", "module @jit_f", text)


def _as_before(layer, state, marker):
    """``apply_layer`` under ``remat="full"`` as it was before a layer type
    could name what it keeps: ``jax.checkpoint(policy=None)`` in the
    layer's scope."""
    def apply(p, xx):
        with jax.named_scope(marker):
            out, _ = jax.checkpoint(
                lambda p_, s_, x_, k_, m_, e_: layer.apply(
                    p_, s_, x_, train=True, rng=k_, mask=m_, **e_),
                policy=None)(p, state, xx, None, None, {})
        return out
    return apply


def _kept_counter():
    return compile_watch.GLOBAL.counters().get("remat.kept_values", 0)


@pytest.mark.parametrize("remat,scans,inputs,counted", [
    ("full", 1, 2, 1), ("dots_saveable", 1, 2, 1),
    ("nothing_saveable", 2, 2, 0), (None, 1, 1, 0)])
@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_a_rematerialised_delta_rule_layer_runs_its_scan_once(
        kind, remat, scans, inputs, counted):
    """``jax.grad`` through ``apply_layer``: under ``"full"`` the backward
    pass recomputes the projections and the input kernel (two
    ``kda_inputs_fwd``) and reads the scan's kept results (ONE
    ``kda_scan_fwd``, where the parent ran two); ``"nothing_saveable"``
    keeps nothing and runs it twice; without ``remat`` nothing is
    recomputed. ``remat.kept_values`` counts a layer application whose
    policy holds names."""
    loss, params, x = _loss_of(dataclasses.replace(_LAYERS[kind],
                                                   remat=remat))
    before = _kept_counter()
    with pk.override(enabled=True, interpret=True):
        found = _kernels(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr)
    assert found == {"kda_scan_fwd": scans, "kda_scan_bwd": 1,
                     "kda_inputs_fwd": inputs, "kda_inputs_bwd": 1}
    assert _kept_counter() - before == counted


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_kept_results_change_no_bit_of_the_gradient(kind):
    """What is kept is what would be recomputed, in float32 as it was
    made: the gradients under ``"full"`` are those under
    ``"nothing_saveable"`` bit for bit."""
    grads = {}
    for remat in ("full", "nothing_saveable"):
        loss, params, x = _loss_of(dataclasses.replace(_LAYERS[kind],
                                                       remat=remat))
        with pk.override(enabled=True, interpret=True):
            grads[remat] = jax.grad(loss, argnums=(0, 1))(params, x)
    kept, recomputed = (jax.tree.leaves(grads[r])
                        for r in ("full", "nothing_saveable"))
    assert len(kept) == len(recomputed) > 5
    for a, b in zip(kept, recomputed):
        assert float(jnp.max(jnp.abs(a))) > 0
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_jax_numpy_scan_names_nothing():
    """Off the kernels the scan checkpoints its own groups of chunks and
    names nothing: the layer's lowered gradient is what ``policy=None``
    lowers to."""
    layer = la.KimiDeltaAttention(n_heads=2, head_dim=8, low_rank=4,
                                  chunk=16, remat="full")
    loss, params, x = _loss_of(layer)
    before = _as_before(layer, {}, "KimiDeltaAttention:mix")

    def plain(p, xx):
        return jnp.sum(jnp.sin(before(p, xx)))

    with pk.override(enabled=False):
        texts = [_lowered_gradient(f, params, x) for f in (loss, plain)]
    assert "checkpoint_name" not in texts[0]
    assert texts[0] == texts[1]


_NAMELESS = [
    GatedFeedForward(ff_size=24), RMSNorm(), DenseLayer(n_out=8),
    RotaryAttention(n_heads=2, head_dim=8, block=8),
    MultiHeadLatentAttention(n_heads=2, nope_dim=8, rope_dim=0, v_dim=8,
                             kv_rank=8, block=8),
    GatedAttention(n_heads=4, n_kv_heads=2, head_dim=8, rotary_dim=4,
                   block=8),
]


@pytest.mark.parametrize("layer", _NAMELESS,
                         ids=[type(l).__name__ for l in _NAMELESS])
@pytest.mark.parametrize("remat", sorted(fusion.REMAT_POLICIES))
def test_a_layer_type_that_names_nothing_keeps_its_policy(layer, remat):
    """Adapting by layer type: a type without ``remat_keeps`` gets exactly
    the policy the name always meant, ``None`` for ``"full"``."""
    layer = dataclasses.replace(layer, remat=remat)
    assert fusion.kept_names(layer) == ()
    policy = fusion.remat_policy(remat, fusion.kept_names(layer))
    attr = fusion.REMAT_POLICIES[remat]
    assert policy is (None if attr is None
                      else getattr(jax.checkpoint_policies, attr))


def test_a_gated_feed_forward_under_full_lowers_to_the_parent_s_text():
    """The lowered gradient of a ``GatedFeedForward`` through
    ``apply_layer`` with ``remat="full"`` is the text of the plain
    ``jax.checkpoint(policy=None)`` form that ``apply_layer`` was before
    layers could name what they keep, and counts no kept value."""
    layer = GatedFeedForward(ff_size=24, remat="full")
    it = InputType.recurrent(12, 16)
    params, state = layer.init(jax.random.key(0), it)
    x = jax.random.normal(jax.random.key(1), (2, 16, 12))

    def now(p, xx):
        out, _ = apply_layer(layer, p, state, xx, train=True, rng=None,
                             mask=None, name="ffn")
        return jnp.sum(out * out)

    as_before = _as_before(layer, state, "GatedFeedForward:ffn")

    def parent(p, xx):
        out = as_before(p, xx)
        return jnp.sum(out * out)

    before = _kept_counter()
    digests = [hashlib.sha256(_lowered_gradient(f, params, x).encode())
               .hexdigest() for f in (now, parent)]
    assert digests[0] == digests[1]
    assert _kept_counter() == before


@pytest.mark.parametrize("name", sorted(fusion.REMAT_POLICIES))
def test_names_join_every_saving_policy_and_not_nothing_saveable(name):
    layer = dataclasses.replace(_LAYERS["kda"], remat=name)
    keeps = fusion.kept_names(layer)
    assert keeps == (() if name == "nothing_saveable" else kda_kernels.KEPT)
    policy = fusion.remat_policy(name, keeps)
    base = fusion.remat_policy(name)
    if name == "nothing_saveable":
        assert policy is base is jax.checkpoint_policies.nothing_saveable
    else:
        assert policy is not base and callable(policy)
        # one object a (name, keeps): layers share their lowered functions
        assert policy is fusion.remat_policy(name, keeps)
    assert fusion.kept_names(dataclasses.replace(layer, remat=None)) == ()


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_kept_results_lie_under_the_layers_scan_scope(kind, step_op_names):
    """``kda.device_ms_per_step`` / ``gdn.device_ms_per_step`` and the scan
    rooflines find operations by ``op_name``: the forward kernel that
    writes the kept o and states (here its interpreted body) lies in the
    compiled step under the layer's marker and ``kda.scan`` / ``gdn.scan``,
    in the first pass alone; the backward kernel reads them under the same
    scope."""
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    cls, scope = _SCOPES[kind]
    conf = (GraphBuilder(NeuralNetConfiguration.builder().seed(3)
                         .updater(Sgd(learning_rate=0.05)))
            .add_inputs("in")
            .add_layer("mix1", dataclasses.replace(_LAYERS[kind],
                                                   remat="full"), "in")
            .add_layer("out", RnnOutputLayer(n_out=3, loss="mcxent"), "mix1")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(12, 64)).build())
    before = _kept_counter()
    with pk.override(enabled=True, interpret=True):
        net = ComputationGraph(conf).init()
        names = step_op_names(
            net, [jax.ShapeDtypeStruct((1, 64, 12), jnp.float32)],
            [jax.ShapeDtypeStruct((1, 64, 3), jnp.float32)])
    assert _kept_counter() - before == 1
    for kernel, way, other in (("kda_scan_fwd", "jvp(", "transpose("),
                               ("kda_scan_bwd", "transpose(", None)):
        mine = [n for n in names if kernel in n]
        assert len(mine) > 20, (kernel, len(mine))
        for n in mine:
            assert f"{cls}:mix1" in n and scope in n and way in n, n
            # the forward kernel is not in the backward's recomputation
            assert other is None or (other not in n
                                     and "rematted_computation" not in n), n


def test_the_counter_reaches_the_scrape():
    from deeplearning4j_tpu.obs.exporters import prometheus_text
    from deeplearning4j_tpu.obs.registry import (MetricsRegistry,
                                                 absorb_compile_watch)
    watch = compile_watch.CompileWatch("m")
    watch.bump("remat.kept_values", 4)
    watch.bump("kernel.pallas_kda_scan", 4)
    registry = MetricsRegistry()
    absorb_compile_watch(registry, watch)
    text = prometheus_text(registry)
    assert "jit_remat_kept_values 4" in text
    assert "jit_kernel_pallas_kda_scan 4" in text


# ----------------------------------------------------------- memory report
def test_kept_bytes_at_the_kimi_cell_s_shape():
    """o (8192, 32, 128) and the states (32, 128, 128, 128), float32: 134
    + 268 MB a layer, 48 KB a token."""
    it = InputType.recurrent(2304, 8192)
    kimi = la.KimiDeltaAttention(n_heads=32, head_dim=128, low_rank=128,
                                 remat="full")
    qwen = la.GatedDeltaNet(n_key_heads=16, n_value_heads=32, head_dim=128,
                            remat="full")
    assert kda_kernels.kept_bytes(8192, 32, 128, 64) == 402_653_184
    assert kimi.remat_kept_bytes(it) == qwen.remat_kept_bytes(it) \
        == 134_217_728 + 268_435_456
    assert kimi.remat_kept_bytes(it) // 8192 == 48 * 1024
    # a length that is padded to whole chunks; a head the kernels refuse
    assert kda_kernels.kept_bytes(100, 2, 128, 64) \
        == kda_kernels.kept_bytes(128, 2, 128, 64)
    assert kda_kernels.kept_bytes(128, 2, 64, 64) == 0
    assert dataclasses.replace(kimi, chunk=32).remat_kept_bytes(it) == 0


@pytest.mark.parametrize("remat,keeps", [
    ("full", True), ("dots_saveable", True), ("nothing_saveable", False),
    (None, False)])
def test_the_memory_report_counts_what_a_layer_keeps(remat, keeps):
    from deeplearning4j_tpu.nn.memory import conf_memory_report
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Sgd(learning_rate=0.1)).list()
            .layer(dataclasses.replace(_LAYERS["kda"], remat=remat))
            .layer(GatedFeedForward(ff_size=24, remat=remat))
            .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.recurrent(_WIDTH, _T)).build())
    rep = conf_memory_report(conf, minibatch=3, training_bytes=False)
    want = kda_kernels.kept_bytes(_T, 2, 128, 64) if keeps else 0
    assert want == (4 * 2 * 128 * (_T + 2 * 128) if keeps else 0)
    assert [l.remat_kept_bytes_per_example for l in rep.layers] \
        == [want, 0, 0]
    outputs = sum(l.activation_bytes_per_example for l in rep.layers)
    assert rep.total_activation_bytes == 3 * (outputs + want)
    assert ("keeps 384.0 KB/ex" in rep.to_string()) == keeps


# -------------------------------------------------------------- validation
@pytest.mark.parametrize("remat,refused", [
    *[(name, False) for name in sorted(fusion.REMAT_POLICIES)],
    ("keep_the_scan", True), ("save_only_these_names", True)])
def test_validation_knows_five_names_and_no_new_one(remat, refused):
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    assert len(fusion.REMAT_POLICIES) == 5
    conf = (NeuralNetConfiguration.builder().seed(1).list()
            .layer(la.KimiDeltaAttention(n_heads=2, head_dim=8, low_rank=4,
                                         remat=remat))
            .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.recurrent(12, 16)).build())
    rules = [i.rule for i in conf.validate(raise_on_error=False)]
    assert ("unknown-remat" in rules) == refused

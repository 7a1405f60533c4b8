"""What a rematerialised layer keeps (``Layer.remat_keeps``, joined to the
layer's ``jax.checkpoint`` policy by ``apply_layer``): the delta-rule
layers keep their scan's output and chunk states, so ``kda_scan_fwd`` runs
once a layer and step, and their wide projections' outputs, so those
products do; the latent attention keeps its attention's output and
log-sum-exp, so ``mla_attend_fwd`` does, and q, k and v as the attention
reads them, so its up-projections, its rotation and its transposes do;
every other layer type names nothing and gets the policy it always got. CPU, the kernels forced and
interpreted. Tail-sorted (``test_zz_``): interpret mode is slow."""

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
    OPERANDS_KEPT, GatedAttention, MultiHeadLatentAttention, RotaryAttention)
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, apply_layer
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.perf import compile_watch, fusion
from deeplearning4j_tpu.perf import pallas as pk
from deeplearning4j_tpu.perf.pallas import attention as attention_kernels
from deeplearning4j_tpu.perf.pallas import kda as kda_kernels

_WIDTH = 32
_LAYERS = {
    "kda": la.KimiDeltaAttention(n_heads=2, head_dim=128, low_rank=8),
    # a q/k head serves two value heads
    "gdn": la.GatedDeltaNet(n_key_heads=1, n_value_heads=2, head_dim=128),
    # the latent attention at a shape the kernels take (two tiles of 128,
    # q/k and v heads of 64) with the cells' fields off (Kimi's layer) ...
    "mla": MultiHeadLatentAttention(n_heads=2, nope_dim=32, rope_dim=32,
                                    v_dim=64, kv_rank=16, block=128),
    # ... and on (JoyAI's: the low-rank query, the decoupled rotation)
    "rmla": MultiHeadLatentAttention(n_heads=2, nope_dim=32, rope_dim=32,
                                     v_dim=64, kv_rank=16, block=128,
                                     q_rank=8, rope_theta=1e4),
}
_DELTA_RULE, _LATENT = ["kda", "gdn"], ["mla", "rmla"]
_TIME = {"kda": 128, "gdn": 128, "mla": 256, "rmla": 256}
# kind -> (class, the scope of the kept results, the kernel that writes
# them, the kernel that reads them)
_SCOPES = {
    "kda": ("KimiDeltaAttention", "kda.scan", "kda_scan_fwd", "kda_scan_bwd"),
    "gdn": ("GatedDeltaNet", "gdn.scan", "kda_scan_fwd", "kda_scan_bwd"),
    "mla": ("MultiHeadLatentAttention", "mla.attend", "mla_attend_fwd",
            "mla_attend_bwd"),
    "rmla": ("MultiHeadLatentAttention", "mla.attend", "mla_attend_fwd",
             "mla_attend_bwd"),
}


def _loss_of(kind, **changes):
    layer = dataclasses.replace(_LAYERS[kind], **changes)
    time = _TIME[kind]
    it = InputType.recurrent(_WIDTH, time)
    params, state = layer.init(jax.random.key(0), it)
    x = jax.random.normal(jax.random.key(1), (1, time, _WIDTH))

    def loss(p, xx):
        out, _ = apply_layer(layer, p, state, xx, train=True, rng=None,
                             mask=None, name="mix")
        return jnp.sum(jnp.sin(out))

    return loss, params, x


def _equations(jaxpr):
    """Every equation of ``jaxpr``, through every nested jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _kernels(jaxpr):
    """Pallas calls by kernel name."""
    return collections.Counter(
        eqn.params["name"] for eqn in _equations(jaxpr)
        if eqn.primitive.name == "pallas_call")


def _names(jaxpr):
    """The ``checkpoint_name``s in ``jaxpr``."""
    return {eqn.params["name"] for eqn in _equations(jaxpr)
            if eqn.primitive.name == "name"}


def _lowered(f, params, x) -> str:
    """The lowered text of ``f``, whatever ``f`` is called."""
    text = jax.jit(f).lower(params, x).as_text()
    return re.sub(r"^module @jit_\S+", "module @jit_f", text)


def _lowered_gradient(f, params, x) -> str:
    return _lowered(jax.grad(f), params, x)


def _renumbered(text: str) -> str:
    """Lowered text with its private functions' names replaced by their
    order of first appearance. jax lowers every kind of equation through an
    out-of-line function of the primitive's name, inlined and erased after;
    MLIR's symbol table numbers name clashes from ONE counter, so an
    equation that lowers to nothing (``name``) still moves the suffixes of
    the functions that stay (``@tril_57`` -> ``@tril_58``)."""
    seen = {}
    return re.sub(r"@[A-Za-z_][\w.]*",
                  lambda m: seen.setdefault(m.group(0), f"@f{len(seen)}"),
                  text)


def _as_before(layer, state, marker, policy=None):
    """``apply_layer`` under ``remat="full"`` as it was before a layer type
    could name what it keeps: ``jax.checkpoint(policy=None)`` in the
    layer's scope."""
    def apply(p, xx):
        with jax.named_scope(marker):
            out, _ = jax.checkpoint(
                lambda p_, s_, x_, k_, m_, e_: layer.apply(
                    p_, s_, x_, train=True, rng=k_, mask=m_, **e_),
                policy=policy)(p, state, xx, None, None, {})
        return out
    return apply


def _saved_in_the_layer(loss, params, x, capsys):
    """(shape and type, whence) of what ``jax.grad(loss)`` saves from its
    forward pass, less its arguments and constants."""
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(loss, params, x)
    lines = capsys.readouterr().out.splitlines()
    return [tuple(line.split(" ", 1)) for line in lines
            if "from the argument" not in line and "constant" not in line]


def _execution(execution):
    return (pk.override(enabled=True, interpret=True)
            if execution == "kernels" else pk.override(enabled=False))


def _kept_counter():
    return compile_watch.GLOBAL.counters().get("remat.kept_values", 0)


@pytest.mark.parametrize("remat,forwards,counted", [
    ("full", 1, True), ("dots_saveable", 1, True),
    ("nothing_saveable", 2, False), (None, 1, False)])
@pytest.mark.parametrize("kind", _DELTA_RULE + _LATENT)
def test_a_rematerialised_layer_runs_its_kept_kernel_once(
        kind, remat, forwards, counted):
    """``jax.grad`` through ``apply_layer``: under ``"full"`` the backward
    pass recomputes what leads to the kernel (the delta-rule layers'
    projections and input kernel: two ``kda_inputs_fwd``; the latent
    attention's q, k, v) and reads the kernel's kept results (ONE
    ``kda_scan_fwd`` / ``mla_attend_fwd``, where the parent ran two);
    ``"nothing_saveable"`` keeps nothing and runs it twice; without
    ``remat`` nothing is recomputed. ``remat.kept_values`` counts the names
    a layer application's policy holds: five a delta-rule layer (the scan's
    o, states, u and scores; the projections' outputs share one), five a
    latent layer."""
    loss, params, x = _loss_of(kind, remat=remat)
    _, _, writes, reads = _SCOPES[kind]
    want = {writes: forwards, reads: 1}
    if kind in _DELTA_RULE:
        want.update(kda_inputs_fwd=2 if remat else 1, kda_inputs_bwd=1)
    before = _kept_counter()
    with pk.override(enabled=True, interpret=True):
        found = _kernels(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr)
    assert found == want
    names = 5
    assert len(_LAYERS[kind].remat_keeps) == names
    assert _kept_counter() - before == (names if counted else 0)


@pytest.mark.parametrize("kind,execution,other", [
    *[(kind, "kernels", "nothing_saveable")
      for kind in _DELTA_RULE + _LATENT],
    *[(kind, "jax_numpy", "nothing_saveable") for kind in _DELTA_RULE],
    # the latent layer's q, k, v are named by the layer, in both
    # executions; and against the layer that rematerialises nothing
    *[(kind, "jax_numpy", "nothing_saveable") for kind in _LATENT],
    *[(kind, execution, None) for kind in _LATENT
      for execution in ("kernels", "jax_numpy")]])
def test_kept_results_change_no_bit_of_the_gradient(kind, execution, other):
    """What is kept is what would be recomputed, as it was written (the
    scan's results in float32, the attention's o, q, k, v in the compute
    type, the delta-rule layers' projections in the compute type, in both
    executions): the gradients under ``"full"`` are those under
    ``"nothing_saveable"`` (and, for the latent layer, under no ``remat``
    at all) bit for bit."""
    grads = {}
    for remat in ("full", other):
        loss, params, x = _loss_of(kind, remat=remat)
        with _execution(execution):
            grads[remat] = jax.grad(loss, argnums=(0, 1))(params, x)
    kept, recomputed = (jax.tree.leaves(grads[r]) for r in ("full", other))
    assert len(kept) == len(recomputed) > 5
    for a, b in zip(kept, recomputed):
        assert float(jnp.max(jnp.abs(a))) > 0
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_jax_numpy_scan_names_nothing():
    """Off the kernels the scan checkpoints its own groups of chunks and
    names nothing: the one name in the layer's gradient is the
    projections', and its lowered text is what a policy that saves that
    name alone lowers to."""
    changes = dict(head_dim=8, low_rank=4, chunk=16, remat="full")
    loss, params, x = _loss_of("kda", **changes)
    before = _as_before(
        dataclasses.replace(_LAYERS["kda"], **changes), {},
        "KimiDeltaAttention:mix",
        jax.checkpoint_policies.save_only_these_names(*la.PROJECTIONS_KEPT))

    def plain(p, xx):
        return jnp.sum(jnp.sin(before(p, xx)))

    with pk.override(enabled=False):
        assert _names(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr) \
            == set(la.PROJECTIONS_KEPT)
        texts = [_lowered_gradient(f, params, x) for f in (loss, plain)]
    assert "checkpoint_name" not in texts[0]
    assert texts[0] == texts[1]


# ------------------------------------------------- the projections' outputs
# kind -> (the weights whose products are kept, those in front of the scan
# whose products are made again, the layer's inner scope)
_PROJECTIONS = {
    # ``x Wv`` is wide and is NOT named: with it the TPU's scheduler reorders
    # the Kimi step for no shorter a step (PERF.md §6, PR 41)
    "kda": (("Wq", "Wk", "Wf1"), ("Wv", "Wf2", "Wb"), "kda.conv"),
    "gdn": (("Wqkvz",), ("Wba",), "gdn.conv"),
    # the latent layer names q, k, v as the attention reads them: what goes
    # from the backward pass is everything between the latents and the
    # kernel; the down-projections feed the up-projections' weight
    # gradients and the norms' backward passes and are made again
    "mla": (("Wq", "Wkvb"), ("Wkva",), "mla.attend"),
    "rmla": (("Wqb", "Wkvb"), ("Wqa", "Wkva"), "mla.attend"),
}
_EXECUTIONS = ["kernels", "jax_numpy"]


def _rotations(jaxpr, width):
    """The rotation's products in the forward direction: ``x @ S`` with S
    the (width, width) signed permutation, contracted over its rows (the
    backward pass's ``dy @ S^T`` contracts its columns)."""
    return sum(1 for eqn in _equations(jaxpr)
               if eqn.primitive.name == "dot_general"
               and eqn.invars[1].aval.shape == (width, width)
               and tuple(eqn.params["dimension_numbers"][0][1]) == (0,))


def _forward_products(jaxpr, leaves):
    """How often each of ``jaxpr``'s arguments (named by ``leaves``, in
    order) is the right-hand side of a product in the forward direction,
    ``x @ W`` (contracted over W's rows: dX = dY W^T contracts its columns
    and dW reads no W), through every nested jaxpr that takes its
    equation's operands one for one."""
    count = collections.Counter()

    def walk(inner, env):
        for eqn in inner.eqns:
            held = [env.get(v) if hasattr(v, "count") else None
                    for v in eqn.invars]
            if eqn.primitive.name == "dot_general" and held[1] and tuple(
                    eqn.params["dimension_numbers"][0][1]) == (0,):
                count[held[1]] += 1
            if eqn.primitive.name == "convert_element_type" and held[0]:
                env = {**env, eqn.outvars[0]: held[0]}
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, {v: name for v, name in zip(
                            sub.invars, held) if name}
                            if len(sub.invars) == len(held) else {})

    walk(jaxpr, dict(zip(jaxpr.invars, leaves)))
    return count


@pytest.mark.parametrize("remat,kept,made_again", [
    ("full", 1, 2), ("nothing_saveable", 2, 2), (None, 1, 1)])
@pytest.mark.parametrize("execution", _EXECUTIONS)
@pytest.mark.parametrize("kind", _DELTA_RULE + _LATENT)
def test_a_rematerialised_layer_runs_its_wide_projections_once(
        kind, execution, remat, kept, made_again):
    """``jax.grad`` through ``apply_layer``: under ``"full"`` the products
    whose outputs the type names (KDA's ``x Wq``, ``x Wk`` and the decay's
    latent ``x Wf1``; Gated DeltaNet's ``x Wqkvz``) are in the gradient
    once in the forward direction, where the parent made them twice, in
    both executions; the others in front of the scan (KDA's ``x Wv``,
    ``Wf2``'s, ``Wb``'s / ``Wba``'s) are made again, the input kernel is
    run again from the kept outputs and the scan's kernel is not;
    ``"nothing_saveable"`` makes everything twice; without ``remat``
    nothing is made again. The latent layer, with and without ``q_rank`` /
    ``rope_theta``: ``W_qb`` (or ``W_q``), ``W_kvb`` and the rotation's two
    products (every head's rotary widths, the one shared key) once, the
    down-projections ``W_qa`` / ``W_kva`` again, the attention's forward
    kernel once."""
    loss, params, x = _loss_of(kind, remat=remat)
    named, narrow, _ = _PROJECTIONS[kind]
    with _execution(execution):
        jaxpr = jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr
    found = _forward_products(jaxpr, sorted(params))
    assert {w: found[w] for w in named + narrow} == {
        **dict.fromkeys(named, kept), **dict.fromkeys(narrow, made_again)}
    assert found["Wo"] == 1
    kernels = _kernels(jaxpr) if execution == "kernels" else None
    if kind in _LATENT:
        rotated = 2 if _LAYERS[kind].rope_theta else 0
        assert _rotations(jaxpr, _LAYERS[kind].rope_dim) == rotated * kept
        assert set(OPERANDS_KEPT) <= _names(jaxpr)
        if kernels:
            assert kernels == {"mla_attend_fwd": kept, "mla_attend_bwd": 1}
    else:
        assert la.PROJECTIONS_KEPT[0] in _names(jaxpr)
        if kernels:
            assert kernels["kda_inputs_fwd"] == (2 if remat else 1)
            assert kernels["kda_scan_fwd"] == (
                2 if remat == "nothing_saveable" else 1)
            assert kernels["kda_inputs_bwd"] == kernels["kda_scan_bwd"] == 1


@pytest.mark.parametrize("kind,shapes", [
    ("kda", ["f32[1,128,256]"] * 2 + ["f32[1,128,8]"]),
    ("gdn", ["f32[1,128,768]"])])
def test_the_jax_numpy_execution_saves_the_named_projections(kind, shapes,
                                                             capsys):
    """One contract, two executions: off the kernels
    ``causal_depthwise_conv`` reads what the input kernel reads, and under
    ``"full"`` the layer saves the named products' outputs, as the products
    wrote them, and nothing else of its own: the scan names nothing
    there."""
    with pk.override(enabled=False):
        loss, params, x = _loss_of(kind, remat="full")
        saved = [what for what, whence in _saved_in_the_layer(
            loss, params, x, capsys) if __file__ not in whence]
    assert sorted(saved) == sorted(shapes)


@pytest.mark.parametrize("remat", [None, "nothing_saveable"])
@pytest.mark.parametrize("kind", _DELTA_RULE + _LATENT)
def test_a_projection_s_name_no_policy_holds_lowers_to_its_operand(
        kind, remat, monkeypatch):
    """A delta-rule or latent layer that is not rematerialised, or keeps
    nothing, lowers to the parent's program: with ``checkpoint_name`` taken
    away (the latent layer's three AND the forward rule's two) the lowered
    gradient is the same text but for the numbers MLIR gives its private
    functions (``_renumbered``)."""
    from deeplearning4j_tpu.nn.conf import attention as attention_layers
    if kind in _LATENT:
        changes, module = dict(remat=remat), attention_layers
        names = set(attention_kernels.KEPT + OPERANDS_KEPT)
    else:
        changes, module = dict(head_dim=8, chunk=16, remat=remat), la
        names = set(la.PROJECTIONS_KEPT)
    if kind == "kda":
        changes["low_rank"] = 4
    loss, params, x = _loss_of(kind, **changes)
    with pk.override(enabled=False):
        assert _names(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr) \
            == names
        named = _lowered_gradient(loss, params, x)
        assert "checkpoint_name" not in named
        assert "latent_attention." not in named
        monkeypatch.setattr(module, "checkpoint_name",
                            lambda value, name: value)
        assert _names(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr) \
            == set()
        assert _renumbered(_lowered_gradient(loss, params, x)) \
            == _renumbered(named)


@pytest.mark.parametrize("remat", [None, "full"])
@pytest.mark.parametrize("kind", _LATENT)
def test_a_latent_layer_outside_training_lowers_to_the_parent_s_text(
        kind, remat, monkeypatch):
    """``train=False`` and nothing differentiated (serving, ``output``,
    ``score``): no policy is ever asked, the five ``name`` equations lower
    to their operands and the program is the parent's, ``remat`` or not."""
    from deeplearning4j_tpu.nn.conf import attention as attention_layers
    layer = dataclasses.replace(_LAYERS[kind], remat=remat)
    time = _TIME[kind]
    params, state = layer.init(jax.random.key(0),
                               InputType.recurrent(_WIDTH, time))
    x = jax.random.normal(jax.random.key(1), (1, time, _WIDTH))

    def forward():      # a new function a trace: jax caches by identity
        return lambda p, xx: apply_layer(
            layer, p, state, xx, train=False, rng=None, mask=None,
            name="mix")[0]

    with pk.override(enabled=False):
        assert _names(jax.make_jaxpr(forward())(params, x).jaxpr) \
            == set(OPERANDS_KEPT)       # the forward RULE is never traced
        named = _lowered(forward(), params, x)
        assert "latent_attention." not in named
        monkeypatch.setattr(attention_layers, "checkpoint_name",
                            lambda value, name: value)
        assert _names(jax.make_jaxpr(forward())(params, x).jaxpr) == set()
        assert _renumbered(_lowered(forward(), params, x)) \
            == _renumbered(named)


# ---------------------------------------------------------- latent attention
@pytest.mark.parametrize("execution", ["jax_numpy", "kernels"])
@pytest.mark.parametrize("kind", _LATENT)
def test_both_executions_keep_the_same_five_names(kind, execution, capsys):
    """One algorithm, two executions, one contract: whichever execution
    ran, the gradient's jaxpr holds ``KEPT``'s two names, the layer's three
    (``OPERANDS_KEPT``) and no other, and under ``"full"`` the layer saves
    the backward rule's five residuals: q, k (batch, heads, time, d_q), v
    and the attention's output (batch, heads, time, d_v), the log-sum-exp,
    and nothing else of its own; ``jax.ad_checkpoint`` reports q, k, v by
    name in both executions and, in the ``jax.numpy`` execution, the other
    two (the kernels' are inside a jitted function, whose name it
    gives)."""
    o_name, lse_name = attention_kernels.KEPT
    kernels = execution == "kernels"
    time = _TIME[kind]
    # q / k heads and v heads are both 64 wide here
    o = f"f32[1,2,{time},64]"
    lse = f"f32[1,2,1,{time}]" if kernels else f"f32[1,2,{time}]"
    with _execution(execution):
        loss, params, x = _loss_of(kind, remat="full")
        assert _names(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr) \
            == {o_name, lse_name, *OPERANDS_KEPT}
        saved = [(what, whence) for what, whence in _saved_in_the_layer(
            loss, params, x, capsys) if __file__ not in whence]
        assert sorted(what for what, _ in saved) == sorted([o] * 4 + [lse]), \
            saved
        loss, params, x = _loss_of(kind)
        named = [(what, whence) for what, whence in _saved_in_the_layer(
            loss, params, x, capsys) if "named" in whence]
    for name in OPERANDS_KEPT + (() if kernels else (o_name,)):
        assert (o, f"named '{name}'") in [
            (what, whence[:whence.index("'", 7) + 1])
            for what, whence in named], (name, named)
    if not kernels:
        assert len(named) == 5
        assert [what for what, whence in named
                if f"named '{lse_name}'" in whence] == [lse]


def test_latent_kept_bytes_at_the_cells_shape():
    """o (32, 8192, 128) in bfloat16 and the log-sum-exp (32, 8192) in
    float32: 64 + 1 MB a layer, 260 bytes a token and head; q, k (32, 8192,
    192) and v (32, 8192, 128) in bfloat16: 96 + 96 + 64 MB, 1,024 bytes a
    token and head; the same in the Kimi and the JoyAI cell (one kernel
    shape, whatever made q)."""
    it = InputType.recurrent(2048, 8192)
    shape = dict(n_heads=32, nope_dim=128, rope_dim=64, v_dim=128,
                 kv_rank=512, remat="full")
    kimi = MultiHeadLatentAttention(**shape)
    joyai = MultiHeadLatentAttention(q_rank=1536, rope_theta=32e6, **shape)
    results = 8192 * 32 * 128 * 2 + 8192 * 32 * 4
    operands = 8192 * 32 * (192 + 192 + 128) * 2
    assert (results, operands) == (68_157_440, 268_435_456)
    assert attention_kernels.kept_bytes(8192, 32, 128, 512,
                                        jnp.bfloat16) == results
    assert kimi.remat_kept_bytes(it, jnp.bfloat16) \
        == joyai.remat_kept_bytes(it, "bfloat16") == results + operands \
        == 336_592_896
    assert (results + operands) // (8192 * 32) == 260 + 1024 == 1284
    # float32 where the network computes in it; o and the log-sum-exp at a
    # length padded to whole tiles, q, k, v at the length the layer is
    # given (the padding is made again); a sequence under one tile as it is
    assert kimi.remat_kept_bytes(it) == 8192 * 32 * (128 + 1 + 512) * 4
    assert attention_kernels.kept_bytes(1000, 2, 64, 512, jnp.float32) \
        == attention_kernels.kept_bytes(1024, 2, 64, 512, jnp.float32)
    assert attention_kernels.kept_bytes(100, 2, 64, 512, jnp.float32) \
        == 2 * 100 * (64 + 1) * 4
    assert kimi.remat_kept_bytes(InputType.recurrent(2048, 1000)) \
        == 32 * (1024 * (128 + 1) + 1000 * 512) * 4
    assert kimi.remat_kept_bytes(InputType.recurrent(2048, 100)) \
        == 32 * 100 * (128 + 1 + 512) * 4


_NAMELESS = [
    GatedFeedForward(ff_size=24), RMSNorm(), DenseLayer(n_out=8),
    RotaryAttention(n_heads=2, head_dim=8, block=8),
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


_PLAIN = {
    "GatedFeedForward": GatedFeedForward(ff_size=24, remat="full"),
    # two tiles of the blocked attention: the forward rule's ``name``
    # equations are in the trace
    "RotaryAttention": RotaryAttention(n_heads=2, head_dim=8, block=8,
                                       remat="full"),
    "GatedAttention": GatedAttention(n_heads=4, n_kv_heads=2, head_dim=8,
                                     rotary_dim=4, block=8, remat="full"),
}


def _plain_loss(kind, remat):
    layer = dataclasses.replace(_PLAIN[kind], remat=remat)
    params, state = layer.init(jax.random.key(0),
                               InputType.recurrent(12, 16))
    x = jax.random.normal(jax.random.key(1), (2, 16, 12))

    def loss(p, xx):
        out, _ = apply_layer(layer, p, state, xx, train=True, rng=None,
                             mask=None, name="mix")
        return jnp.sum(out * out)

    return loss, params, x, layer, state


@pytest.mark.parametrize("kind", sorted(_PLAIN))
def test_a_nameless_type_under_full_lowers_to_the_parent_s_text(kind):
    """The lowered gradient of a layer whose type names nothing, through
    ``apply_layer`` with ``remat="full"``, is the text of the plain
    ``jax.checkpoint(policy=None)`` form that ``apply_layer`` was before
    layers could name what they keep, and counts no kept value:
    ``RotaryAttention`` and ``GatedAttention`` run the forward rule that
    names its results and hold no policy that knows the names."""
    now, params, x, layer, state = _plain_loss(kind, "full")
    as_before = _as_before(layer, state, f"{kind}:mix")

    def parent(p, xx):
        out = as_before(p, xx)
        return jnp.sum(out * out)

    before = _kept_counter()
    digests = [hashlib.sha256(_lowered_gradient(f, params, x).encode())
               .hexdigest() for f in (now, parent)]
    assert digests[0] == digests[1]
    assert _kept_counter() == before


@pytest.mark.parametrize("kind", ["RotaryAttention", "GatedAttention"])
@pytest.mark.parametrize("remat", [*sorted(fusion.REMAT_POLICIES), None])
def test_a_name_no_policy_holds_lowers_to_its_operand(kind, remat,
                                                      monkeypatch):
    """The other attention types' programs are the parent's: with the
    forward rule's ``checkpoint_name`` taken away the lowered gradient is
    the same text but for the numbers MLIR gives its private functions
    (``_renumbered``), under every ``remat`` that recomputes and without
    one (under ``"everything_saveable"`` a named value is saved through a
    ``reduce_precision`` of its own, as on the parent: there the text is
    held to the forward rule's two names alone); the latent layer's own
    three names are in no trace of theirs."""
    from deeplearning4j_tpu.nn.conf import attention as attention_layers
    loss, params, x, _, _ = _plain_loss(kind, remat)
    assert _names(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr) \
        == set(attention_kernels.KEPT)
    named = _lowered_gradient(loss, params, x)
    assert "blocked_attention." not in named
    assert "latent_attention." not in named
    monkeypatch.setattr(attention_layers, "checkpoint_name",
                        lambda value, name: value)
    assert _names(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr) == set()
    if remat != "everything_saveable":
        assert _renumbered(_lowered_gradient(loss, params, x)) \
            == _renumbered(named)


@pytest.mark.parametrize("name", sorted(fusion.REMAT_POLICIES))
@pytest.mark.parametrize("kind", ["kda", "mla", "rmla"])
def test_names_join_every_saving_policy_and_not_nothing_saveable(kind, name):
    kept = (kda_kernels.KEPT + la.PROJECTIONS_KEPT if kind == "kda"
            else attention_kernels.KEPT + OPERANDS_KEPT)
    layer = dataclasses.replace(_LAYERS[kind], remat=name)
    keeps = fusion.kept_names(layer)
    assert keeps == (() if name == "nothing_saveable" else kept)
    policy = fusion.remat_policy(name, keeps)
    base = fusion.remat_policy(name)
    if name == "nothing_saveable":
        assert policy is base is jax.checkpoint_policies.nothing_saveable
    else:
        assert policy is not base and callable(policy)
        # one object a (name, keeps): layers share their lowered functions
        assert policy is fusion.remat_policy(name, keeps)
    assert fusion.kept_names(dataclasses.replace(layer, remat=None)) == ()


def _one_mixer_step(kind, remat, execution, step_op_names):
    """The ``op_name``s of the compiled train step of a graph of one mixer
    (``mix1``) and an output layer."""
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    time = _TIME[kind]
    conf = (GraphBuilder(NeuralNetConfiguration.builder().seed(3)
                         .updater(Sgd(learning_rate=0.05)))
            .add_inputs("in")
            .add_layer("mix1", dataclasses.replace(_LAYERS[kind],
                                                   remat=remat), "in")
            .add_layer("out", RnnOutputLayer(n_out=3, loss="mcxent"), "mix1")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(12, time)).build())
    with _execution(execution):
        net = ComputationGraph(conf).init()
        return step_op_names(
            net, [jax.ShapeDtypeStruct((1, time, 12), jnp.float32)],
            [jax.ShapeDtypeStruct((1, time, 3), jnp.float32)])


@pytest.mark.parametrize("kind", _DELTA_RULE + ["rmla"])
def test_kept_results_lie_under_the_layers_scope(kind, step_op_names):
    """``kda.device_ms_per_step`` / ``gdn.`` / ``mla.`` / ``rmla.`` and the
    scan and attention rooflines find operations by ``op_name``: the forward
    kernel that writes the kept results (here its interpreted body) lies in
    the compiled step under the layer's marker and ``kda.scan`` /
    ``gdn.scan`` / ``mla.attend``, in the first pass alone; the backward
    kernel reads them under the same scope."""
    cls, scope, writes, reads = _SCOPES[kind]
    before = _kept_counter()
    names = _one_mixer_step(kind, "full", "kernels", step_op_names)
    assert _kept_counter() - before == len(_LAYERS[kind].remat_keeps)
    for kernel, way, other in ((writes, "jvp(", "transpose("),
                               (reads, "transpose(", None)):
        mine = [n for n in names if kernel in n]
        assert len(mine) > 10, (kernel, len(mine))
        for n in mine:
            assert f"{cls}:mix1" in n and scope in n and way in n, n
            # the forward kernel is not in the backward's recomputation
            assert other is None or (other not in n
                                     and "rematted_computation" not in n), n


@pytest.mark.parametrize("kind", _DELTA_RULE)
def test_the_backward_pass_makes_no_named_projection_again(kind,
                                                           step_op_names):
    """In the COMPILED step the backward pass's recomputation
    (``rematted_computation``) under ``kda.conv`` / ``gdn.conv`` still holds
    products, the narrow ones, and fewer instructions of them than where
    nothing is kept; the first pass's lie under the same scope, which is
    where ``kda.device_ms_per_step`` / ``gdn.`` look for them. (Which
    weight a compiled product reads is not in its name: the jaxpr's count
    above is the exact one.)"""
    cls, (_, _, scope) = _SCOPES[kind][0], _PROJECTIONS[kind]
    made_again = {}
    for remat in ("full", "nothing_saveable"):
        names = _one_mixer_step(kind, remat, "jax_numpy", step_op_names)
        products = [n for n in names if f"{cls}:mix1" in n
                    and n.endswith(f"{scope}/dot_general")]
        first = [n for n in products if "transpose(" not in n]
        assert first and all("rematted_computation" not in n for n in first)
        made_again[remat] = [n for n in products
                             if "rematted_computation" in n]
        assert all("transpose(" in n for n in made_again[remat])
    assert 0 < len(made_again["full"]) < len(made_again["nothing_saveable"])


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
_CELL_LAYERS = {
    "kimi": la.KimiDeltaAttention(n_heads=32, head_dim=128, low_rank=128,
                                  remat="full"),
    "qwen": la.GatedDeltaNet(n_key_heads=16, n_value_heads=32, head_dim=128,
                             remat="full"),
}


def test_kept_bytes_at_the_kimi_cell_s_shape():
    """The scan's share, in both cells: o, u and the scores [P | kk_off]
    (8192, 32, 128) each and the states (32, 128, 128, 128), float32
    whatever the network computes in: 3 x 134 + 268 MB a layer, 80 KB a
    token."""
    it = InputType.recurrent(2304, 8192)
    kimi, qwen = _CELL_LAYERS["kimi"], _CELL_LAYERS["qwen"]
    assert kda_kernels.kept_bytes(8192, 32, 128, 64) == 671_088_640 \
        == 3 * 134_217_728 + 268_435_456
    for layer, columns in ((kimi, 2 * 4096 + 128), (qwen, 12288)):
        assert layer.remat_kept_bytes(it, jnp.bfloat16) \
            - 8192 * columns * 2 == 671_088_640
    assert kda_kernels.kept_bytes(8192, 32, 128, 64) // 8192 == 80 * 1024
    # the scores are 2 x 64 wide whatever the head: 256-wide heads keep
    # o and u at 256 and the states at 256 x 256
    assert kda_kernels.kept_bytes(64, 1, 256, 64) == 4 * (
        2 * 64 * 256 + 64 * 128 + 256 * 256)
    # a length that is padded to whole chunks; a head the kernels refuse
    assert kda_kernels.kept_bytes(100, 2, 128, 64) \
        == kda_kernels.kept_bytes(128, 2, 128, 64)
    assert kda_kernels.kept_bytes(128, 2, 64, 64) == 0
    # a chunk the kernels refuse: the ``jax.numpy`` execution keeps the
    # projections alone, at the length it is given
    assert dataclasses.replace(kimi, chunk=32).remat_kept_bytes(it) \
        == 8192 * (2 * 4096 + 128) * 4
    short = InputType.recurrent(2304, 100)
    assert dataclasses.replace(kimi, chunk=32).remat_kept_bytes(short) \
        == 100 * (2 * 4096 + 128) * 4
    assert kimi.remat_kept_bytes(short) \
        == kda_kernels.kept_bytes(128, 32, 128, 64) + 128 * (2 * 4096 + 128) * 4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", _DELTA_RULE)
def test_kept_bytes_are_the_bytes_of_the_values_named(kind, dtype):
    """``remat_kept_bytes`` against the layer's own gradient trace under
    the kernels (without ``remat``, where every named value is made once):
    the values that carry one of ``remat_keeps``'s names (the scan's o, its
    chunk states, the chunks' solved u and their scores [P | kk_off] in
    float32, the projections' outputs in the network's type) add up to it,
    and every name is there."""
    layer = _LAYERS[kind]
    it = InputType.recurrent(_WIDTH, _TIME[kind])
    params, state = layer.init(jax.random.key(0), it)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = jnp.zeros((1, _TIME[kind], _WIDTH), dtype)
    with pk.override(enabled=True, interpret=True):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p, xx: jnp.sum(apply_layer(
            layer, p, state, xx, train=True, rng=None, mask=None,
            name="mix")[0].astype(jnp.float32))))(params, x).jaxpr
    named = [(eqn.params["name"], eqn.outvars[0].aval)
             for eqn in _equations(jaxpr) if eqn.primitive.name == "name"]
    assert {name for name, _ in named} == set(layer.remat_keeps) \
        == set(kda_kernels.KEPT + la.PROJECTIONS_KEPT)
    for name in ("kda_scan.u", "kda_scan.scores"):
        read, = [aval for n, aval in named if n == name]
        assert read.shape == (1, 2, _TIME[kind], 128)
        assert read.dtype == jnp.float32
    assert sum(aval.size * aval.dtype.itemsize for _, aval in named) \
        == layer.remat_kept_bytes(it, dtype)


@pytest.mark.parametrize("cell,width,projections,a_token", [
    # x Wq, x Wk (8192, 4096) and the decay's latent (8192, 128)
    ("kimi", 2304, 136_314_880, 16_640),
    # x Wqkvz (8192, 12288): q | k | v | z
    ("qwen", 2048, 201_326_592, 24_576)])
def test_projections_kept_bytes_at_the_cells_shapes(cell, width, projections,
                                                    a_token):
    """What the named projections add to a delta-rule layer's kept bytes at
    the cells' shapes (1 x 8192 tokens, bfloat16): 136.3 MB a Kimi layer,
    201.3 MB a Qwen layer, 16.6 and 24.6 KB a token beside the scan's 80
    KB; in float32 twice that; and ``conf.memory_report()`` prints the
    sum."""
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    layer = _CELL_LAYERS[cell]
    it = InputType.recurrent(width, 8192)
    scan = 671_088_640
    assert layer.remat_kept_bytes(it, jnp.bfloat16) \
        == layer.remat_kept_bytes(it, "bfloat16") == scan + projections
    assert layer.remat_kept_bytes(it) == scan + 2 * projections
    assert projections // 8192 == a_token
    for remat, kept in (("full", scan + projections), ("nothing_saveable", 0)):
        conf = (NeuralNetConfiguration.builder().seed(1)
                .updater(Sgd(learning_rate=0.1)).dtype("bfloat16").list()
                .layer(dataclasses.replace(layer, remat=remat))
                .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
                .set_input_type(it).build())
        text = conf.memory_report(minibatch=1).to_string()
        assert (f"keeps {kept / 2**10:.1f} KB/ex" in text) == bool(kept)
        assert ("keeps" in text) == bool(kept)


@pytest.mark.parametrize("kind,dtype,want,printed", [
    # o, u, the scores and the two chunks' states, float32 whatever the
    # network's type, and the projections' 2 x 256 + 8 columns in the
    # network's type
    ("kda", "float32", 4 * 2 * 128 * (3 * 128 + 2 * 128) + 4 * 128 * 520,
     "keeps 900.0 KB/ex"),
    ("kda", "bfloat16", 4 * 2 * 128 * (3 * 128 + 2 * 128) + 2 * 128 * 520,
     "keeps 770.0 KB/ex"),
    # one product of 2 x (1 + 2) x 128 columns
    ("gdn", "bfloat16", 4 * 2 * 128 * (3 * 128 + 2 * 128) + 2 * 128 * 768,
     "keeps 832.0 KB/ex"),
    # o, q, k, v (64 wide each) in the network's type and a float32 a token
    # and head
    ("rmla", "bfloat16", 2 * 256 * (4 * 64 * 2 + 4), "keeps 258.0 KB/ex"),
    ("rmla", "float32", 2 * 256 * (4 * 64 * 4 + 4), "keeps 514.0 KB/ex"),
    # the same whatever made q: the full-rank query, no rotation
    ("mla", "bfloat16", 2 * 256 * (4 * 64 * 2 + 4), "keeps 258.0 KB/ex")])
@pytest.mark.parametrize("remat,keeps", [
    ("full", True), ("dots_saveable", True), ("nothing_saveable", False),
    (None, False)])
def test_the_memory_report_counts_what_a_layer_keeps(remat, keeps, kind,
                                                     dtype, want, printed):
    from deeplearning4j_tpu.nn.memory import conf_memory_report
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Sgd(learning_rate=0.1)).dtype(dtype).list()
            .layer(dataclasses.replace(_LAYERS[kind], remat=remat))
            # runs the forward rule that names its results; declares nothing
            .layer(RotaryAttention(n_heads=2, head_dim=8, block=8,
                                   remat=remat))
            .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.recurrent(_WIDTH, _TIME[kind])).build())
    rep = conf_memory_report(conf, minibatch=3, training_bytes=False)
    want = want if keeps else 0
    assert [l.remat_kept_bytes_per_example for l in rep.layers] \
        == [want, 0, 0]
    outputs = sum(l.activation_bytes_per_example for l in rep.layers)
    assert rep.total_activation_bytes == 3 * (outputs + want)
    assert (printed in rep.to_string()) == keeps


# -------------------------------------------------------------- validation
@pytest.mark.parametrize("remat,refused", [
    *[(name, False) for name in sorted(fusion.REMAT_POLICIES)],
    ("keep_the_scan", True), ("save_only_these_names", True),
    # what a type keeps is no value of the knob: a ``checkpoint_name`` is
    # not a policy's name
    *[(name, True) for name in OPERANDS_KEPT]])
def test_validation_knows_five_names_and_no_new_one(remat, refused):
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    assert len(fusion.REMAT_POLICIES) == 5
    assert not set(OPERANDS_KEPT + attention_kernels.KEPT) \
        & set(fusion.REMAT_POLICIES)
    conf = (NeuralNetConfiguration.builder().seed(1).list()
            .layer(la.KimiDeltaAttention(n_heads=2, head_dim=8, low_rank=4,
                                         remat=remat))
            .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.recurrent(12, 16)).build())
    rules = [i.rule for i in conf.validate(raise_on_error=False)]
    assert ("unknown-remat" in rules) == refused

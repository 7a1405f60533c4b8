"""The loop construct and what a looped language model adds around it:
``LoopVertex`` (a sub-graph run several times over one set of weights),
``RotaryAttention``, the exit-weighted output layer. Small
sizes on the CPU in float32; ``tests/benchmark/test_benchmark_ouro.py``
holds the zoo builder to its plain reference."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn import lossfunctions
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward
from deeplearning4j_tpu.nn.conf.graph import (ComputationGraphConfiguration,
                                              ElementWiseVertex, GraphBuilder,
                                              LoopVertex)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (
    EmbeddingSequenceLayer, ExitWeightedTokenOutputLayer, TokenOutputLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.optimize.updaters import Adam
from deeplearning4j_tpu.perf import pallas as pk

D, T, V = 16, 24, 11


def _block(width=D, time=T, dropout=0.0):
    """norm -> rotary attention -> add -> SwiGLU -> add, width-preserving."""
    b = GraphBuilder()
    b.add_inputs("h")
    b.add_layer("n1", RMSNorm(), "h")
    b.add_layer("attn", RotaryAttention(n_heads=2, head_dim=8, block=8,
                                        dropout=dropout), "n1")
    b.add_vertex("add1", ElementWiseVertex("add"), "h", "attn")
    b.add_layer("ffn", GatedFeedForward(ff_size=32), "add1")
    b.add_vertex("add2", ElementWiseVertex("add"), "add1", "ffn")
    b.set_outputs("add2")
    b.set_input_types(InputType.recurrent(width, time))
    return b.build()


def _looped(steps=3, head=None, **loop):
    g = GraphBuilder()
    g.add_inputs("ids")
    g.add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=D), "ids")
    g.add_layer("loop", LoopVertex(body=_block(), steps=steps, **loop),
                "embed")
    g.add_layer("head", head or ExitWeightedTokenOutputLayer(
        n_out=V, time_block=8, entropy_weight=0.05), "loop")
    g.set_outputs("head")
    g.set_input_types(InputType.recurrent(V, T))
    return dataclasses.replace(g.build(), updater=Adam(1e-2))


def _ids(seed=0, batch=2):
    ids = np.random.default_rng(seed).integers(
        0, V, (batch, T + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _loss_and_grads(net, x, y, params=None):
    def loss(p):
        return net._loss_fn(p, net.state, [jnp.asarray(x)], [jnp.asarray(y)],
                            None, None, None)[0]
    return jax.jit(jax.value_and_grad(loss))(
        net.params if params is None else params)


def _close(a, b, tol=1e-5):
    leaves_a, leaves_b = (jax.tree_util.tree_leaves(t) for t in (a, b))
    assert len(leaves_a) == len(leaves_b)
    for u, v in zip(leaves_a, leaves_b):
        scale = max(1.0, float(jnp.max(jnp.abs(v))))
        assert float(jnp.max(jnp.abs(u - v))) <= tol * scale


# ------------------------------------------------------------ the loop vertex
def test_the_body_s_weights_exist_once_and_the_json_round_trips():
    conf = _looped()
    text = conf.to_json()
    again = ComputationGraphConfiguration.from_json(text)
    assert again.to_json() == text
    loop = again.vertices["loop"][0]
    assert isinstance(loop, LoopVertex) and loop.steps == 3
    assert isinstance(loop.body, ComputationGraphConfiguration)
    net = ComputationGraph(again).init()
    assert set(net.params["loop"]) == {"n1", "attn", "ffn"}
    body = D + 4 * D * D + 3 * D * 32
    assert net.num_params() == V * D + body + D * V + D + 1
    one_pass = ComputationGraph(_looped(steps=1)).init()
    assert one_pass.num_params() == net.num_params()
    report = conf.memory_report(minibatch=2)
    assert {r.name: r.num_params for r in report.layers}["loop"] == body
    # one optimizer state for the body, shaped like its leaves
    mu = jax.tree_util.tree_leaves(net.opt_state["loop"])
    assert sum(a.size for a in mu) == 2 * body + 1   # mu, nu and the count
    assert conf.vertex_output_types()["loop"].passes == 3
    assert not [i for i in conf.validate(eval_shape_check=True,
                                         raise_on_error=False)
                if i.severity == "error"]


def test_the_gradient_is_the_sum_over_the_passes_of_untied_copies():
    """R copies of the block, each with weights of its own, run one after
    the other: with every copy holding the looped block's weights the loss
    is the loop's, and the looped block's gradient is the sum of the
    copies' gradients, leaf by leaf."""
    steps = 3
    net = ComputationGraph(_looped(steps=steps)).init()
    x, y = _ids()
    loss, grads = _loss_and_grads(net, x, y)
    loop = net.vertices["loop"][0]
    one = dataclasses.replace(loop, steps=1, stacked=False)
    head = net.vertices["head"][0]
    it = InputType.recurrent(D, T)

    def untied(copies, rest):
        h, _ = net.vertices["embed"][0].apply(rest["embed"], {},
                                              jnp.asarray(x))
        outs = []
        for p in copies:
            h, _ = one.apply(p, {}, h)
            outs.append(h)
        pre = head.pre_output(rest["head"], jnp.stack(outs))
        return head.compute_score(jnp.asarray(y), pre)

    copies = [net.params["loop"]] * steps
    loss_u, (g_copies, g_rest) = jax.jit(jax.value_and_grad(untied, (0, 1)))(
        copies, net.params)
    assert one.output_type(it) == it
    assert float(abs(loss - loss_u)) < 1e-6
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *g_copies)
    _close(grads["loop"], summed)
    _close(grads["head"], g_rest["head"])
    _close(grads["embed"], g_rest["embed"])
    # and no single copy's gradient is the sum
    first = jax.tree_util.tree_leaves(g_copies[0])
    total = jax.tree_util.tree_leaves(summed)
    assert any(float(jnp.max(jnp.abs(a - b))) > 1e-4
               for a, b in zip(first, total))


@pytest.mark.parametrize("stacked", [True, False])
def test_the_scanned_passes_are_the_passes_written_out(stacked):
    """One ``lax.scan`` over the body against one-pass vertices applied one
    after the other: the same outputs and, through a sum, the same
    gradients; the compiled step counts one scanned loop."""
    loop = LoopVertex(body=_block(), steps=3, stacked=stacked)
    one = dataclasses.replace(loop, steps=1, stacked=False)
    params, state = loop.init(jax.random.key(0), InputType.recurrent(D, T))
    x = jax.random.normal(jax.random.key(1), (2, T, D))

    def scanned(p, x):
        return loop.apply(p, state, x)[0]

    def written_out(p, x):
        outs, h = [], x
        for _ in range(3):
            h, _ = one.apply(p, state, h)
            outs.append(h)
        return jnp.stack(outs) if stacked else h

    assert scanned(params, x).shape == ((3, 2, T, D) if stacked
                                        else (2, T, D))
    results = [jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(jnp.sin(f(p, x))), (0, 1)))(params, x)
               for f in (scanned, written_out)]
    _close(results[0], results[1], 5e-5)
    head = None if stacked else TokenOutputLayer(n_out=V, time_block=8)
    net = ComputationGraph(_looped(stacked=stacked, head=head)).init()
    net.fit(DataSet(*_ids(1)))
    assert net.compile_watch.counters("loop.") == {"loop.scanned": 1}
    assert net.output(_ids(1)[0])[0].shape == (2, T, V)


def test_every_pass_draws_its_own_dropout():
    loop = LoopVertex(body=_block(dropout=0.5), steps=3)
    it = InputType.recurrent(D, T)
    params, state = loop.init(jax.random.key(0), it)
    x = jax.random.normal(jax.random.key(1), (2, T, D))
    quiet, _ = loop.apply(params, state, x, train=False)
    a, _ = loop.apply(params, state, x, train=True, rng=jax.random.key(2))
    b, _ = loop.apply(params, state, x, train=True, rng=jax.random.key(2))
    assert a.shape == quiet.shape == (3, 2, T, D)
    assert float(jnp.max(jnp.abs(a - b))) == 0.0      # seeded
    assert float(jnp.max(jnp.abs(a - quiet))) > 1e-3
    # pass 2 from pass 1's output with pass 1's key is not pass 2
    one = dataclasses.replace(loop, steps=1, stacked=False)
    again, _ = one.apply(params, state, a[0], train=True,
                         rng=jax.random.key(2))
    assert float(jnp.max(jnp.abs(again - a[1]))) > 1e-3


def test_a_body_carries_its_state_through_the_passes():
    """Batch normalisation inside a body: the running statistics after one
    call have seen every pass."""
    from deeplearning4j_tpu.nn.conf.normalization import BatchNormalization
    b = GraphBuilder()
    b.add_inputs("h")
    b.add_layer("dense", DenseLayer(n_out=6, activation="tanh"), "h")
    b.add_layer("bn", BatchNormalization(), "dense")
    b.set_outputs("bn")
    b.set_input_types(InputType.feed_forward(6))
    x = jax.random.normal(jax.random.key(0), (8, 6)) + 2.0
    loop = LoopVertex(body=b.build(), steps=3)
    params, state = loop.init(jax.random.key(1), InputType.feed_forward(6))
    assert set(state) == {"bn"} and set(params) == {"dense", "bn"}
    out, new = loop.apply(params, state, x, train=True)
    assert out.shape == (3, 8, 6)
    one = dataclasses.replace(loop, steps=1)
    once, after_one = one.apply(params, state, x, train=True)
    assert float(jnp.max(jnp.abs(once[0] - out[0]))) < 1e-6
    moved = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(
        a - b))), new, after_one)
    assert max(jax.tree_util.tree_leaves(moved)) > 1e-4
    # three one-pass calls, each handed the state the last one left
    h, st = x, state
    for r in range(3):
        h, st = one.apply(params, st, h[-1] if r else h, train=True)
    _close((out[-1], new), (h[-1], st), 1e-6)


@pytest.mark.parametrize("fault, match", [
    ({"steps": 0}, "steps >= 1"),
    ({"body": None}, "needs a body"),
    ({"width": 12}, "typed for"),
    ({"l2": 1e-4}, "sets \\['l2'\\]"),
    ({"narrow": 6}, "a pass writes"),
    ({"output": True}, "is an output layer"),
    ({"updaters": True}, "different updaters"),
])
def test_a_body_that_cannot_loop_is_refused_by_name(fault, match):
    b = GraphBuilder()
    b.add_inputs("h")
    b.add_layer("ffn", GatedFeedForward(
        ff_size=32, n_out=fault.get("narrow", 0), l2=fault.get("l2", 0.0),
        updater=Adam(1e-3) if fault.get("updaters") else None), "h")
    out = "ffn"
    if fault.get("output"):
        b.add_layer("out", TokenOutputLayer(n_out=D), "ffn")
        out = "out"
    if fault.get("updaters"):
        b.add_layer("more", GatedFeedForward(ff_size=32,
                                             updater=Adam(1e-4)), "ffn")
        out = "more"
    b.set_outputs(out)
    b.set_input_types(InputType.recurrent(fault.get("width", D), T))
    loop = LoopVertex(body=b.build() if "body" not in fault else None,
                      steps=fault.get("steps", 2))
    with pytest.raises(ValueError, match=match):
        loop.output_type(InputType.recurrent(D, T))


def test_a_loop_inside_a_loop_and_a_body_rematerialised_as_a_unit():
    inner = LoopVertex(body=_block(), steps=1, stacked=False, remat="full")
    b = GraphBuilder()
    b.add_inputs("h")
    b.add_layer("blk0", inner, "h")
    b.add_layer("blk1", inner, "blk0")
    b.set_outputs("blk1")
    b.set_input_types(InputType.recurrent(D, T))
    outer = LoopVertex(body=b.build(), steps=2)
    it = InputType.recurrent(D, T)
    params, state = outer.init(jax.random.key(0), it)
    assert set(params) == {"blk0", "blk1"} and set(params["blk0"]) == {
        "n1", "attn", "ffn"}
    x = jax.random.normal(jax.random.key(1), (2, T, D))
    out, _ = outer.apply(params, state, x)
    h = x
    plain = dataclasses.replace(inner, remat=None)
    for _ in range(2):
        for name in ("blk0", "blk1"):
            h, _ = plain.apply(params[name], {}, h)
    _close(out[-1], h, 1e-4)
    text = LoopVertex.to_dict(outer)
    from deeplearning4j_tpu.nn.conf.layers import layer_from_dict
    assert layer_from_dict(text) == outer


def test_checkpoint_save_and_restore_resume_bitwise(tmp_path):
    from deeplearning4j_tpu.utils.serialization import restore, write_model
    x, y = _ids(2)
    ds = DataSet(x, y)
    net = ComputationGraph(_looped()).init()
    for _ in range(3):
        net.fit(ds)
    path = str(tmp_path / "looped.zip")
    write_model(net, path)
    back = restore(path)
    assert isinstance(back.vertices["loop"][0], LoopVertex)
    _close(back.params, net.params, 0.0)
    _close(back.state, net.state, 0.0)
    for n in (net, back):
        n._rng = jax.random.key(5)
        n.fit(ds)
    _close(back.params, net.params, 0.0)
    _close(back.opt_state, net.opt_state, 0.0)
    # init(params=): the given leaves are taken, nested as they lie
    given = jax.tree_util.tree_map(jnp.array, net.params)
    started = ComputationGraph(_looped()).init(params=given)
    _close(started.params, net.params, 0.0)
    with pytest.raises(ValueError, match="do not fit"):
        ComputationGraph(_looped()).init(params={
            **given, "loop": {"attn": given["loop"]["attn"]}})


# ------------------------------------------------------- the rotary attention
def _dense_rotary_attention(layer, params, x):
    """q, k rotated pair by pair as the equations write it, the whole score
    matrix, a softmax."""
    bsz, t, _ = x.shape
    h, dh = layer.n_heads, layer.head_dim
    hkv = layer.n_kv_heads or h
    rot = dh
    q = (x @ params["Wq"]).reshape(bsz, t, h, dh)
    k = (x @ params["Wk"]).reshape(bsz, t, hkv, dh)
    v = (x @ params["Wv"]).reshape(bsz, t, hkv, dh)

    def turn(a):
        out = np.array(a)
        for j in range(rot // 2):
            angle = np.arange(t) * layer.rope_theta ** (-2.0 * j / rot)
            c, s = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
            a1, a2 = np.array(a[..., j]), np.array(a[..., j + rot // 2])
            out[..., j] = a1 * c - a2 * s
            out[..., j + rot // 2] = a2 * c + a1 * s
        return jnp.asarray(out)

    q, k = turn(q), turn(k)
    k, v = (jnp.repeat(a, h // hkv, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(bsz, t, h * dh) @ params["Wo"]


@pytest.mark.parametrize("kv_heads, t", [(0, 40), (2, 40), (1, 40), (0, 7)])
def test_rotary_attention_is_the_dense_form(kv_heads, t):
    layer = RotaryAttention(n_heads=4, n_kv_heads=kv_heads, head_dim=8,
                            rope_theta=1e4, block=16)
    it = InputType.recurrent(12, t)
    params, _ = layer.init(jax.random.key(0), it)
    assert params["Wk"].shape == (12, (kv_heads or 4) * 8)
    x = jax.random.normal(jax.random.key(1), (2, t, 12))
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    before = dict(GLOBAL.counters("attention.rotary"))
    got, _ = jax.jit(layer.apply)(params, {}, x)
    want = _dense_rotary_attention(layer, params, x)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    rose = {k for k, v in GLOBAL.counters("attention.rotary").items()
            if v > before.get(k, 0)}
    assert rose == {"attention.rotary_blocked" if t > 16
                    else "attention.rotary_single_tile"}
    mask = jnp.asarray(np.arange(t)[None, :] < np.array([[t], [t - 3]]))
    masked, _ = jax.jit(layer.apply)(params, {}, x, mask=mask)
    assert float(jnp.max(jnp.abs(masked[1, t - 3:]))) == 0.0
    assert float(jnp.max(jnp.abs(masked[1, :t - 3] - got[1, :t - 3]))) < 2e-5


def test_rotary_attention_through_the_pallas_kernels():
    """Equal q, k and v heads of 128 through the tile kernels (interpreted
    on the CPU), forward and gradients, against the ``jax.numpy`` tiles."""
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    layer = RotaryAttention(n_heads=2, head_dim=128, rope_theta=1e6,
                            block=128)
    params, _ = layer.init(jax.random.key(0), InputType.recurrent(32, 256))
    x = jax.random.normal(jax.random.key(1), (1, 256, 32))

    def run(p, x):
        return jnp.sum(jnp.sin(layer.apply(p, {}, x)[0]))

    results = []
    for enabled in (True, False):
        before = GLOBAL.counters("kernel.").get(
            "kernel.pallas_blocked_attention", 0)
        with pk.override(enabled=enabled, interpret=True):
            results.append(
                jax.jit(jax.value_and_grad(run, (0, 1)))(params, x))
        rose = GLOBAL.counters("kernel.").get(
            "kernel.pallas_blocked_attention", 0) > before
        assert rose == enabled
    _close(results[0], results[1], 2e-4)


def test_a_bad_rotary_layer_is_refused():
    it = InputType.recurrent(12, 8)
    with pytest.raises(ValueError, match="no multiple"):
        RotaryAttention(n_heads=4, n_kv_heads=3, head_dim=8).output_type(it)
    with pytest.raises(ValueError, match="has to be even"):
        RotaryAttention(n_heads=4, head_dim=7).output_type(it)


# -------------------------------------------------------- the exit-weighted loss
def test_the_exit_distribution_sums_to_one_and_is_the_products():
    g = 3.0 * jax.random.normal(jax.random.key(0), (4, 2, 9))
    logp = lossfunctions.exit_distribution(g)
    p = jnp.exp(logp)
    assert float(jnp.max(jnp.abs(jnp.sum(p, 0) - 1.0))) < 1e-6
    lam = jax.nn.sigmoid(g)
    want = [lam[0], lam[1] * (1 - lam[0]),
            lam[2] * (1 - lam[0]) * (1 - lam[1]),
            (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])]
    assert float(jnp.max(jnp.abs(p - jnp.stack(want)))) < 1e-6
    # a gate that is shut or wide open: no NaN, the mass moves whole
    hard = jnp.array([[-80.0, 80.0], [80.0, 80.0], [0.0, 0.0]])
    p = jnp.exp(lossfunctions.exit_distribution(hard))
    assert np.allclose(np.asarray(p), [[0, 1], [1, 0], [0, 0]], atol=1e-6)
    one = lossfunctions.exit_distribution(jnp.zeros((1, 5)))
    assert float(jnp.max(jnp.abs(one))) == 0.0


@pytest.mark.parametrize("t, block, masked, r, beta, biased, scaled", [
    (24, 8, False, 4, 0.05, True, False), (21, 8, True, 4, 0.05, True, False),
    (5, 1024, False, 4, 0.05, True, False),
    # no entropy term; one pass; no bias; a loss that is scaled and summed
    # with a penalty (an upstream cotangent of 0.37)
    (24, 8, False, 4, 0.0, True, False), (21, 8, True, 1, 0.05, True, False),
    (24, 8, False, 3, 0.05, False, False), (21, 8, True, 4, 0.05, True, True),
    (5, 1024, True, 2, 0.0, False, True)])
def test_the_loss_is_the_four_line_form(t, block, masked, r, beta, biased,
                                        scaled):
    ks = jax.random.split(jax.random.key(3), 6)
    bsz, d = 2, 10
    x = jax.random.normal(ks[0], (r, bsz, t, d))
    w = jax.random.normal(ks[1], (d, V))
    b = 0.1 * jax.random.normal(ks[2], (V,)) if biased else None
    wg = jax.random.normal(ks[3], (d, 1))
    bg = jnp.array([0.3])
    ids = jax.random.randint(ks[4], (bsz, t), 0, V)
    mask = (jax.random.uniform(ks[5], (bsz, t)) < 0.7) if masked else None

    def around(loss, x, w, wg):
        return (0.37 * loss + 0.1 * jnp.sum(x * x) + 0.2 * jnp.sum(w * w)
                + 0.3 * jnp.sum(wg * wg) if scaled else loss)

    def by_hand(x, w, b, wg, bg):
        lam = jax.nn.sigmoid((x @ wg)[..., 0] + bg)
        stay = jnp.cumprod(1 - lam, 0)             # prod_{j<=r} (1 - lam_j)
        before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], 0)
        p = jnp.concatenate([lam[:-1] * before[:-1], before[-1:]], 0)
        z = x @ w if b is None else x @ w + b
        ce = -jnp.take_along_axis(jax.nn.log_softmax(z, -1),
                                  ids[None, ..., None], -1)[..., 0]
        token = jnp.sum(p * ce, 0) + beta * jnp.sum(p * jnp.log(p), 0)
        m = jnp.ones_like(token) if mask is None else mask.astype(token.dtype)
        return around(jnp.sum(token * m) / jnp.sum(m), x, w, wg)

    def blocked(x, w, b, wg, bg):
        return around(lossfunctions.blocked_exit_weighted_mcxent(
            x, w, b, wg, bg, ids, mask, block, beta), x, w, wg)

    wrt = (0, 1, 2, 3, 4) if biased else (0, 1, 3, 4)
    want, g_want = jax.value_and_grad(by_hand, wrt)(x, w, b, wg, bg)
    got, g_got = jax.value_and_grad(blocked, wrt)(x, w, b, wg, bg)
    assert float(abs(got - want)) < 1e-5
    # not differentiated: the forward-only loop gives the same number
    assert float(abs(blocked(x, w, b, wg, bg) - want)) < 1e-5
    _close(g_got, g_want, 2e-5)


def test_the_looped_graph_s_step_keeps_the_exits_scopes_and_one_loss_loop():
    """The compiled train step of the small looped graph carries the three
    scopes that ``loop.exits_device_ms_per_step`` sums, forward and
    backward, and the exits' head is ONE ``while`` (loss and gradients in
    one pass over the (pass, block) pairs), traced once."""
    net = ComputationGraph(_looped()).init()
    x, y = _ids(7)
    net.fit(DataSet(x, y))
    assert net.compile_watch.counters("loss.") == {"loss.blocked_one_pass": 1}
    net.score_dataset(DataSet(x, y))
    assert net.compile_watch.counters("loss.") == {
        "loss.blocked_one_pass": 1, "loss.blocked_forward_only": 1}

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    ids = jax.ShapeDtypeStruct(x.shape, jnp.int32)
    text = net._get_jitted("train").lower(
        struct(net.params), struct(net.state), struct(net.opt_state),
        struct(net._rng), [ids], [ids], None, None).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("loop.exit_gate", "loop.exit_head", "loss.exit_weighted"):
        under = [o for o in names if scope in o]
        assert any("transpose(" in o for o in under), scope
        assert any("transpose(" not in o for o in under), scope
    loops = [o for o in names if o.endswith("/while") and "exit_head" in o]
    assert loops and all("transpose(" not in o for o in loops)


def test_with_one_pass_the_loss_is_the_token_output_layer_s():
    plain = ComputationGraph(_looped(
        steps=1, stacked=False, head=TokenOutputLayer(
            n_out=V, time_block=8))).init()
    exits = ComputationGraph(_looped(steps=1)).init()
    x, y = _ids(4)
    params = {**plain.params, "head": {**plain.params["head"],
                                       **{k: exits.params["head"][k]
                                          for k in ("Wg", "bg")}}}
    l_plain, g_plain = _loss_and_grads(plain, x, y)
    l_exit, g_exit = _loss_and_grads(exits, x, y, params)
    assert float(abs(l_plain - l_exit)) < 1e-6
    _close(g_exit["loop"], g_plain["loop"])
    _close(g_exit["head"]["W"], g_plain["head"]["W"])
    # one pass: the gate decides nothing
    assert float(jnp.max(jnp.abs(g_exit["head"]["Wg"]))) == 0.0
    assert exits.output(x)[0].shape == plain.output(x)[0].shape


def test_the_head_answers_with_the_last_pass_and_learns():
    net = ComputationGraph(_looped()).init()
    x, y = _ids(5)
    y = x.copy()                                   # learn to repeat the id
    ds = DataSet(x, y)
    net.fit(ds)
    first = net.score()
    for _ in range(40):
        net.fit(ds)
    assert net.score() < 0.5 * first
    probs = net.output(x)[0]
    assert probs.shape == (2, T, V)
    acts = net._forward(net.params, net.state, [jnp.asarray(x)], False, None,
                        None)[0]
    last = jax.nn.softmax(acts["loop"][-1] @ net.params["head"]["W"], -1)
    assert float(jnp.max(jnp.abs(probs - last))) < 1e-5
    with pytest.raises(ValueError, match="stacked passes"):
        ComputationGraph(_looped(stacked=False)).init()

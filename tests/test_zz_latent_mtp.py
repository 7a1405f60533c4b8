"""What a DeepSeek-V3-family decoder adds to the program: the latent
attention's low-rank query and decoupled rotation (fields that default to
the layer as it was, bit for bit), the time-shift and stack vertices, the
multi-token prediction module's combine layer and the output layer over the
trunk's and the module's states with ONE head and ONE block loop. Against
plain formulas written here; the whole model against its plain reference is
``tests/benchmark/test_benchmark_joyai.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import lossfunctions
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import (MultiHeadLatentAttention,
                                                  blocked_causal_attention,
                                                  rotate_interleaved)
from deeplearning4j_tpu.nn.conf.graph import (ElementWiseVertex, GraphBuilder,
                                              StackStatesVertex,
                                              TimeShiftVertex,
                                              vertex_from_dict, vertex_to_dict)
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm, rms_norm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  MultiTokenCombine,
                                                  MultiTokenOutputLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.initializers import init_weights
from deeplearning4j_tpu.perf.compile_watch import GLOBAL

THETA = 32e6


# ------------------------------------------------------------- the rotation
@pytest.mark.parametrize("position", [0, 1, 8191])
def test_the_interleaved_rotation_is_the_complex_product(position):
    """Width pair (2j, 2j + 1) as the complex number x[2j] + i x[2j + 1],
    times exp(i t theta^(-2j / r)), by hand in float64."""
    r = 64
    x = np.random.default_rng(position).normal(size=(2, 1, 3, r))
    got = rotate_interleaved(jnp.asarray(x, jnp.float32),
                             jnp.asarray([position]), THETA)
    z = x[..., 0::2] + 1j * x[..., 1::2]
    turned = z * np.exp(1j * position * THETA ** (-2.0 * np.arange(r // 2) / r))
    want = np.stack([turned.real, turned.imag], -1).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-3 if position
                               > 1 else 1e-6)
    if position == 0:
        np.testing.assert_array_equal(np.asarray(got),
                                      x.astype(np.float32))


def test_the_rotation_keeps_norms_and_relative_positions():
    """|R_t x| = |x|, and R_t q . R_u k depends on t - u alone."""
    q = jax.random.normal(jax.random.key(0), (1, 1, 16))
    k = jax.random.normal(jax.random.key(1), (1, 1, 16))

    def score(t, u):
        return float(jnp.sum(rotate_interleaved(q, jnp.asarray([t]), 1e4)
                             * rotate_interleaved(k, jnp.asarray([u]), 1e4)))

    assert score(7, 3) == pytest.approx(score(104, 100), rel=1e-4)
    assert score(7, 3) != pytest.approx(score(7, 4), rel=1e-3)
    turned = rotate_interleaved(q, jnp.asarray([50]), 1e4)
    assert float(jnp.linalg.norm(turned)) == pytest.approx(
        float(jnp.linalg.norm(q)), rel=1e-6)


def test_the_rotation_answers_in_the_input_s_type_and_shape():
    x = jnp.ones((2, 5, 8), jnp.bfloat16)
    out = rotate_interleaved(x, jnp.arange(5), THETA)
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape
    heads = jnp.ones((2, 5, 3, 8), jnp.float32)
    assert rotate_interleaved(heads, jnp.arange(5), THETA).shape == heads.shape


# ------------------------------------------------------- the latent layer
def _parent_init(layer, rng, it, dtype=jnp.float32):
    """``MultiHeadLatentAttention.init`` as it was before the layer had a
    low-rank query or a rotation."""
    d, h = it.size, layer.n_heads
    ks = jax.random.split(rng, 4)

    def dense(key, n_in, n_out):
        return init_weights(key, (n_in, n_out), n_in, n_out,
                            layer.weight_init, layer.dist, dtype)

    return {
        "Wq": dense(ks[0], d, h * (layer.nope_dim + layer.rope_dim)),
        "Wkva": dense(ks[1], d, layer.kv_rank + layer.rope_dim),
        "kv_norm": jnp.ones((layer.kv_rank,), dtype),
        "Wkvb": dense(ks[2], layer.kv_rank,
                      h * (layer.nope_dim + layer.v_dim)),
        "Wo": dense(ks[3], h * layer.v_dim, d),
    }


def _parent_apply(layer, params, x):
    """``MultiHeadLatentAttention.apply`` as it was (no dropout, no
    mask)."""
    bsz, t, _ = x.shape
    h, nope, rope = layer.n_heads, layer.nope_dim, layer.rope_dim
    q = (x @ params["Wq"]).reshape(bsz, t, h, nope + rope)
    kva = x @ params["Wkva"]
    c = rms_norm(kva[..., :layer.kv_rank], params["kv_norm"], layer.eps)
    k_r = kva[..., layer.kv_rank:]
    kvb = (c @ params["Wkvb"]).reshape(bsz, t, h, nope + layer.v_dim)
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_r[:, :, None, :], (bsz, t, h, rope))], -1)
    v = kvb[..., nope:]
    with jax.named_scope("mla.attend"):
        o = blocked_causal_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), layer.block)
    return o.transpose(0, 2, 1, 3).reshape(bsz, t, h * layer.v_dim) \
        @ params["Wo"]


def _latent(**fields):
    return MultiHeadLatentAttention(n_heads=2, nope_dim=16, rope_dim=8,
                                    v_dim=16, kv_rank=24, block=16, **fields)


def test_with_both_fields_off_the_layer_is_the_parent_s_bit_for_bit(
        monkeypatch):
    """The same leaves from the same key, the same output and the same
    program (jaxpr text) as the layer before PR 39, but for the three
    ``name`` equations the layer has put on q, k, v since PR 45 (that they
    lower to their operands is ``tests/test_zz_remat_keeps.py``'s)."""
    from deeplearning4j_tpu.nn.conf import attention as attention_layers
    layer, it = _latent(), InputType.recurrent(32, 40)
    params, state = layer.init(jax.random.key(3), it)
    want = _parent_init(layer, jax.random.key(3), it)
    assert list(params) == list(want) and state == {}
    for k in want:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(want[k]))
    x = jax.random.normal(jax.random.key(4), (2, 40, 32))
    np.testing.assert_array_equal(
        np.asarray(layer.apply(params, {}, x)[0]),
        np.asarray(_parent_apply(layer, params, x)))

    def program():
        return str(jax.make_jaxpr(lambda p, a: layer.apply(p, {}, a)[0])(
            params, x))

    assert program().count(" name[") == 3
    monkeypatch.setattr(attention_layers, "checkpoint_name",
                        lambda value, name: value)
    assert program() == str(jax.make_jaxpr(
        lambda p, a: _parent_apply(layer, p, a))(params, x))
    assert layer.regularizable() == ("Wq", "Wkva", "Wkvb", "Wo")


def _plain_latent(layer, p, x):
    """The layer's equations with whole score rows, float64-free plain
    jax.numpy: low-rank query, rotation on adjacent pairs by reshape."""
    bsz, t, _ = x.shape
    h, nope, rope, vd = (layer.n_heads, layer.nope_dim, layer.rope_dim,
                         layer.v_dim)

    def norm(a, g):
        return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True)
                                 + layer.eps) * g

    def turn(a):
        j = jnp.arange(rope // 2, dtype=jnp.float32)
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
            * layer.rope_theta ** (-2.0 * j / rope)
        ang = ang.reshape((1, t) + (1,) * (a.ndim - 3) + (rope // 2,))
        pair = a.reshape(a.shape[:-1] + (rope // 2, 2))
        re, im = pair[..., 0], pair[..., 1]
        return jnp.stack([re * jnp.cos(ang) - im * jnp.sin(ang),
                          re * jnp.sin(ang) + im * jnp.cos(ang)],
                         -1).reshape(a.shape)

    q = (norm(x @ p["Wqa"], p["q_norm"]) @ p["Wqb"]).reshape(
        bsz, t, h, nope + rope)
    kva = x @ p["Wkva"]
    c = norm(kva[..., :layer.kv_rank], p["kv_norm"])
    k_pe = turn(kva[..., layer.kv_rank:])
    kvb = (c @ p["Wkvb"]).reshape(bsz, t, h, nope + vd)
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_pe[:, :, None, :], (bsz, t, h, rope))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kvb[..., nope:])
    return o.reshape(bsz, t, h * vd) @ p["Wo"]


@pytest.fixture(scope="module")
def rotated():
    layer = _latent(q_rank=12, rope_theta=THETA)
    it = InputType.recurrent(32, 40)
    params, _ = layer.init(jax.random.key(5), it)
    params = {k: v + 0.1 * jax.random.normal(jax.random.key(i), v.shape)
              if v.ndim == 1 else v for i, (k, v) in enumerate(params.items())}
    x = jax.random.normal(jax.random.key(6), (2, 40, 32))
    return layer, params, x


def test_the_low_rank_query_and_the_rotation_follow_the_equations(rotated):
    layer, params, x = rotated
    assert list(params) == ["Wqa", "q_norm", "Wqb", "Wkva", "kv_norm",
                            "Wkvb", "Wo"]
    assert params["Wqa"].shape == (32, 12) and params["Wqb"].shape == (12, 48)
    assert layer.regularizable() == ("Wqa", "Wqb", "Wkva", "Wkvb", "Wo")
    with jax.default_matmul_precision("highest"):
        got = jax.jit(layer.apply)(params, {}, x)[0]
        want = jax.jit(_plain_latent, static_argnums=0)(layer, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # and it is not the layer without its rotation
    plain = jax.jit(dataclasses.replace(layer, rope_theta=0.0).apply)(
        params, {}, x)[0]
    assert float(jnp.max(jnp.abs(plain - got))) > 1e-2


@pytest.fixture(scope="module")
def rotated_gradients(rotated):
    """Both forms' gradients of one weighted sum, made once for the cases
    below (each reads one leaf of them)."""
    layer, params, x = rotated
    w = jax.random.normal(jax.random.key(9), (2, 40, 32))

    def through(f):
        def loss(p, a):
            return jnp.sum(f(p, a) * w)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))

    with jax.default_matmul_precision("highest"):
        return (through(lambda p, a: layer.apply(p, {}, a)[0])(params, x),
                through(lambda p, a: _plain_latent(layer, p, a))(params, x))


@pytest.mark.parametrize("leaf", ["Wqa", "q_norm", "Wqb", "Wkva", "kv_norm",
                                  "Wkvb", "Wo", "x"])
def test_every_gradient_of_the_rotated_layer_follows_the_equations(
        rotated_gradients, leaf):
    (gp, gx), (wp, wx) = rotated_gradients
    got, want = ((gx, wx) if leaf == "x" else (gp[leaf], wp[leaf]))
    assert float(jnp.max(jnp.abs(want))) > 0
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_the_layer_counts_its_fields_and_masks_its_output(rotated):
    layer, params, x = rotated
    before = {k: GLOBAL.counter(k) for k in ("attention.mla_q_lora",
                                             "attention.mla_rotary",
                                             "attention.mla_blocked")}
    mask = jnp.ones((2, 40)).at[1, 30:].set(0.0)
    out = jax.jit(lambda p, a: layer.apply(p, {}, a, mask=mask)[0])(params, x)
    for k, n in before.items():
        assert GLOBAL.counter(k) == n + 1, k
    assert float(jnp.max(jnp.abs(out[1, 30:]))) == 0.0
    assert float(jnp.max(jnp.abs(out[1, :30]))) > 0.0
    with pytest.raises(ValueError, match="even"):
        MultiHeadLatentAttention(rope_dim=7, rope_theta=1e4).output_type(
            InputType.recurrent(32, 8))
    # without the rotation an odd width is no one's business
    MultiHeadLatentAttention(rope_dim=7).output_type(InputType.recurrent(32, 8))


# ------------------------------------------------------------ the vertices
def test_the_time_shift_hands_position_i_the_value_of_i_plus_steps():
    x = jnp.arange(2 * 5 * 3, dtype=jnp.float32).reshape(2, 5, 3)
    out = TimeShiftVertex(steps=2).apply(x)
    np.testing.assert_array_equal(np.asarray(out[:, :3]), np.asarray(x[:, 2:]))
    assert float(jnp.max(jnp.abs(out[:, 3:]))) == 0.0
    ids = jnp.arange(10).reshape(2, 5)
    np.testing.assert_array_equal(
        np.asarray(TimeShiftVertex().apply(ids)),
        [[1, 2, 3, 4, 0], [6, 7, 8, 9, 0]])
    # the cotangent goes back one step later, the first position gets none
    w = jax.random.normal(jax.random.key(0), x.shape)
    g = jax.grad(lambda a: jnp.sum(TimeShiftVertex().apply(a) * w))(x)
    np.testing.assert_array_equal(np.asarray(g[:, 1:]), np.asarray(w[:, :-1]))
    assert float(jnp.max(jnp.abs(g[:, 0]))) == 0.0


@pytest.mark.parametrize("steps", [0, -1, 5, 9])
def test_a_time_shift_outside_the_sequence_raises(steps):
    with pytest.raises(ValueError, match="shift"):
        TimeShiftVertex(steps=steps).apply(jnp.zeros((1, 5, 2)))


def test_the_stack_vertex_puts_states_on_a_leading_axis():
    it = InputType.recurrent(6, 9)
    v = StackStatesVertex()
    out = v.output_type(it, it, it)
    assert out.passes == 3 and out.size == 6 and out.timeseries_length == 9
    assert out.example_shape(2) == (3, 2, 9, 6)
    a, b = jnp.ones((2, 9, 6)), jnp.zeros((2, 9, 6))
    stacked = v.apply(a, b)
    assert stacked.shape == (2, 2, 9, 6)
    np.testing.assert_array_equal(np.asarray(stacked[0]), np.asarray(a))
    with pytest.raises(ValueError, match="one type"):
        v.output_type(it, InputType.recurrent(7, 9))
    with pytest.raises(ValueError, match="one type"):
        v.output_type(out, out)


@pytest.mark.parametrize("vertex", [TimeShiftVertex(steps=3),
                                    StackStatesVertex()])
def test_the_new_vertices_go_through_json(vertex):
    assert vertex_from_dict(vertex_to_dict(vertex)) == vertex


# ---------------------------------------------------------------- the loss
def _plain_multi_token(x, w, ids, mask, weight):
    """L_main + weight / D * sum_k L_k with whole logits."""
    states, bsz, t, _ = x.shape
    m = jnp.ones((bsz, t)) if mask is None else mask
    count = jnp.sum(m)
    total = 0.0
    for k in range(states):
        logp = jax.nn.log_softmax(x[k] @ w, -1)
        want = jnp.pad(ids[:, k:], ((0, 0), (0, k)))
        keep = m * jnp.pad(m[:, k:], ((0, 0), (0, k)))
        ce = -jnp.take_along_axis(logp, want[..., None], -1)[..., 0]
        term = jnp.sum(ce * keep) / count
        total = total + (term if k == 0 else weight / (states - 1) * term)
    return total


@pytest.mark.parametrize("states,masked", [(2, False), (2, True), (3, False)],
                         ids=["one_module", "one_module_masked",
                              "two_modules"])
def test_the_multi_token_loss_is_the_two_terms_by_hand(states, masked):
    """Values and gradients; T = 50 in blocks of 16, so the last block is
    padded."""
    ks = jax.random.split(jax.random.key(states), 3)
    x = jax.random.normal(ks[0], (states, 2, 50, 8))
    w = jax.random.normal(ks[1], (8, 11))
    ids = jax.random.randint(ks[2], (2, 50), 0, 11)
    mask = jnp.ones((2, 50)).at[0, 40:].set(0.0) if masked else None

    def blocked(x, w):
        return lossfunctions.blocked_multi_token_mcxent(
            x, w, None, ids, mask, block=16, module_weight=0.3)

    with jax.default_matmul_precision("highest"):
        got, (gx, gw) = jax.value_and_grad(blocked, argnums=(0, 1))(x, w)
        want, (wx, ww) = jax.value_and_grad(
            lambda x, w: _plain_multi_token(x, w, ids, mask, 0.3),
            argnums=(0, 1))(x, w)
        forward_only = blocked(x, w)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(forward_only) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(wx), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ww), atol=1e-5)
    # a module's last positions have no label: no gradient reaches them
    for k in range(1, states):
        assert float(jnp.max(jnp.abs(gx[k, :, -k:]))) == 0.0
        assert float(jnp.max(jnp.abs(gx[k, :, :-k]))) > 0.0


def test_the_module_s_last_position_is_not_read():
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], (2, 1, 20, 8))
    w = jax.random.normal(ks[1], (8, 11))
    ids = jax.random.randint(ks[2], (1, 20), 0, 11)
    loss = lambda x: float(lossfunctions.blocked_multi_token_mcxent(
        x, w, None, ids, None, block=8))
    assert loss(x) == loss(x.at[1, :, -1].set(1e3))
    assert loss(x) != loss(x.at[1, :, -2].set(1e3))
    assert loss(x) != loss(x.at[0, :, -1].set(1e3))


def test_both_states_go_through_one_block_loop():
    """One ``scan`` over the (state, block) pairs under ``jax.grad``, so the
    head's gradient is summed in one carry."""
    x = jnp.ones((2, 1, 32, 4))
    w = jnp.ones((4, 5))
    ids = jnp.zeros((1, 32), jnp.int32)
    before = GLOBAL.counter("loss.blocked_one_pass")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, w: lossfunctions.blocked_multi_token_mcxent(
            x, w, None, ids, None, block=8), argnums=(0, 1)))(x, w)
    assert GLOBAL.counter("loss.blocked_one_pass") == before + 1
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 2 * 4


def _mtp_graph(weight=0.3):
    """embed -> norm -> (trunk state); the module: the next token's
    embedding beside the state, combined; one head over both."""
    g = GraphBuilder()
    g.add_inputs("ids")
    g.add_layer("embed", EmbeddingSequenceLayer(n_in=13, n_out=8), "ids")
    g.add_layer("final_norm", RMSNorm(eps=1e-6), "embed")
    g.add_vertex("mtp1_shift", TimeShiftVertex(), "embed")
    g.add_vertex("mtp1_in", StackStatesVertex(), "embed", "mtp1_shift")
    g.add_layer("mtp1_combine", MultiTokenCombine(), "mtp1_in")
    g.add_vertex("mtp1_add", ElementWiseVertex("add"), "mtp1_combine",
                 "embed")
    g.add_layer("mtp1_norm", RMSNorm(eps=1e-6), "mtp1_add")
    g.add_vertex("states", StackStatesVertex(), "final_norm", "mtp1_norm")
    g.add_layer("head", MultiTokenOutputLayer(
        n_out=13, time_block=8, module_weight=weight), "states")
    g.set_outputs("head")
    g.set_input_types(InputType.recurrent(13, 20))
    return ComputationGraph(g.build()).init(seed=1)


def test_the_output_layer_scores_and_answers_with_the_trunk():
    from deeplearning4j_tpu.datasets.dataset import DataSet

    net = _mtp_graph()
    ids = np.random.default_rng(0).integers(0, 13, (3, 21)).astype(np.int32)
    x, y = ids[:, :-1], ids[:, 1:]
    p = net.params

    def norm(a, g):
        return a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-6) * g

    emb = p["embed"]["W"][x]
    nxt = jnp.pad(emb[:, 1:], ((0, 0), (0, 1), (0, 0)))
    c = p["mtp1_combine"]
    m = jnp.concatenate([norm(emb, c["h_norm"]), norm(nxt, c["e_norm"])],
                        -1) @ c["W"]
    states = jnp.stack([norm(emb, p["final_norm"]["g"]),
                        norm(m + emb, p["mtp1_norm"]["g"])])
    want = _plain_multi_token(states, p["head"]["W"], jnp.asarray(y), None,
                              0.3)
    assert net.score_dataset(DataSet(x, y)) == pytest.approx(float(want),
                                                             rel=1e-5)
    # the answer is the TRUNK's softmax
    probs = net.output(x)[0]
    np.testing.assert_allclose(
        probs, np.asarray(jax.nn.softmax(states[0] @ p["head"]["W"], -1)),
        atol=1e-6)
    before = net.compile_watch.counter("mtp.modules")
    net.fit(DataSet(x, y))
    assert net.compile_watch.counter("mtp.modules") == before + 1
    assert net.compile_watch.counter("loss.blocked_one_pass") == 1
    assert float(net.score()) == pytest.approx(float(want), rel=1e-5)


def test_the_embedding_s_gradient_holds_both_uses():
    """The table is read by the trunk and, one step on, by the module: its
    gradient is the sum of both, and the module's part is what its weight
    scales."""
    nets = {w: _mtp_graph(w) for w in (0.0, 0.5, 1.0)}
    ids = np.random.default_rng(1).integers(0, 13, (2, 21)).astype(np.int32)
    x, y = [jnp.asarray(ids[:, :-1])], [jnp.asarray(ids[:, 1:])]

    def grad(w):
        net = nets[w]
        return jax.jit(jax.grad(lambda p: net._loss_fn(
            p, net.state, x, y, None, None, None)[0]))(nets[0.0].params)

    g0, g5, g1 = (grad(w)["embed"]["W"] for w in (0.0, 0.5, 1.0))
    module = g1 - g0
    assert float(jnp.max(jnp.abs(module))) > 1e-4
    np.testing.assert_allclose(np.asarray(g5), np.asarray(g0 + 0.5 * module),
                               atol=1e-6)
    # without the module's loss its own weights get no gradient
    assert float(jnp.max(jnp.abs(grad(0.0)["mtp1_combine"]["W"]))) == 0.0


def test_the_module_s_layers_refuse_a_single_state():
    one = InputType.recurrent(8, 20)
    with pytest.raises(ValueError, match="StackStatesVertex"):
        MultiTokenOutputLayer(n_out=5).output_type(one)
    with pytest.raises(ValueError, match="StackStatesVertex"):
        MultiTokenCombine().output_type(one)
    with pytest.raises(ValueError, match="two states"):
        MultiTokenCombine().output_type(dataclasses.replace(one, passes=3))
    assert MultiTokenCombine().output_type(
        dataclasses.replace(one, passes=2)) == one
    with pytest.raises(ValueError, match="sparse_mcxent"):
        MultiTokenOutputLayer(n_out=5, loss="mse").compute_score(
            None, {"x": None, "W": None}, None)


def test_a_graph_with_the_module_goes_through_json():
    from deeplearning4j_tpu.nn.conf.graph import (
        ComputationGraphConfiguration)
    conf = _mtp_graph().conf
    assert ComputationGraphConfiguration.from_json(conf.to_json()) == conf


# ------------------------------------------------------------ the builders
def _kimi_config(**over):
    cfg = dict(
        hidden_size=32, intermediate_size=64, vocab_size=40,
        num_hidden_layers=2, num_attention_heads=2, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        q_lora_rank=None, mla_use_nope=True, rope_theta=10000,
        rms_norm_eps=1e-5, first_k_dense_replace=1, moe_layer_freq=1,
        num_experts=4, num_experts_per_token=2, moe_intermediate_size=16,
        num_shared_experts=1, routed_scaling_factor=2.0,
        moe_router_activation_func="sigmoid", moe_renormalize=True,
        num_expert_group=1,
        linear_attn_config={"kda_layers": [1], "full_attn_layers": [2],
                            "num_heads": 2, "head_dim": 8,
                            "short_conv_kernel_size": 4})
    cfg.update(over)
    return cfg


@pytest.mark.parametrize("over,fields", [
    ({}, (0, 0.0)),
    ({"q_lora_rank": 12}, (12, 0.0)),
    ({"mla_use_nope": False}, (0, 10000.0)),
    ({"mla_use_nope": False, "q_lora_rank": 12, "rope_theta": 5e5},
     (12, 5e5))], ids=["nope", "q_lora", "rotated", "both"])
def test_the_kimi_builder_takes_a_low_rank_query_and_rotated_keys(over,
                                                                  fields):
    """Where it raised ``NotImplementedError`` it now builds the layer with
    those fields, and the model trains a step."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import KimiLinear

    zoo = KimiLinear(_kimi_config(**over), sequence_length=24,
                     attention_block=8, loss_block=8)
    net = ComputationGraph(zoo.conf()).init()
    layer = net.vertices["l2_attn"][0]
    assert (layer.q_rank, layer.rope_theta) == fields
    assert ("Wqa" in net.params["l2_attn"]) == bool(fields[0])
    ids = np.random.default_rng(0).integers(0, 40, (2, 25)).astype(np.int32)
    net.fit(DataSet(ids[:, :-1], ids[:, 1:]))
    assert np.isfinite(float(net.score()))


def test_the_kimi_builder_still_refuses_what_is_not_built():
    from deeplearning4j_tpu.models import KimiLinear

    for over in ({"rope_scaling": {"type": "yarn"}},
                 {"rope_interleave": False}):
        zoo = KimiLinear(_kimi_config(mla_use_nope=False, **over),
                         sequence_length=24)
        with pytest.raises(NotImplementedError, match="adjacent widths"):
            zoo.conf()


def _joyai_config(**over):
    cfg = dict(
        attention_bias=False, first_k_dense_replace=1, hidden_size=32,
        intermediate_size=64, kv_lora_rank=16, moe_intermediate_size=16,
        moe_layer_freq=1, n_group=1, n_routed_experts=8, n_shared_experts=1,
        norm_topk_prob=True, num_attention_heads=2, num_experts_per_tok=2,
        num_hidden_layers=2, num_nextn_predict_layers=1, q_lora_rank=12,
        qk_nope_head_dim=8, qk_rope_head_dim=4, rms_norm_eps=1e-6,
        rope_interleave=True, rope_scaling=None, rope_theta=32000000,
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
        v_head_dim=8, vocab_size=40)
    cfg.update(over)
    return cfg


@pytest.mark.parametrize("over", [
    {"n_group": 8}, {"topk_group": 4}, {"scoring_func": "softmax"},
    {"topk_method": "greedy"}, {"norm_topk_prob": False},
    {"rope_interleave": False}, {"rope_scaling": {"type": "yarn"}},
    {"attention_bias": True},
    {"num_nextn_predict_layers": 2}], ids=lambda o: next(iter(o)))
def test_the_joyai_builder_refuses_what_it_does_not_build(over):
    from deeplearning4j_tpu.models import JoyAIFlash

    with pytest.raises(NotImplementedError):
        JoyAIFlash(_joyai_config(**over))


def test_the_joyai_builder_ties_the_head_where_the_config_says_so():
    """Since PR 43 a field, not a raise: the ONE table is then the shifted
    embedding's, the gather's and both states' head (see
    ``tests/test_zz_short_conv_tied_head.py``)."""
    from deeplearning4j_tpu.models import JoyAIFlash

    conf = JoyAIFlash(_joyai_config(tie_word_embeddings=True)).conf()
    assert conf.vertices["head"][0].tied_to == "embed"
    assert JoyAIFlash(_joyai_config()).conf().vertices["head"][0].tied_to \
        == ""


@pytest.fixture(scope="module")
def joyai_net():
    from deeplearning4j_tpu.models import JoyAIFlash

    zoo = JoyAIFlash(_joyai_config(), experts_held=4, sequence_length=24,
                     attention_block=8, loss_block=8)
    return ComputationGraph(zoo.conf()).init()


def test_the_joyai_builder_names_the_module_s_vertices(joyai_net):
    net = joyai_net
    module = [n for n in net.order if n.startswith("mtp1_")]
    assert module == ["mtp1_shift", "mtp1_in", "mtp1_combine",
                      "mtp1_attn_norm", "mtp1_attn", "mtp1_attn_add",
                      "mtp1_ffn_norm", "mtp1_ffn", "mtp1_ffn_add",
                      "mtp1_norm"]
    assert net.vertices["mtp1_shift"][1] == ("embed",)
    assert net.vertices["mtp1_in"][1] == ("l2_ffn_add", "mtp1_shift")
    assert net.vertices["states"][1] == ("final_norm", "mtp1_norm")
    kinds = [type(net.vertices[n][0]).__name__
             for n in ("l1_ffn", "l2_ffn", "mtp1_ffn", "head")]
    assert kinds == ["GatedFeedForward", "RoutedExperts", "RoutedExperts",
                     "MultiTokenOutputLayer"]
    attn = net.vertices["mtp1_attn"][0]
    assert (attn.q_rank, attn.rope_theta, attn.rope_dim) == (12, 32e6, 4)
    experts = net.vertices["l2_ffn"][0]
    assert (experts.n_experts, experts.experts_held, experts.top_k,
            experts.scaling, experts.shared_size,
            experts.router_activation) == (8, 4, 2, 2.5, 16, "sigmoid")


def test_without_a_module_the_joyai_builder_ends_in_the_plain_head():
    from deeplearning4j_tpu.models import JoyAIFlash

    net = ComputationGraph(JoyAIFlash(
        _joyai_config(num_nextn_predict_layers=0), sequence_length=24).conf())
    assert not [n for n in net.order if n.startswith("mtp")]
    assert type(net.vertices["head"][0]).__name__ == "TokenOutputLayer"
    assert net.vertices["head"][1] == ("final_norm",)


def test_the_joyai_step_has_an_owner_for_all_it_emitted(joyai_net,
                                                        step_op_names):
    """The new vertices and the module's layers are owners like any other,
    and the new scopes lie under their layers' markers forward and
    backward."""
    from deeplearning4j_tpu.obs.owners import owner_of

    x = jax.ShapeDtypeStruct((2, 24), jnp.int32)
    names = step_op_names(joyai_net, [x], [x])
    assert [n for n in names if owner_of(n) is None] == []
    owners = {owner_of(n) for n in names}
    assert {"TimeShiftVertex", "StackStatesVertex", "MultiTokenCombine",
            "MultiHeadLatentAttention", "RoutedExperts", "RMSNorm", "loss",
            "optim"} <= owners
    for scope, marker in (("mla.q_lora", "MultiHeadLatentAttention:"),
                          ("mla.rope", "MultiHeadLatentAttention:"),
                          ("mla.attend", "MultiHeadLatentAttention:mtp1_attn"),
                          ("mtp.combine", "MultiTokenCombine:mtp1_combine"),
                          ("loss.blocked", ""), ("loss.multi_token", "")):
        under = [n for n in names if scope in n and marker in n]
        assert any("transpose(" not in n for n in under), scope
        if scope != "loss.multi_token":     # ids and weights: no cotangent
            assert any("transpose(" in n for n in under), scope

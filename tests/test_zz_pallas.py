"""Pallas kernel layer tests (perf/pallas/).

Named ``test_zz_*`` DELIBERATELY: the tier-1 command runs under a hard
870s timeout that cuts tests from the tail of the alphabetical order —
these additions must sort LAST so a timeout can only ever cut the new
tests, never evict older passing ones from the dots count.

Covers the PR-16 acceptance bars, all on CPU via Pallas interpret mode
(the measured step-time/HBM thresholds are the TPU round's):

- interpret-mode parity vs the XLA references: BN-train fwd+bwd through
  the ``fused_bn_act_train`` custom-VJP (f32 + bf16, with/without
  residual) and through a fused conv→BN→act network; ADC top-k ids
  identical and distances bitwise for PQ / IVF-PQ; int4 nibble-unpack
  exact (matmul and brute index);
- int4 WEIGHT serving (quant/lowering.py ``weight_bits=4``) behind the
  existing ``assert_accuracy_within`` gate, Pallas and XLA arms equal;
- fallback selection: XLA serves (and the ``kernel.xla_*`` counter
  records it) whenever kernels are disabled or the shape unsupported;
- the kernel choice is an autotuner candidate that rides TuningRecord
  (JSON round-trip, ``apply_tuning``, ``ParallelInference(tuning=...)``)
  into serving;
- a warmed retrieval ladder under forced-Pallas serves a burst with ZERO
  new compiles (CompileWatch-asserted).
"""

import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.convolutional import (ConvolutionLayer,
                                                      fused_bn_act_train)
from deeplearning4j_tpu.nn.conf.layers import (ActivationLayer, DenseLayer,
                                               OutputLayer)
from deeplearning4j_tpu.nn.conf.normalization import BatchNormalization
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.perf import pallas as pk
from deeplearning4j_tpu.perf.autotune import (TuningRecord, apply_tuning,
                                              autotune, build_network)
from deeplearning4j_tpu.quant import (accuracy_delta, assert_accuracy_within,
                                      calibrate, param_bytes, quantize)
from deeplearning4j_tpu.retrieval import (BruteForceIndex, IVFPQIndex,
                                          PQIndex, synthetic_corpus)

RNG = np.random.default_rng(16)


def _relerr(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-12)


def _fused_cnn_conf():
    return (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.05))
            .list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                    convolution_mode="same",
                                    activation="identity", has_bias=False))
            .layer(BatchNormalization())
            .layer(ActivationLayer(activation="relu"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 3))
            .build().fused())


# ------------------------------------------------------ BN kernel parity
class TestBnParity:
    @pytest.mark.parametrize("dtype,with_res", [
        (jnp.float32, False), (jnp.float32, True),
        (jnp.bfloat16, False), (jnp.bfloat16, True),
    ])
    def test_fwd_bwd_parity_vs_xla_reference(self, dtype, with_res):
        """fused_bn_act_train forward outputs AND the custom-VJP grads
        match the XLA reference under interpret mode; dispatch is eager
        here so each arm re-resolves selection per call."""
        n, h, w, c = 3, 5, 4, 160  # c=160: single-block channel tile
        z = jnp.asarray(RNG.standard_normal((n, h, w, c)), dtype)
        res = (jnp.asarray(RNG.standard_normal((n, h, w, c)), dtype)
               if with_res else None)
        gamma = jnp.asarray(RNG.standard_normal(c), jnp.float32)
        beta = jnp.asarray(RNG.standard_normal(c), jnp.float32)

        def loss(z, gamma, beta, res):
            out, mean, var = fused_bn_act_train(
                "relu", 1e-5, z, gamma, beta, res)
            return (jnp.sum(out.astype(jnp.float32) ** 2), (out, mean, var))

        argnums = (0, 1, 2, 3) if with_res else (0, 1, 2)
        grad_fn = jax.grad(loss, argnums=argnums, has_aux=True)
        results = {}
        for flag in (False, True):
            with pk.override(enabled=flag):
                out, mean, var = loss(z, gamma, beta, res)[1]
                grads, _ = grad_fn(z, gamma, beta, res)
                results[flag] = (out, mean, var) + tuple(grads)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        for ref, got in zip(results[False], results[True]):
            assert got.dtype == ref.dtype
            assert _relerr(ref, got) <= tol, (ref.dtype, _relerr(ref, got))
        # O(C) stats are f32 both ways: tight even for bf16 inputs
        for i in (1, 2):
            assert _relerr(results[False][i], results[True][i]) <= 1e-5

    def test_fused_network_loss_and_grads_parity(self):
        """The whole FusedConvBNActivation train path — conv + BN-train +
        activation + loss — agrees between kernel arms."""
        net = MultiLayerNetwork(_fused_cnn_conf()).init()
        x = RNG.standard_normal((4, 8, 8, 3)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[RNG.integers(0, 3, 4)]

        def f(p):
            return net._loss_fn(p, net.state, x, y, None, None, None)[0]

        out = {}
        for flag in (False, True):
            with pk.override(enabled=flag):
                out[flag] = jax.jit(jax.value_and_grad(f))(net.params)
        loss_ref, grads_ref = out[False]
        loss_pk, grads_pk = out[True]
        assert _relerr(loss_ref, loss_pk) <= 1e-5
        flat_ref = jax.tree_util.tree_leaves(grads_ref)
        flat_pk = jax.tree_util.tree_leaves(grads_pk)
        assert len(flat_ref) == len(flat_pk)
        for a, b in zip(flat_ref, flat_pk):
            assert _relerr(a, b) <= 1e-4

    def test_unsupported_shape_falls_back(self):
        # 1-D z is below the kernel's support floor: XLA must serve it,
        # with identical results either way
        z = jnp.asarray(RNG.standard_normal(7), jnp.float32)
        g = jnp.ones((7,), jnp.float32)
        b = jnp.zeros((7,), jnp.float32)
        with pk.override(enabled=True):
            on = fused_bn_act_train("identity", 1e-5, z, g, b, None)
        off = fused_bn_act_train("identity", 1e-5, z, g, b, None)
        for a, r in zip(on, off):
            assert np.array_equal(np.asarray(a), np.asarray(r))


# --------------------------------------------------- retrieval kernel parity
class TestRetrievalParity:
    def _arms(self, make_index, queries, k):
        outs = {}
        for flag in (False, True):
            ix = make_index()
            with pk.override(enabled=flag):
                outs[flag] = ix.search(queries, k)
        return outs[False], outs[True]

    def test_pq_adc_ids_identical_distances_bitwise(self):
        V, Q = synthetic_corpus(500, 16, n_clusters=10, seed=0, queries=8)
        ref, got = self._arms(lambda: PQIndex(V, M=4, ksub=16), Q, 10)
        assert np.array_equal(ref[0], got[0])
        assert np.array_equal(ref[1], got[1])

    def test_ivf_pq_adc_ids_identical_distances_bitwise(self):
        V, Q = synthetic_corpus(600, 16, n_clusters=12, seed=1, queries=8)
        ref, got = self._arms(
            lambda: IVFPQIndex(V, M=4, ksub=16, n_cells=8, nprobe=3), Q, 10)
        assert np.array_equal(ref[0], got[0])
        assert np.array_equal(ref[1], got[1])

    def test_int4_brute_bitwise(self):
        V, Q = synthetic_corpus(400, 24, n_clusters=8, seed=2, queries=8)
        ref, got = self._arms(lambda: BruteForceIndex(V, int4=True), Q, 10)
        assert np.array_equal(ref[0], got[0])
        assert np.array_equal(ref[1], got[1])

    def test_int4_matmul_exact_vs_host_unpack(self):
        from deeplearning4j_tpu.perf.pallas import adc as pk_adc
        from deeplearning4j_tpu.quant.pack import quantize_int4, \
            unpack_nibbles
        d = 33  # odd width: the padded last nibble must not leak
        table = RNG.standard_normal((50, d)).astype(np.float32)
        packed, _, _ = quantize_int4(table)
        qq = jnp.asarray(RNG.integers(-127, 128, (6, d)), jnp.int8)
        with pk.override(enabled=True):
            got = np.asarray(pk_adc.int4_matmul(qq, jnp.asarray(packed), d))
        codes = unpack_nibbles(packed, d)
        want = np.asarray(qq, np.int32) @ np.asarray(codes, np.int32).T
        assert got.dtype == np.int32
        assert np.array_equal(got, want)


# ------------------------------------------------- int4 weight serving
def test_int4_weight_serving_accuracy_gate_and_kernel_parity():
    """Satellite 1: packed int4 weights through the QuantizedLayer
    lowering — halves int8 param bytes, passes the existing accuracy
    gate, and the Pallas in-kernel unpack serves bitwise-identically to
    the XLA reference."""
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.05))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(DenseLayer(n_out=32, activation="tanh"))
            .layer(OutputLayer(n_out=4, loss="mcxent"))
            .set_input_type(InputType.feed_forward(12)).build())
    net = MultiLayerNetwork(conf).init()
    from deeplearning4j_tpu.datasets.dataset import DataSet
    data = [DataSet(RNG.standard_normal((16, 12)).astype(np.float32),
                    np.eye(4, dtype=np.float32)[RNG.integers(0, 4, 16)])
            for _ in range(4)]
    for d in data:
        net.fit(d)
    rec = calibrate(net, (d.features for d in data))
    q8 = quantize(net, rec)
    q4 = quantize(net, rec, weight_bits=4)
    for p in q4.params:
        assert np.asarray(p["Wq"]).dtype == np.int8  # packed nibbles
    # packed nibbles halve the weight-table bytes vs int8
    assert param_bytes(q4) < 0.75 * param_bytes(q8)
    assert_accuracy_within(accuracy_delta(net, q4, data),
                           top1_budget=0.05, loss_budget=0.2)
    # kernel arms agree bitwise on the served logits (fresh trace per arm)
    x = data[0].features
    ref = np.asarray(quantize(net, rec, weight_bits=4).output(x))
    with pk.override(enabled=True):
        got = np.asarray(quantize(net, rec, weight_bits=4).output(x))
    assert np.array_equal(ref, got)

    with pytest.raises(ValueError):
        quantize(net, rec, weight_bits=5)


# ------------------------------------------------ selection + counters
class TestSelectionAndCounters:
    def test_auto_off_on_cpu_and_env_configure_precedence(self):
        assert not pk.enabled()  # CPU backend, no env/configure: auto-off
        assert pk.interpret()    # ...and interpret mode off-TPU
        try:
            pk.configure(enabled=True)
            assert pk.enabled()
        finally:
            pk.configure(enabled=None)
        assert not pk.enabled()

    def test_take_records_dispatch_counters_both_ways(self):
        from deeplearning4j_tpu.perf.compile_watch import GLOBAL
        base_x = GLOBAL.counter("kernel.xla_bn_act")
        base_p = GLOBAL.counter("kernel.pallas_bn_act")
        with pk.override(enabled=True):
            assert pk.take("bn_act") is True
            assert pk.take("bn_act", supported=False) is False
        with pk.override(enabled=False):
            assert pk.take("bn_act") is False
        assert GLOBAL.counter("kernel.pallas_bn_act") == base_p + 1
        assert GLOBAL.counter("kernel.xla_bn_act") == base_x + 2

    def test_index_dispatch_lands_on_owning_watch(self):
        V, Q = synthetic_corpus(300, 16, n_clusters=6, seed=3, queries=4)
        ix = PQIndex(V, M=4, ksub=16)
        with pk.override(enabled=False):
            ix.search(Q, 5)
        with pk.override(enabled=True):
            ix.search(Q, 5)
        counts = ix.compile_watch.counters("kernel.")
        assert counts.get("kernel.xla_adc_pq", 0) >= 1
        assert counts.get("kernel.pallas_adc_pq", 0) >= 1

    def test_kernel_select_rejects_unknown_family(self):
        with pytest.raises(KeyError):
            pk.kernel_select("nope", lambda: None, lambda: None)

    def test_candidate_flags_follow_servability(self):
        # CPU + auto-off: no arms (the search space stays untouched)...
        assert pk.candidate_flags() == ()
        # ...forced on (the CPU-CI case): off-vs-on becomes searchable
        with pk.override(enabled=True):
            assert pk.candidate_flags() == (False, True)

    def test_selection_snapshot_covers_every_family(self):
        with pk.override(enabled=True):
            snap = pk.selection_snapshot()
        assert set(snap) == set(pk.FAMILIES)
        assert set(snap.values()) == {"pallas"}
        assert set(pk.selection_snapshot().values()) == {"xla"}


# --------------------------------------- autotuner / TuningRecord riding
def test_tuning_record_rides_pallas_choice_into_serving():
    """The kernel choice is a searched autotuner arm; the winner rides
    TuningRecord (JSON round-trip) through apply_tuning and
    ParallelInference so replicas inherit it without re-searching."""
    from deeplearning4j_tpu.parallel import ParallelInference

    conf = _fused_cnn_conf()
    with pk.override(enabled=True):  # make the arms searchable on CPU
        rec = autotune(conf, batch_sizes=(4,), top_k=1, reps=1,
                       max_serving_batch=8)
    assert rec.pallas_kernels in (True, False)
    rt = TuningRecord.from_json(rec.to_json())
    assert rt == rec and rt.pallas_kernels == rec.pallas_kernels
    assert json.loads(rec.to_json())["pallas_kernels"] == rec.pallas_kernels

    try:
        apply_tuning(conf, rec)
        assert pk.enabled() == rec.pallas_kernels

        pk.configure(enabled=None)  # serving must re-apply it itself
        net = build_network(conf, rec).init()
        pi = ParallelInference(net, inference_mode="sequential")
        try:
            assert pk.enabled() == rec.pallas_kernels
            # the inherited ladder was warmed UNDER the record's kernel
            # selection: in-ladder traffic compiles nothing further
            before = net.compile_watch.compiles()
            for n in (1, 3, 8):
                out = pi.output(RNG.standard_normal((n, 8, 8, 3))
                                .astype(np.float32))
                assert out.shape == (n, 3)
            assert net.compile_watch.compiles() == before
        finally:
            pi.shutdown()
    finally:
        pk.configure(enabled=None)


def test_memory_plan_snapshots_kernel_selection():
    from deeplearning4j_tpu.perf.planner import plan_memory
    conf = _fused_cnn_conf()
    with pk.override(enabled=True):
        plan = plan_memory(conf, budget_bytes=1 << 30, minibatch=4)
    assert plan.kernels == {fam: "pallas" for fam in pk.FAMILIES}
    assert "kernels:" in plan.summary()
    assert plan.to_dict()["kernels"] == plan.kernels


# -------------------------------------------- warmed ladder, zero compiles
def test_forced_pallas_warmed_ladder_serves_with_zero_compiles():
    V, Q = synthetic_corpus(800, 16, n_clusters=16, seed=4, queries=64)
    with pk.override(enabled=True):
        ix = PQIndex(V, M=4, ksub=16)
        ix.warmup(max_queries=64, ks=(1, 2, 4, 8, 10))
        c0 = ix.compile_watch.compiles()
        for lo in range(0, 64, 16):
            ids, _ = ix.search(Q[lo:lo + 16], 10)
            assert ids.shape == (16, 10)
        for n, k in ((1, 1), (7, 4), (33, 8)):  # pow2-padded in-ladder
            ix.search(Q[:n], k)
        assert ix.compile_watch.compiles() == c0
        assert ix.compile_watch.counters("kernel.")[
            "kernel.pallas_adc_pq"] >= 1


# ----------------------------------------------------- KDA's chunked scan
# perf/pallas/kda.py behind nn/conf/linear_attention.py::chunked_kda. The
# recurrence, the keys that point the same way and the gradients against a
# scan over tokens are tests/test_sequence_layers.py's, run over both
# executions; here the kernels' hand-written pieces are held to jax.vjp of
# the jax.numpy form, and the selection to what it says.
from deeplearning4j_tpu.nn.conf import linear_attention as la  # noqa: E402
from deeplearning4j_tpu.perf.pallas import kda  # noqa: E402


@pytest.fixture
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _kda_chunk(seed, decay, c=64, kd=128):
    ks = jax.random.split(jax.random.key(seed), 8)
    q = 0.3 * jax.random.normal(ks[0], (c, kd))
    k = jax.random.normal(ks[1], (c, kd))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (c, kd))
    g = -decay * jax.random.uniform(ks[3], (c, kd))
    b = jax.random.uniform(ks[4], (c, 1))
    state = jax.random.normal(ks[5], (kd, kd))      # (V, K), as the kernels
    do = jax.random.normal(ks[6], (c, kd))
    dstate = jax.random.normal(ks[7], (kd, kd))
    return q, k, v, g, b, state, do, dstate


def _close(got, want, tol=2e-5):
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    assert float(jnp.max(jnp.abs(got - want))) < tol * scale


def _jnp_chunk(q, k, v, g, b, state):
    """A chunk as ``chunked_kda`` computes it: ``_chunk_terms`` then
    ``_state_step``, the state carried transposed as the kernels do."""
    terms = la._chunk_terms(q, k, v, g, b[:, 0], sub=8)
    s, o = la._state_step(state.T, terms)
    return o, s.T


@pytest.mark.parametrize("decay", [0.05, 1.0, 40.0])
def test_kda_chunk_forward_and_backward_are_chunk_terms_and_step(
        decay, exact_products):
    q, k, v, g, b, state, do, dstate = _kda_chunk(1, decay)
    want, vjp = jax.vjp(jax.jit(_jnp_chunk), q, k, v, g, b, state)
    o, exit_state, u, scores = jax.jit(kda.chunk_forward, static_argnums=6)(
        q, k, v, g, b, state, True)
    for a, w in zip((o, exit_state), want):
        _close(a, w)
    got = jax.jit(kda.chunk_backward, static_argnums=10)(
        q, k, v, g, b, state, u, scores, do, dstate, True)
    for a, w in zip(got, vjp((do, dstate))):
        assert np.all(np.isfinite(a))
        _close(a, w)


@pytest.mark.parametrize("decay", [0.05, 40.0])
def test_kda_scores_backward_is_the_vjp_of_decayed_scores(decay,
                                                          exact_products):
    q, k, _, g, _, _, _, _ = _kda_chunk(2, decay)
    c = q.shape[0]
    ks = jax.random.split(jax.random.key(5), 2)
    lower = jnp.tril(jnp.ones((c, c), bool))
    dp = jnp.where(lower, jax.random.normal(ks[0], (c, c)), 0.0)
    dkk = jnp.where(jnp.tril(lower, -1), jax.random.normal(ks[1], (c, c)),
                    0.0)
    g_cum = jnp.cumsum(g, 0)

    def scores(q, kx, k, g_cum):      # kx: k as the rows' factor
        return la._decayed_scores(jnp.stack([q, kx]), k, g_cum, 8)

    both, vjp = jax.vjp(jax.jit(scores), q, k, k, g_cum)
    want_q, want_kx, want_k, want_g = vjp(jnp.stack([dp, dkk]))
    p, kk_off, kk_cols = jax.jit(kda._chunk_terms, static_argnums=3)(
        q, k, g, True)[1:]
    _close(p, both[0])
    _close(kk_off + kda._placed(kk_cols), jnp.tril(both[1], -1))
    dq, dkx, dk = jax.jit(kda._scores_backward, static_argnums=5)(
        q, k, g_cum, dp, dkk, True)
    _close(dq, want_q)
    _close(dkx, want_kx)
    _close(dk, want_k)
    _close(q * dq + k * dkx - k * dk, want_g)


def test_kda_block_solves_are_solve_unit_lower_and_its_transpose(
        exact_products):
    """Keys nearly alike and b near 1: the system whose explicit inverse
    overflows. Forward substitution over blocks of 8, the diagonal blocks'
    inverse applied by substitution inside the block, against
    ``_solve_unit_lower`` (Neumann inverses of the blocks) and its vjp."""
    c, kd = 64, 128
    ks = jax.random.split(jax.random.key(7), 4)
    k = 0.95 * jax.random.normal(ks[0], (1, kd)) + 0.05 * jax.random.normal(
        ks[1], (c, kd))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    a = jnp.tril(0.97 * k @ k.T, -1)
    rhs, dx = (jax.random.normal(key, (c, kd)) for key in ks[2:])
    col_in_block, _ = kda._block_masks(c)
    inside = (col_in_block >= 0) & (col_in_block < 8)
    a_off = jnp.where(inside, 0.0, a)
    a_cols = [jnp.sum(jnp.where(col_in_block == i, a, 0.0), 1, keepdims=True)
              for i in range(8)]
    want, vjp = jax.vjp(jax.jit(lambda rhs: la._solve_unit_lower(a, rhs, 8)),
                        rhs)
    _close(jax.jit(kda._solve_lower)(a_off, a_cols, rhs), want, 5e-5)
    _close(jax.jit(kda._solve_upper)(a_off, a_cols, dx), vjp(dx)[0], 5e-5)


def _backward_that_solves_again(q, k, v, g, b, st, u, scores, do, dst, exact):
    """``chunk_backward`` as it was before PR 49: it takes nothing of the
    chunk from the forward pass, makes the chunk's terms again and runs the
    forward substitution a second time."""
    g_cum, p, kk_off, kk_cols = kda._chunk_terms(q, k, g, exact)
    resid = v - kda._dot(k * jnp.exp(g_cum), st, exact, kda._NT)
    u = kda._solve_lower(kk_off * b, [col * b for col in kk_cols], b * resid)
    return _kept_chunk_backward(q, k, v, g, b, st, u,
                                jnp.concatenate([p, kk_off], 1), do, dst,
                                exact)


_kept_chunk_backward = kda.chunk_backward


@pytest.mark.parametrize("heads,head_dim", [(2, 128), (3, 256)])
@pytest.mark.parametrize("dtype,exact", [(jnp.bfloat16, False),
                                         (jnp.float32, True)],
                         ids=["bfloat16", "exact"])
def test_kda_backward_kernel_reads_u_and_equals_the_solve_run_again(
        heads, head_dim, dtype, exact, monkeypatch):
    """The backward kernel's five outputs with ``u`` and the scores
    ``[P | kk_off]`` read from the forward kernel's outputs, against the same
    kernel with the parent's chunk rule (the chunk's terms and the solve made
    again from the chunk's inputs and entry state): equal, at two sequences
    of three chunks, heads of 128 and 256, bfloat16 operands with bfloat16
    products and float32 with exact ones. What is read is what the forward
    pass made (the patched rule ignores zeros handed in their place), and
    the form without ``save`` writes o alone."""
    shape = (2, 192, heads, head_dim)
    ks = jax.random.split(jax.random.key(49), 6)
    k = jax.random.normal(ks[1], shape)
    q, k, v = (a.astype(dtype) for a in (
        0.3 * jax.random.normal(ks[0], shape),
        k / jnp.linalg.norm(k, axis=-1, keepdims=True),
        jax.random.normal(ks[2], shape)))
    g = -jax.random.uniform(ks[3], shape)
    b = jax.random.uniform(ks[4], shape[:3])
    do = jax.random.normal(ks[5], shape)
    o, states, u, scores = kda._forward(q, k, v, g, b, True, exact, True)
    assert u.shape == (2, heads, 192, head_dim) and u.dtype == jnp.float32
    assert scores.shape == (2, heads, 192, 128) and scores.dtype == u.dtype
    assert np.array_equal(np.asarray(o), np.asarray(
        kda._forward(q, k, v, g, b, False, exact, True)))
    got = kda._backward(q, k, v, g, b, states, u, scores, do, exact, True)
    monkeypatch.setattr(kda, "chunk_backward", _backward_that_solves_again)
    # a fresh function: jax's trace cache would serve the trace above
    want = jax.jit(lambda *a: kda._backward.__wrapped__(*a, exact, True))(
        q, k, v, g, b, states, jnp.zeros_like(u), jnp.zeros_like(scores), do)
    for a, w in zip(got, want):
        assert a.dtype == w.dtype and np.all(np.isfinite(
            np.asarray(a, np.float32)))
        assert np.array_equal(np.asarray(a), np.asarray(w))


def _kda_layer_grads(layer, t, seed=0, dtype=jnp.float32, batch=1):
    it = InputType.recurrent(12, t)
    params, state = layer.init(jax.random.key(seed), it)
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    x = jax.random.normal(jax.random.key(seed + 1),
                          (batch, t, 12)).astype(dtype)

    def loss(params, x):
        out = layer.apply(params, state, x)[0]
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)


def _family_counters(family):
    """(``kernel.xla_<family>``, ``kernel.pallas_<family>``) so far."""
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    counters = GLOBAL.as_dict().get("counters", {})
    return (counters.get(f"kernel.xla_{family}", 0),
            counters.get(f"kernel.pallas_{family}", 0))


def _kda_counters():
    return _family_counters("kda_scan")


def test_kda_layer_is_untouched_where_the_kernels_do_not_apply():
    """Family off, and family on at a shape ``supported`` refuses (heads
    of 8, chunks of 16): the same program, bit for bit, and the
    ``kernel.xla_kda_scan`` counter says so both times."""
    layer = la.KimiDeltaAttention(n_heads=2, head_dim=8, chunk=16)
    before = _kda_counters()
    with pk.override(enabled=False):
        off = _kda_layer_grads(layer, 40)
    with pk.override(enabled=True, interpret=True):
        on = _kda_layer_grads(layer, 40)
    assert _kda_counters() == (before[0] + 2, before[1])
    for a, b in zip(jax.tree.leaves(off), jax.tree.leaves(on)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_kda_layer_counts_the_execution_it_took_and_agrees():
    """Heads of 128, 70 steps (no multiple of 64): the kernels serve when
    the family is on (``kernel.pallas_kda_scan``), ``jax.numpy`` when it is
    off, and the layer's output and gradients agree."""
    layer = la.KimiDeltaAttention(n_heads=2, head_dim=128, low_rank=8)
    before = _kda_counters()
    with jax.default_matmul_precision("highest"):
        with pk.override(enabled=False):
            off = _kda_layer_grads(layer, 70)
        assert _kda_counters() == (before[0] + 1, before[1])
        with pk.override(enabled=True, interpret=True):
            on = _kda_layer_grads(layer, 70)
        assert _kda_counters() == (before[0] + 1, before[1] + 1)
    for a, b in zip(jax.tree.leaves(off), jax.tree.leaves(on)):
        _close(a, b, 1e-4)
    assert "kda_scan" in pk.FAMILIES and "kda_scan" in pk.TPU_AUTO_FAMILIES
    assert not kda.supported(*[jnp.zeros((1, 64, 2, 128))] * 4,
                             jnp.zeros((1, 64, 2)), 32, 8)


def test_kda_kernels_take_two_sequences_of_heads_of_256(exact_products):
    """The widest head ``supported`` lets in, a batch of two, three heads
    (one grid step takes them all) and a length that is no multiple of
    the chunk: output and all five gradients against ``jax.numpy``."""
    ks = jax.random.split(jax.random.key(11), 5)
    shape = (2, 70, 3, 256)
    k = jax.random.normal(ks[1], shape)
    args = (0.3 * jax.random.normal(ks[0], shape),
            k / jnp.linalg.norm(k, axis=-1, keepdims=True),
            jax.random.normal(ks[2], shape),
            -jax.random.uniform(ks[3], shape),
            jax.random.uniform(ks[4], shape[:3]))

    def run(*a):
        o = la.chunked_kda(*a)
        return jnp.sum(jnp.sin(o)), o

    with pk.override(enabled=False):
        want = jax.jit(
            jax.value_and_grad(run, range(5), has_aux=True))(*args)
    with pk.override(enabled=True, interpret=True):
        assert kda.supported(*args, 64, 8)
        got = jax.jit(jax.value_and_grad(run, range(5), has_aux=True))(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b)


def test_kda_kernels_lower_under_the_layers_scan_scope():
    """``kda.device_ms_per_step`` and ``kda.scan_roofline_pct`` find their
    operations by ``op_name`` in the step's HLO text: every operation the
    forward and backward kernels (here their interpreted bodies) lower to
    has to carry the layer's scope and ``kda.scan``, in the backward pass
    too."""
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (GraphBuilder(NeuralNetConfiguration.builder().seed(3)
                         .updater(Sgd(learning_rate=0.05)))
            .add_inputs("in")
            .add_layer("kda1", la.KimiDeltaAttention(
                n_heads=2, head_dim=128, low_rank=8), "in")
            .add_layer("out", RnnOutputLayer(n_out=3, loss="mcxent"), "kda1")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(12, 64)).build())
    with pk.override(enabled=True, interpret=True):
        net = ComputationGraph(conf).init()
        x = jnp.zeros((1, 64, 12), jnp.float32)
        y = jnp.zeros((1, 64, 3), jnp.float32)
        step = net._get_jitted("train")
        hlo = step.lower(net.params, net.state, net.opt_state, net._rng,
                         [x], [y], None, None).compile().as_text()
    ops = [l for l in hlo.splitlines() if "op_name=" in l]
    for kernel, way in (("kda_scan_fwd", "jvp("), ("kda_scan_bwd",
                                                   "transpose(")):
        mine = [l for l in ops if kernel in l]
        assert len(mine) > 50, (kernel, len(mine))
        for l in mine:
            name = l.split('op_name="')[1].split('"')[0]
            assert "KimiDeltaAttention:kda1" in name and "kda.scan" in name
            assert way in name, name


# ------------------------------------------- blocked causal attention (PR 29)
# The kernels against the dense score matrix are
# tests/test_sequence_layers.py's, run over both executions; here the
# selection is held to what it says, and the kernels to the layer's scope.
from deeplearning4j_tpu.nn.conf import attention as att  # noqa: E402
from deeplearning4j_tpu.perf.pallas import attention as att_kernels  # noqa: E402


def _attention_counters():
    from deeplearning4j_tpu.perf.compile_watch import GLOBAL
    counters = GLOBAL.as_dict().get("counters", {})
    return (counters.get("kernel.xla_blocked_attention", 0),
            counters.get("kernel.pallas_blocked_attention", 0))


def _attention_out_and_grads(q, k, v, block, fn=None):
    fn = fn or att.blocked_causal_attention

    def run(*a):
        o = fn(*a, block)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        run, (0, 1, 2), has_aux=True))(q, k, v)
    return (o,) + grads


@pytest.mark.parametrize("shape,block,why", [
    ((2, 2, 256, 192, 128), 128, "family_off"),
    ((2, 2, 256, 192, 128), 128, "cpu_unforced"),
    ((2, 3, 100, 24, 16), 32, "odd_widths"),
    ((1, 2, 192, 192, 128), 96, "length_no_multiple_of_128"),
    ((1, 2, 128, 192, 128), 128, "one_tile"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_blocked_attention_is_untouched_where_the_kernels_do_not_apply(
        shape, block, why, dtype):
    """Family off, the CPU with nothing forced, and family on at a shape
    ``supported`` refuses: the ``jax.numpy`` form as it was before the
    kernels, output and the three gradients bit for bit, and the
    ``kernel.xla_blocked_attention`` counter says so."""
    b, h, t, dq, dv = shape
    ks = jax.random.split(jax.random.key(29), 3)
    q = jax.random.normal(ks[0], (b, h, t, dq), dtype)
    k = jax.random.normal(ks[1], (b, h, t, dq), dtype)
    v = jax.random.normal(ks[2], (b, h, t, dv), dtype)

    def before_the_kernels(q, k, v, block):
        # blocked_causal_attention as PR 26 wrote it
        t = q.shape[2]
        block = min(block, t)
        pad = (-t) % block
        if pad:
            q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                       for a in (q, k, v))
        return att._blocked_attention(q, k, v, block)[:, :, :t]

    want = _attention_out_and_grads(q, k, v, block, before_the_kernels)
    counted = _attention_counters()
    if why == "family_off":
        with pk.override(enabled=False):
            got = _attention_out_and_grads(q, k, v, block)
    elif why == "cpu_unforced":
        assert jax.default_backend() == "cpu" and not pk.enabled(
            "blocked_attention")
        got = _attention_out_and_grads(q, k, v, block)
    else:
        with pk.override(enabled=True, interpret=True):
            assert not att_kernels.supported(
                *(jnp.pad(a, ((0, 0), (0, 0), (0, (-t) % min(block, t)),
                              (0, 0))) for a in (q, k, v)), min(block, t))
            got = _attention_out_and_grads(q, k, v, block)
    assert _attention_counters() == (counted[0] + 1, counted[1])
    for a, b_ in zip(got, want):
        assert a.dtype == b_.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b_))


def test_blocked_attention_family_is_in_the_automatic_rule():
    assert "blocked_attention" in pk.FAMILIES
    assert "blocked_attention" in pk.TPU_AUTO_FAMILIES
    z = jnp.zeros((1, 2, 256, 192))
    with pk.override(enabled=True, interpret=True):
        assert att_kernels.supported(z, z, z[..., :128], 128)
        assert not att_kernels.supported(z, z, z[..., :128], 256)
        assert not att_kernels.supported(z, z[:, :1], z[..., :128], 128)
        assert not att_kernels.supported(
            z, z.astype(jnp.bfloat16), z[..., :128], 128)
        assert not att_kernels.supported(
            *[z.astype(jnp.float16)] * 2, z[..., :128].astype(jnp.float16),
            128)
        assert not att_kernels.supported(z[..., :100], z[..., :100],
                                         z[..., :128], 128)
    # selected by backend: nothing forced, a CPU interprets and is refused
    # by enabled(), not by the shape
    assert att_kernels.supported(z, z, z[..., :128], 128) == pk.interpret()


def test_blocked_attention_counters_reach_the_metrics_registry():
    """``kernel.pallas_blocked_attention`` / ``kernel.xla_blocked_attention``
    are counted once a call at trace time and surface on ``/metrics``
    through ``absorb_compile_watch``, like ``kernel.pallas_kda_scan``."""
    from deeplearning4j_tpu import obs
    z = jnp.ones((1, 2, 256, 64))
    before = _attention_counters()
    with pk.override(enabled=True, interpret=True):
        att.blocked_causal_attention(z, z, z, 128)
    with pk.override(enabled=False):
        att.blocked_causal_attention(z, z, z, 128)
    assert _attention_counters() == (before[0] + 1, before[1] + 1)
    registry = obs.MetricsRegistry()
    obs.absorb_compile_watch(registry)
    for impl, count in zip(("xla", "pallas"), _attention_counters()):
        gauge = registry.metric(f"jit_kernel_{impl}_blocked_attention")
        assert gauge is not None and gauge.value == count


@pytest.mark.parametrize("heads,t", [(5, 640), (8, 256)])
def test_attention_kernels_take_head_groups_and_many_tiles(
        heads, t, exact_products):
    """Head counts that a grid step takes one at a time (5 heads) and in
    groups (8 heads: all forward, 4 backward), five by five tiles of 128
    (every kind of pair: first, inner, diagonal) and one tile of 256:
    output and the three gradients against ``jax.numpy``."""
    ks = jax.random.split(jax.random.key(31), 3)
    q = jax.random.normal(ks[0], (1, heads, t, 64))
    k = jax.random.normal(ks[1], (1, heads, t, 64))
    v = jax.random.normal(ks[2], (1, heads, t, 256))
    with pk.override(enabled=False):
        want = _attention_out_and_grads(q, k, v, 128)
    with pk.override(enabled=True, interpret=True):
        assert att_kernels.supported(q, k, v, 128)
        got = _attention_out_and_grads(q, k, v, 128)
    for a, b in zip(got, want):
        _close(a, b)


def test_attention_kernels_lower_under_the_layers_attend_scope():
    """``mla.device_ms_per_step`` finds its operations by ``op_name`` in
    the step's HLO text: every operation the forward and backward kernels
    (here their interpreted bodies) lower to has to carry the layer's
    scope and ``mla.attend``, in the backward pass too; otherwise the
    metric reads a gain that is only operations gone missing."""
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (GraphBuilder(NeuralNetConfiguration.builder().seed(3)
                         .updater(Sgd(learning_rate=0.05)))
            .add_inputs("in")
            .add_layer("mla1", att.MultiHeadLatentAttention(
                n_heads=2, nope_dim=128, rope_dim=64, v_dim=128, kv_rank=16,
                block=128), "in")
            .add_layer("out", RnnOutputLayer(n_out=3, loss="mcxent"), "mla1")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(12, 256)).build())
    before = _attention_counters()
    with pk.override(enabled=True, interpret=True):
        net = ComputationGraph(conf).init()
        x = jnp.zeros((1, 256, 12), jnp.float32)
        y = jnp.zeros((1, 256, 3), jnp.float32)
        step = net._get_jitted("train")
        hlo = step.lower(net.params, net.state, net.opt_state, net._rng,
                         [x], [y], None, None).compile().as_text()
    assert _attention_counters() == (before[0], before[1] + 1)
    # a reduction's scalar reducer (``f32[] add(a, b)``) is no operation
    # of its own on a device and is named from the kernel's root
    ops = [l for l in hlo.splitlines() if "op_name=" in l
           and not re.search(r"= f32\[\] (add|maximum)\(", l)]
    for kernel, way in (("mla_attend_fwd", "jvp("),
                        ("mla_attend_bwd", "transpose(")):
        mine = [l for l in ops if kernel in l]
        assert len(mine) > 20, (kernel, len(mine))
        for l in mine:
            name = l.split('op_name="')[1].split('"')[0]
            assert ("MultiHeadLatentAttention:mla1" in name
                    and "mla.attend" in name), name
            assert way in name, name


# ------------------------------------- the delta-rule layers' input path
# perf/pallas/kda_inputs.py behind KimiDeltaAttention.apply and
# GatedDeltaNet.apply: convolution, SiLU, head norms and decay as one kernel
# forward and one backward that hand the scan heads-major operands. The
# jax.numpy lines of the layers are the reference. Last in the file: these
# are its heaviest cases, and the file's tail runs when the other files'
# host-timing tests are over.
from deeplearning4j_tpu.perf.pallas import kda_inputs  # noqa: E402

_INPUT_LAYERS = {
    "kda": la.KimiDeltaAttention(n_heads=2, head_dim=128, low_rank=8),
    # Gated DeltaNet's one product and one taps array, each q/k head
    # written to the two value heads it serves
    "gdn": la.GatedDeltaNet(n_key_heads=2, n_value_heads=4, head_dim=128),
}


def _inputs_counters():
    return _family_counters("kda_inputs")


@pytest.mark.parametrize("t", [192, 150],
                         ids=["three_tiles", "padded_150_to_192"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_fused_input_path_is_the_layers_jnp_lines(kind, dtype, t):
    """The layer with the ``kda_inputs`` kernels (and the scan on their
    heads-major operands) against its ``jax.numpy`` lines: the loss and the
    gradient of every parameter (the taps, ``A_log`` and ``dt_bias`` among
    them) and of the input, over a length of three time tiles and one that
    is padded; the counters say which path each call took. bfloat16 is
    held to its own rounding: the kernels skip the ``jax.numpy`` form's
    roundings after the convolution and the SiLU."""
    layer = _INPUT_LAYERS[kind]
    before = _inputs_counters()
    with jax.default_matmul_precision("highest"):
        with pk.override(enabled=False):
            want = _kda_layer_grads(layer, t, 5, dtype)
        assert _inputs_counters() == (before[0] + 1, before[1])
        with pk.override(enabled=True, interpret=True):
            got = _kda_layer_grads(layer, t, 5, dtype)
        assert _inputs_counters() == (before[0] + 1, before[1] + 1)
    names = [jax.tree_util.keystr(k)
             for k, _ in jax.tree_util.tree_flatten_with_path(want)[0]]
    assert any("conv" in n for n in names) and any("A_log" in n
                                                   for n in names)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for name, a, b in zip(names, jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        # the loss is a sum of t x 12 sines that cancel: held to their count
        scale = np.sqrt(12.0 * t) if a.ndim == 0 else np.linalg.norm(b)
        assert np.linalg.norm(a - b) / max(scale, 1e-30) < tol, name


def _kda_streams(t, seed=0, h=2, d=128, taps=4):
    ks = jax.random.split(jax.random.key(seed), 10)
    xs = tuple(jax.random.normal(ks[i], (1, t, h * d)) for i in range(4))
    ws = tuple(0.5 * jax.random.normal(ks[4 + i], (taps, h * d))
               for i in range(3))
    rows = (-jnp.exp(jax.random.normal(ks[7], (1, h * d))),
            jax.random.normal(ks[8], (1, h * d)))
    spec = kda_inputs.Spec(srcs=((0, 0, 0), (1, 1, 0), (2, 2, 0)),
                           decay=(3, 0), key_heads=h, rep=1, head_dim=d)
    return xs, ws, rows, spec


def _jnp_streams(xs, ws, rows, spec):
    """The layers' own lines on the streams, heads-major."""
    bsz, t, _ = xs[0].shape
    h, d = spec.key_heads, spec.head_dim

    def heads(a):
        return jnp.swapaxes(a.reshape(bsz, t, h, d), 1, 2)

    q, k, v = (jax.nn.silu(la.causal_depthwise_conv(x, w))
               for x, w in zip(xs, ws))
    g = rows[0][0] * jax.nn.softplus(xs[3] + rows[1][0])
    return (heads(la._l2norm(q.reshape(bsz, t, h, d)).reshape(q.shape))
            / math.sqrt(d),
            heads(la._l2norm(k.reshape(bsz, t, h, d)).reshape(k.shape)),
            heads(v), heads(g))


@pytest.mark.parametrize("t,row", [(192, 126), (1024, 510)],
                         ids=["three_tiles_of_64", "two_tiles_of_512"])
def test_fused_inputs_halo_is_causal_and_tiles_meet_row_for_row(t, row):
    """Three time tiles of one pass, and two of eight passes (four to a
    loop body): every row agrees with the unfused form (the rows after a
    tile's or a pass's edge take their earlier steps from the halo window
    or the rows before), and a change at ``row`` moves no output before it
    by a single bit, but moves it and the three rows after it, which lie
    across a tile's edge."""
    xs, ws, rows, spec = _kda_streams(t)
    with pk.override(enabled=True, interpret=True):
        assert kda_inputs.supported(jnp.float32, 1, t, spec, 4)
        got = kda_inputs.kda_inputs(xs, ws, rows, spec)
        bumped = tuple(x.at[0, row].add(1.0) for x in xs)
        moved = kda_inputs.kda_inputs(bumped, ws, rows, spec)
    for a, b in zip(got, _jnp_streams(xs, ws, rows, spec)):
        gap = jnp.max(jnp.abs(a - b), axis=(0, 1, 3))        # a row
        assert float(jnp.max(gap)) < 2e-5, np.argmax(np.asarray(gap))
    for a, b in zip(got[:3], moved[:3]):
        assert np.array_equal(np.asarray(a[:, :, :row]),
                              np.asarray(b[:, :, :row]))
        for r in range(row, row + 4):
            assert not np.array_equal(np.asarray(a[:, :, r]),
                                      np.asarray(b[:, :, r]))
    # the decay has no convolution: only its own row moves
    assert np.array_equal(np.asarray(got[3][:, :, row + 1:]),
                          np.asarray(moved[3][:, :, row + 1:]))


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_input_path_is_untouched_where_the_kernels_do_not_apply(kind):
    """Heads of 64 (``supported`` refuses) with the families on, and any
    shape on a CPU with nothing forced: the layer lowers to the text it
    lowers to with the families off, and ``kernel.xla_kda_inputs`` counts
    each call."""
    small = {"kda": la.KimiDeltaAttention(n_heads=2, head_dim=64, low_rank=8),
             "gdn": la.GatedDeltaNet(n_key_heads=2, n_value_heads=4,
                                     head_dim=64)}[kind]

    def text(layer):
        params, state = layer.init(jax.random.key(0),
                                   InputType.recurrent(12, 128))
        x = jnp.zeros((1, 128, 12))
        return jax.jit(lambda p, x: layer.apply(p, state, x)[0]).lower(
            params, x).as_text()

    before = _inputs_counters()
    with pk.override(enabled=False):
        off_small, off_wide = text(small), text(_INPUT_LAYERS[kind])
    with pk.override(enabled=True, interpret=True):
        assert text(small) == off_small
    assert text(_INPUT_LAYERS[kind]) == off_wide        # nothing forced
    assert _inputs_counters() == (before[0] + 4, before[1])
    assert ("kda_inputs" in pk.FAMILIES
            and "kda_inputs" in pk.TPU_AUTO_FAMILIES)


def _zoo_step(name):
    """The train step of a zoo model at the benchmark configuration's
    rehearsal size with delta-rule heads of 128, lowered (nothing runs)."""
    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", name + ".json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearse"]}
    cfg = {**cfg, **cfg["published"]}
    if name.startswith("kimi"):
        cfg["linear_attn_config"] = {**cfg["linear_attn_config"],
                                     "head_dim": 128}
        zoo = models.KimiLinear(cfg, layers=5, experts_held=4,
                                kda_low_rank=16, sequence_length=128,
                                attention_block=64, loss_block=64)
    else:
        cfg.update(linear_key_head_dim=128, linear_value_head_dim=128)
        zoo = models.Qwen3Next(cfg, layers=4, experts_held=4,
                               sequence_length=128, attention_block=64,
                               loss_block=64)
    net = ComputationGraph(zoo.conf()).init()
    ids = jnp.zeros((1, 128), jnp.int32)
    return net._get_jitted("train").lower(
        net.params, net.state, net.opt_state, net._rng, [ids], [ids], None,
        None)


@pytest.mark.parametrize("name,layers", [
    ("kimi_linear_48b_a3b_ep32", 4), ("qwen3_next_80b_a3b_ep16", 3)])
def test_zoo_steps_count_their_delta_rule_layers(name, layers):
    """The two zoo models' steps at small depth (the cells' cuts: four KDA
    layers of five, three Gated DeltaNet layers of four): every delta-rule
    layer takes the input kernels and the scan's, none the ``jax.numpy``
    form: 4 / 0 and 3 / 0, beside ``kernel.pallas_kda_scan`` 4 and 3."""
    before, scan_before = _inputs_counters(), _kda_counters()
    with pk.override(enabled=True, interpret=True):
        _zoo_step(name)
    assert _inputs_counters() == (before[0], before[1] + layers)
    assert _kda_counters() == (scan_before[0], scan_before[1] + layers)


@pytest.mark.parametrize("kind,cls,scope", [
    ("kda", "KimiDeltaAttention", "kda.conv"),
    ("gdn", "GatedDeltaNet", "gdn.conv")])
def test_input_kernels_lower_under_the_layers_conv_scope(kind, cls, scope):
    """``kda.device_ms_per_step`` / ``gdn.device_ms_per_step`` and the
    scope table find operations by ``op_name``: what the input kernels
    (here their interpreted bodies) lower to carries the layer's scope and
    ``kda.conv`` / ``gdn.conv``, forward and backward."""
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (GraphBuilder(NeuralNetConfiguration.builder().seed(3)
                         .updater(Sgd(learning_rate=0.05)))
            .add_inputs("in")
            .add_layer("mix1", _INPUT_LAYERS[kind], "in")
            .add_layer("out", RnnOutputLayer(n_out=3, loss="mcxent"), "mix1")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(12, 64)).build())
    with pk.override(enabled=True, interpret=True):
        net = ComputationGraph(conf).init()
        x = jnp.zeros((1, 64, 12), jnp.float32)
        y = jnp.zeros((1, 64, 3), jnp.float32)
        hlo = net._get_jitted("train").lower(
            net.params, net.state, net.opt_state, net._rng, [x], [y], None,
            None).compile().as_text()
    ops = [l for l in hlo.splitlines() if "op_name=" in l
           and not re.search(r"= f32\[\] (add|maximum)\(", l)]
    for kernel, way in (("kda_inputs_fwd", "jvp("),
                        ("kda_inputs_bwd", "transpose(")):
        mine = [l for l in ops if kernel in l]
        assert len(mine) > 20, (kernel, len(mine))
        for l in mine:
            name = l.split('op_name="')[1].split('"')[0]
            assert f"{cls}:mix1" in name and scope in name, name
            assert way in name, name


# ------------------------------------------- the state-space scan (PR 47)
# ``chunked_ssd``'s jax.numpy form against the token-by-token recurrence is
# tests/test_zz_state_space.py's; here the kernels (interpreted) are held to
# that form, the selection to what it says, and the kernels to the layer's
# scope.
from deeplearning4j_tpu.nn.conf import state_space as ssm  # noqa: E402
from deeplearning4j_tpu.perf.pallas import ssd  # noqa: E402
from test_zz_state_space import _operands  # noqa: E402


def _ssd_counters():
    return _family_counters("ssd_scan")


def _ssd_operands(t, decay, dtype=jnp.float32, groups=1, h=4, p=64, n=128):
    """tests/test_zz_state_space.py's seeded operands (steps that all but
    forget the state, ``near_zero``, all but keep it, ``near_one``, or both
    in one head, ``mixed``) at widths the kernels take, x, B and C in
    ``dtype``."""
    x, dt, a, bm, cm = _operands(t, groups, decay, h=h, p=p, n=n)
    return x.astype(dtype), dt, a, bm.astype(dtype), cm.astype(dtype)


def _ssd_out_and_grads(args, chunk):
    probe = jax.random.normal(jax.random.key(9), args[0].shape)

    def run(*a):
        y = ssm.chunked_ssd(*a, chunk=chunk)
        return jnp.sum(y * probe), y

    return jax.jit(jax.value_and_grad(run, range(5), has_aux=True))(*args)


@pytest.mark.parametrize("decay", ["mixed", "near_zero", "near_one"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("p,chunk,t", [(64, 128, 384), (128, 256, 512)],
                         ids=["pairs_of_64", "heads_of_128"])
def test_ssd_kernels_are_the_jnp_scan(p, chunk, t, dtype, decay):
    """Forward and EVERY gradient (x, dt, A, B, C) of ``ssd_scan`` against
    ``jax.vjp`` of the ``jax.numpy`` form, over several chunks (a carried
    state, a chunk boundary, dS carried back), two sequences, heads that
    share a lane tile and heads that fill one, a chunk of one factor block
    and of two (the skipped block above the diagonal): float32 to float32's
    rounding, bfloat16 to the products' (the ``jax.numpy`` form rounds its
    cotangents to bfloat16 where the kernels keep float32)."""
    args = _ssd_operands(t, decay, dtype, p=p)
    before = _ssd_counters()
    with pk.override(enabled=False):
        want = _ssd_out_and_grads(args, chunk)
    assert _ssd_counters() == (before[0] + 1, before[1])
    with pk.override(enabled=True, interpret=True):
        assert ssd.supported(*args, chunk)
        got = _ssd_out_and_grads(args, chunk)
    assert _ssd_counters() == (before[0] + 1, before[1] + 1)
    assert got[0][1].dtype == jnp.float32
    # near_zero: A's gradient is a sum of terms e-100 and smaller beside a
    # few of order one; both forms round it in the fourth digit
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-3 if decay == "near_zero" \
        else 3e-5
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        _close(a.astype(jnp.float32), b.astype(jnp.float32), tol)


@pytest.mark.parametrize("why,change", [
    ("a ragged length", dict(t=300)),
    ("heads of 32", dict(p=32)),
    ("an odd count of 64-wide heads", dict(h=3)),
    ("two groups", dict(groups=2)),
    ("a state of 64", dict(n=64)),
    ("a chunk of 64", dict(chunk=64))])
def test_ssd_scan_is_untouched_where_the_kernels_do_not_apply(why, change):
    """Family on at a shape ``supported`` refuses: ``chunked_ssd`` runs the
    ``jax.numpy`` form, the same program bit for bit, and counts
    ``kernel.xla_ssd_scan``."""
    chunk = change.pop("chunk", 128)
    args = _ssd_operands(**{"t": 256, "decay": "mixed", **change})
    before = _ssd_counters()
    with pk.override(enabled=False):
        off = _ssd_out_and_grads(args, chunk)
    with pk.override(enabled=True, interpret=True):
        assert not ssd.supported(*args, chunk), why
        on = _ssd_out_and_grads(args, chunk)
    assert _ssd_counters() == (before[0] + 2, before[1])
    for a, b in zip(jax.tree.leaves(off), jax.tree.leaves(on)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_ssd_supported_reads_types_and_the_backend():
    x, dt, a, bm, cm = _ssd_operands(256, "mixed")
    with pk.override(enabled=True, interpret=True):
        assert ssd.supported(x, dt, a, bm, cm, 128)
        assert not ssd.supported(x.astype(jnp.bfloat16), dt, a, bm, cm, 128)
        assert not ssd.supported(x.astype(jnp.float16), dt, a,
                                 bm.astype(jnp.float16),
                                 cm.astype(jnp.float16), 128)
        # the windows of 1,024 heads of 128 at a chunk of 512: over the limit
        wide = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (1, 512, 1024, 128), (1, 512, 1024), (1024,), (1, 512, 1, 128),
            (1, 512, 1, 128))]
        assert not ssd.supported(*wide, 512)
    with pk.override(interpret=False):           # a CPU that would compile
        assert not ssd.supported(x, dt, a, bm, cm, 128)
    assert "ssd_scan" in pk.FAMILIES and "ssd_scan" in pk.TPU_AUTO_FAMILIES


def _mixer_grads(layer, params, x):
    from deeplearning4j_tpu.nn.conf.layers import apply_layer

    def loss(p, xx):
        out = apply_layer(layer, p, {}, xx, train=True, rng=None, mask=None,
                          name="mix")[0]
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)


@pytest.mark.parametrize("remat", [None, "full"], ids=["plain", "remat"])
def test_a_mixer_gives_the_same_gradients_with_the_kernels(remat):
    """A ``Mamba2Mixer`` (rematerialised too: the forward kernel twice, the
    backward once, the entry states the custom-VJP's residuals) with the
    kernels and without: every leaf's gradient and the input's."""
    layer = ssm.Mamba2Mixer(n_heads=2, head_dim=64, state_size=128,
                            chunk=128, remat=remat)
    params, _ = layer.init(jax.random.key(0), InputType.recurrent(12, 256))
    x = jax.random.normal(jax.random.key(1), (2, 256, 12))
    before = _ssd_counters()
    with jax.default_matmul_precision("highest"):
        with pk.override(enabled=False):
            off = _mixer_grads(layer, params, x)
        assert _ssd_counters()[1] == before[1]
        with pk.override(enabled=True, interpret=True):
            on = _mixer_grads(layer, params, x)
    assert _ssd_counters()[1] > before[1]
    for a, b in zip(jax.tree.leaves(off), jax.tree.leaves(on)):
        _close(a, b, 1e-4)


def test_ssd_kernels_lower_under_the_layers_scan_scope():
    """``ssm.scan_device_ms_per_step`` and ``ssm.scan_roofline_pct`` find
    their operations by ``op_name`` in the step's HLO text: every operation
    the forward and backward kernels (here their interpreted bodies) lower
    to carries the layer's scope and ``ssm.scan``, in the backward pass
    too."""
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (GraphBuilder(NeuralNetConfiguration.builder().seed(3)
                         .updater(Sgd(learning_rate=0.05)))
            .add_inputs("in")
            .add_layer("l0_ssm", ssm.Mamba2Mixer(
                n_heads=2, head_dim=64, state_size=128, chunk=128), "in")
            .add_layer("out", RnnOutputLayer(n_out=3, loss="mcxent"),
                       "l0_ssm")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(12, 256)).build())
    with pk.override(enabled=True, interpret=True):
        net = ComputationGraph(conf).init()
        x = jnp.zeros((1, 256, 12), jnp.float32)
        y = jnp.zeros((1, 256, 3), jnp.float32)
        hlo = net._get_jitted("train").lower(
            net.params, net.state, net.opt_state, net._rng, [x], [y], None,
            None).compile().as_text()
    ops = [l for l in hlo.splitlines() if "op_name=" in l]
    for kernel, way in (("ssd_scan_fwd", "jvp("), ("ssd_scan_bwd",
                                                   "transpose(")):
        mine = [l for l in ops if kernel in l]
        assert len(mine) > 20, (kernel, len(mine))
        for l in mine:
            name = l.split('op_name="')[1].split('"')[0]
            assert "Mamba2Mixer:l0_ssm" in name and "ssm.scan" in name, name
            assert way in name, name


# ------------------------------------------ the selective scan (PR 51)
# ``chunked_selective_scan``'s ``lax`` form against the recurrence run step
# by step is tests/test_zz_selective_scan.py's; here the kernels
# (interpreted) are held to that form, the selection to what it says, and
# the kernels to the layer's scope.
from deeplearning4j_tpu.perf.pallas import selective_scan  # noqa: E402


def _selective_counters():
    return _family_counters("selective_scan")


def _selective_operands(decay, dtype=jnp.float32, t=512, c=128, n=16, b=2,
                        skip=True):
    """Seeded operands of a selective scan whose steps decay ``near_zero``
    (a block's product of decays underflows float32 many times over),
    ``near_one`` (the state all but kept over both blocks) or ``mixed``
    (channels of both kinds, long steps among short ones): x, B and C in
    ``dtype``, dt float32 and >= 0, A (channels, N) < 0, D or None."""
    k = jax.random.split(jax.random.key(0), 8)
    x = jax.random.normal(k[0], (b, t, c)).astype(dtype)
    dt = jnp.exp(jax.random.uniform(k[1], (b, t, c), minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    rate = {"near_zero": -400.0, "near_one": -1e-3, "mixed": -1.0}[decay]
    a = rate * jnp.exp(jax.random.uniform(k[2], (c, n), minval=-0.5,
                                          maxval=0.5))
    if decay == "mixed":
        dt = jnp.where(jax.random.bernoulli(k[3], 0.2, dt.shape), 30.0 * dt,
                       dt)
        a = jnp.where(jax.random.bernoulli(k[4], 0.3, (c, 1)), 300.0 * a, a)
    bm = jax.random.normal(k[5], (b, t, n)).astype(dtype)
    cm = jax.random.normal(k[6], (b, t, n)).astype(dtype)
    d = jax.random.normal(k[7], (c,)) if skip else None
    return x, dt, a, bm, cm, d


def _selective_out_and_grads(args):
    """y and the gradient of every operand that is there (x, dt, A, B, C
    and D where the call has one)."""
    probe = jax.random.normal(jax.random.key(9), args[0].shape)
    args = args if args[5] is not None else args[:5]

    def run(*a):
        y = ssm.chunked_selective_scan(
            *a[:5], chunk=64, skip=a[5] if len(a) > 5 else None)
        return jnp.sum(y * probe), y

    return jax.jit(jax.value_and_grad(run, range(len(args)),
                                      has_aux=True))(*args)


@pytest.mark.parametrize("decay", ["mixed", "near_zero", "near_one"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("c,skip", [(128, True), (384, True), (512, False)],
                         ids=["one_tile", "three_tiles", "a_wide_tile_no_skip"])
def test_selective_scan_kernels_are_the_lax_scan(c, skip, dtype, decay):
    """Forward and EVERY gradient (x, dt, A, B, C, D) of ``selective_scan``
    against ``jax.vjp`` of the ``lax`` form over two time blocks (a carried
    state, dS carried back, a block's states made again from its entry),
    two sequences, one channel tile, several (dB, dC and the states'
    scratch over tiles) and a tile of four lane tiles: the same float32
    operations but for the order of the sum over the states, so both types
    agree to float32's rounding (the cast of dx, dB and dC to bfloat16 is
    the same in both)."""
    args = _selective_operands(decay, dtype, c=c, skip=skip)
    before = _selective_counters()
    with pk.override(enabled=False):
        want = _selective_out_and_grads(args)
    assert _selective_counters() == (before[0] + 1, before[1])
    with pk.override(enabled=True, interpret=True):
        assert selective_scan.supported(*args)
        got = _selective_out_and_grads(args)
    assert _selective_counters() == (before[0] + 1, before[1] + 1)
    assert got[0][1].dtype == jnp.float32
    # near_zero: A's gradient is a sum of terms e-100 and smaller beside a
    # few of order one; bfloat16: one rounding of a cotangent's last bit
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-3 if decay == "near_zero" \
        else 3e-5
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
        _close(a.astype(jnp.float32), b.astype(jnp.float32), tol)


@pytest.mark.parametrize("why,change", [
    ("a ragged length", dict(t=300)),
    ("channels off a lane tile", dict(c=96)),
    ("a state of 4", dict(n=4)),
    ("float16", dict(dtype=jnp.float16)),
    ("a CPU that would compile", dict(interpret=False))])
def test_selective_scan_is_untouched_where_the_kernels_do_not_apply(why,
                                                                    change):
    """Family on at a call ``supported`` refuses: ``chunked_selective_scan``
    runs the ``lax`` form, the same program bit for bit, and counts
    ``kernel.xla_selective_scan``."""
    interpret = change.pop("interpret", True)
    args = _selective_operands(**{"decay": "mixed", "t": 256, **change})
    before = _selective_counters()
    with pk.override(enabled=False):
        off = _selective_out_and_grads(args)
    with pk.override(enabled=True, interpret=interpret):
        assert not selective_scan.supported(*args), why
        on = _selective_out_and_grads(args)
    assert _selective_counters() == (before[0] + 2, before[1])
    for a, b in zip(jax.tree.leaves(off), jax.tree.leaves(on)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_selective_scan_supported_reads_shapes_types_and_the_limit():
    x, dt, a, bm, cm, d = _selective_operands("mixed", t=256)
    with pk.override(enabled=True, interpret=True):
        assert selective_scan.supported(x, dt, a, bm, cm, d)
        assert selective_scan.supported(x, dt, a, bm, cm)
        # x, B and C alike; dt float32; A and D as wide as x
        assert not selective_scan.supported(x.astype(jnp.bfloat16), dt, a,
                                            bm, cm, d)
        assert not selective_scan.supported(x, dt.astype(jnp.bfloat16), a,
                                            bm, cm, d)
        assert not selective_scan.supported(x, dt, a[:64], bm, cm, d)
        assert not selective_scan.supported(x, dt, a, bm, cm, d[:64])
        # the cell's call, and 64 times its channels: over the VMEM limit
        S = jax.ShapeDtypeStruct
        cell = [S((1, 8192, 5120), jnp.bfloat16), S((1, 8192, 5120),
                                                    jnp.float32),
                S((5120, 16), jnp.float32), S((1, 8192, 16), jnp.bfloat16),
                S((1, 8192, 16), jnp.bfloat16), S((5120,), jnp.float32)]
        assert selective_scan.supported(*cell)
        wide = [S(tuple(5120 * 64 if k == 5120 else k for k in s.shape),
                  s.dtype) for s in cell]
        assert not selective_scan.supported(*wide)
    assert ("selective_scan" in pk.FAMILIES
            and "selective_scan" in pk.TPU_AUTO_FAMILIES)


@pytest.mark.parametrize("kind", ["plain", "share_scan", "remat"])
def test_a_mamba1_mixer_gives_the_same_gradients_with_the_kernels(kind):
    """A ``Mamba1Mixer`` plain, handing its scan on (``share_scan``: the
    scan's output has two readers) and rematerialised (the forward kernel
    twice, the backward once, the entry states the custom-VJP's residuals)
    with the kernels and without: the output, every leaf's gradient and
    the input's."""
    from deeplearning4j_tpu.nn.conf.layers import apply_layer
    layer = ssm.Mamba1Mixer(expand=2, state_size=16,
                            share_scan=kind == "share_scan",
                            remat="full" if kind == "remat" else None)
    params, _ = layer.init(jax.random.key(0), InputType.recurrent(64, 256))
    x = jax.random.normal(jax.random.key(1), (2, 256, 64))

    def loss(p, xx):
        out = apply_layer(layer, p, {}, xx, train=True, rng=None, mask=None,
                          name="mix")[0]
        total = sum(jnp.sum(jnp.sin(o.astype(jnp.float32)))
                    for o in jax.tree.leaves(out))
        return total, out

    def run():
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(params, x)

    before = _selective_counters()
    with jax.default_matmul_precision("highest"):
        with pk.override(enabled=False):
            off = run()
        assert _selective_counters()[1] == before[1]
        with pk.override(enabled=True, interpret=True):
            on = run()
    assert _selective_counters()[1] > before[1]
    assert len(jax.tree.leaves(off)) == len(jax.tree.leaves(on)) >= 12
    for a, b in zip(jax.tree.leaves(off), jax.tree.leaves(on)):
        _close(a, b, 1e-4)


def test_selective_scan_kernels_lower_under_the_layers_scan_scope():
    """``mamba1.scan_device_ms_per_step`` and ``mamba1.scan_roofline_pct``
    find their operations by ``op_name`` in the step's HLO text: every
    operation the forward and backward kernels (here their interpreted
    bodies) lower to carries the layer's scope and ``mamba1.scan``, in the
    backward pass too."""
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (GraphBuilder(NeuralNetConfiguration.builder().seed(3)
                         .updater(Sgd(learning_rate=0.05)))
            .add_inputs("in")
            .add_layer("l0_ssm", ssm.Mamba1Mixer(expand=2, state_size=16),
                       "in")
            .add_layer("out", RnnOutputLayer(n_out=3, loss="mcxent"),
                       "l0_ssm")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(64, 256)).build())
    before = _selective_counters()
    with pk.override(enabled=True, interpret=True):
        net = ComputationGraph(conf).init()
        x = jnp.zeros((1, 256, 64), jnp.float32)
        y = jnp.zeros((1, 256, 3), jnp.float32)
        hlo = net._get_jitted("train").lower(
            net.params, net.state, net.opt_state, net._rng, [x], [y], None,
            None).compile().as_text()
    assert _selective_counters() == (before[0], before[1] + 1)
    # but the bodies of the reducers (the interpreted sums over the states)
    ops = [l for l in hlo.splitlines() if "op_name=" in l
           and not re.search(r"= f32\[\] (add|maximum)\(", l)]
    for kernel, way in (("selective_scan_fwd", "jvp("),
                        ("selective_scan_bwd", "transpose(")):
        mine = [l for l in ops if kernel in l]
        assert len(mine) > 20, (kernel, len(mine))
        for l in mine:
            name = l.split('op_name="')[1].split('"')[0]
            assert "Mamba1Mixer:l0_ssm" in name and "mamba1.scan" in name, \
                name
            assert way in name, name

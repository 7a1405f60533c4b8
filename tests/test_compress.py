"""Compressed gradient collectives (parallel/compress.py): scheme
semantics, error feedback, adaptive-τ controller, convergence parity vs
dense, bitwise determinism, zero-host-sync trace guarantee, checkpoint /
kill-and-resume / sharded-reshard ride-along, obs metrics, and the
acceptance bar (≥4× byte reduction at the default threshold policy).
"""

import json
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis.trace_check import trace_check
from deeplearning4j_tpu.checkpoint import (CheckpointManager, FaultInjector,
                                           ObjectStoreBackend, train_until)
from deeplearning4j_tpu.checkpoint.sharded import (restore_from_payloads,
                                                   shard_zip_bytes,
                                                   simulated_shard_snapshots,
                                                   state_sha)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import GraphBuilder, MergeVertex
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import (Adam, Sgd, is_sgd_family,
                                                  normalize_optimization_algo,
                                                  updater_has_accumulating_state)
from deeplearning4j_tpu.parallel.compress import (GradientCompression,
                                                  Int8Compression,
                                                  OneBitCompression,
                                                  ThresholdCompression,
                                                  TopKCompression,
                                                  compression_stats,
                                                  enable_grad_compression,
                                                  ensure_compress_state,
                                                  measure_compression_overhead)
from deeplearning4j_tpu.parallel.trainer import ClusterTrainer, ParallelWrapper

ALL_SCHEMES = [
    ThresholdCompression(target_sparsity=0.05),
    TopKCompression(ratio=0.05),
    Int8Compression(),
    OneBitCompression(),
]


def _net(seed=7, updater=None):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(updater or Sgd(learning_rate=0.05))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _graph(seed=5):
    conf = (GraphBuilder()
            .add_inputs("in")
            .add_layer("d1", DenseLayer(n_out=12, activation="relu"), "in")
            .add_layer("d2", DenseLayer(n_out=12, activation="tanh"), "in")
            .add_vertex("merge", MergeVertex(), "d1", "d2")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent",
                                          updater=Adam(0.02)), "merge")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(4))
            .build())
    return ComputationGraph(conf).init()


def _batches(n=160, batch=32, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random((n, 4), np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y).split(batch), DataSet(x, y)


def _leaves(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


def _assert_bitwise(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


# ============================================================ scheme units
class TestThresholdScheme:
    def test_encode_decode_semantics(self):
        """DL4J's scheme: |v| >= tau encodes as sign(v)*tau; the residual
        carries exactly what decode dropped."""
        s = ThresholdCompression(threshold=0.1, adaptive=False)
        g = {"W": jnp.asarray([0.5, -0.5, 1e-4, 0.09])}
        state = s.init_state(g)
        dec, new = s.apply(g, state)
        np.testing.assert_allclose(np.asarray(dec["W"]),
                                   [0.1, -0.1, 0.0, 0.0])
        np.testing.assert_allclose(np.asarray(new["residual"]["W"]),
                                   [0.4, -0.4, 1e-4, 0.09], rtol=1e-6)

    def test_error_feedback_accumulates(self):
        """A sub-threshold gradient applied repeatedly crosses tau through
        the residual — nothing is permanently lost."""
        s = ThresholdCompression(threshold=0.1, adaptive=False)
        g = {"W": jnp.asarray([0.04])}
        state = s.init_state(g)
        passed = []
        for _ in range(6):
            dec, state = s.apply(g, state)
            passed.append(float(np.asarray(dec["W"][0])))
        # 0.04/step accumulates; by step 3 the residual+g >= 0.1
        assert any(p > 0 for p in passed)
        assert passed[0] == 0.0  # first step below tau

    def test_adaptive_tau_moves_toward_target(self):
        # everything above tau -> ratio 1.0 >> target -> tau grows
        s = ThresholdCompression(threshold=0.01, target_sparsity=0.01)
        g = {"W": jnp.full((64,), 0.5)}
        state = s.init_state(g)
        _, state = s.apply(g, state)
        assert float(np.asarray(state["ctrl"]["tau"])) > 0.01
        # nothing above tau -> ratio 0 << target -> tau shrinks
        s2 = ThresholdCompression(threshold=0.5, target_sparsity=0.5)
        g2 = {"W": jnp.full((64,), 1e-6)}
        st2 = s2.init_state(g2)
        _, st2 = s2.apply(g2, st2)
        assert float(np.asarray(st2["ctrl"]["tau"])) < 0.5

    def test_tau_clamped_to_bounds(self):
        s = ThresholdCompression(threshold=0.9, target_sparsity=0.9,
                                 max_threshold=1.0)
        g = {"W": jnp.full((64,), 5.0)}
        state = s.init_state(g)
        for _ in range(8):
            _, state = s.apply(g, state)
        assert float(np.asarray(state["ctrl"]["tau"])) <= 1.0

    def test_wire_accounting_dual_encoding(self):
        """Sparse form (4B/index + header) when sparse, bitmap form
        (2 bits/elt + header) when dense — whichever is smaller."""
        s = ThresholdCompression(threshold=0.1, adaptive=False)
        n = 160
        v = np.zeros(n, np.float32)
        v[:2] = 1.0  # 2 encoded -> sparse wins: 4*2+16=24 < 160/16*4+16=56
        g = {"W": jnp.asarray(v)}
        _, st = s.apply(g, s.init_state(g))
        assert float(np.asarray(st["acc"]["last_wire_bytes"])) == 24.0
        v[:] = 1.0   # all encoded -> bitmap wins: 56
        g = {"W": jnp.asarray(v)}
        _, st = s.apply(g, s.init_state(g))
        assert float(np.asarray(st["acc"]["last_wire_bytes"])) == 56.0
        assert float(np.asarray(st["acc"]["dense_bytes"])) == 4.0 * n


class TestTopKScheme:
    def test_keeps_k_largest_with_values(self):
        s = TopKCompression(ratio=0.25, min_k=1, error_feedback=True)
        v = jnp.asarray([0.1, -3.0, 0.2, 2.0, -0.05, 0.0, 1.0, 0.3])
        g = {"W": v}
        dec, st = s.apply(g, s.init_state(g))
        np.testing.assert_allclose(
            np.asarray(dec["W"]), [0, -3.0, 0, 2.0, 0, 0, 0, 0])
        assert float(np.asarray(st["acc"]["last_wire_bytes"])) == 8.0 * 2 + 16

    def test_zero_gradient_encodes_nothing(self):
        s = TopKCompression(ratio=0.5)
        g = {"W": jnp.zeros(16)}
        dec, st = s.apply(g, s.init_state(g))
        assert float(np.asarray(st["acc"]["last_wire_bytes"])) == 16.0
        np.testing.assert_array_equal(np.asarray(dec["W"]), np.zeros(16))


class TestQuantizedSchemes:
    def test_int8_roundtrip_error_bounded_by_half_step(self):
        rng = np.random.default_rng(3)
        v = rng.standard_normal(256).astype(np.float32)
        s = Int8Compression()
        g = {"W": jnp.asarray(v)}
        dec, _ = s.apply(g, s.init_state(g))
        scale = np.max(np.abs(v)) / 127.0
        assert np.max(np.abs(np.asarray(dec["W"]) - v)) <= scale / 2 + 1e-7

    def test_int8_per_chunk_scales_beat_per_tensor_on_mixed_magnitudes(self):
        v = np.concatenate([np.full(64, 1e-3, np.float32),
                            np.full(64, 10.0, np.float32)])
        g = {"W": jnp.asarray(v)}
        per_tensor, _ = Int8Compression().apply(
            g, Int8Compression().init_state(g))
        chunked_scheme = Int8Compression(chunk_size=64)
        chunked, _ = chunked_scheme.apply(g, chunked_scheme.init_state(g))
        err_t = np.max(np.abs(np.asarray(per_tensor["W"])[:64] - 1e-3))
        err_c = np.max(np.abs(np.asarray(chunked["W"])[:64] - 1e-3))
        assert err_c < err_t  # the small-magnitude chunk got its own scale

    def test_onebit_decodes_per_sign_means(self):
        v = jnp.asarray([1.0, 3.0, -2.0, -4.0])
        s = OneBitCompression()
        g = {"W": v}
        dec, st = s.apply(g, s.init_state(g))
        np.testing.assert_allclose(np.asarray(dec["W"]),
                                   [2.0, 2.0, -3.0, -3.0])
        # residual carries the dropped detail
        np.testing.assert_allclose(np.asarray(st["residual"]["W"]),
                                   [-1.0, 1.0, 1.0, -1.0])


class TestConfigRoundTrip:
    @pytest.mark.parametrize("scheme", ALL_SCHEMES +
                             [ThresholdCompression(adaptive=False),
                              Int8Compression(chunk_size=128),
                              TopKCompression(error_feedback=False)])
    def test_to_from_config(self, scheme):
        cfg = scheme.to_config()
        assert json.loads(json.dumps(cfg)) == cfg  # JSON-safe (metadata)
        assert GradientCompression.from_config(cfg) == scheme

    def test_unknown_scheme_raises(self):
        with pytest.raises(ValueError, match="unknown gradient-compression"):
            GradientCompression.from_config({"@scheme": "Nope"})


# ================================================================== guards
class TestGuards:
    def test_updater_name_helper_normalizes(self):
        assert normalize_optimization_algo("SGD") == "sgd"
        assert normalize_optimization_algo("Stochastic Gradient Descent") \
            == "stochastic_gradient_descent"
        assert is_sgd_family("sgd")
        assert is_sgd_family("stochastic_gradient_descent")
        assert not is_sgd_family("lbfgs")
        assert is_sgd_family(_net().conf)
        assert not updater_has_accumulating_state(Sgd())
        assert updater_has_accumulating_state(Adam())

    def test_no_error_feedback_with_momentum_updater_raises(self):
        net = _net(updater=Adam(0.01))
        with pytest.raises(ValueError, match="error_feedback=False"):
            enable_grad_compression(
                net, ThresholdCompression(error_feedback=False))
        # stateless Sgd composes
        enable_grad_compression(
            _net(), ThresholdCompression(error_feedback=False))

    def test_error_feedback_composes_with_momentum(self):
        net = _net(updater=Adam(0.01))
        enable_grad_compression(net, ThresholdCompression())
        batches, _ = _batches()
        net.fit(batches)
        assert compression_stats(net)["steps"] == 5

    def test_solver_config_raises(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(1).updater(Sgd(0.05)).weight_init("xavier")
                .list().optimization_algo("lbfgs")
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=3, loss="mcxent"))
                .set_input_type(InputType.feed_forward(4))
                .build())
        with pytest.raises(ValueError, match="solver"):
            enable_grad_compression(MultiLayerNetwork(conf).init(),
                                    Int8Compression())

    def test_conflicting_scheme_raises_same_scheme_idempotent(self):
        net = _net()
        enable_grad_compression(net, Int8Compression())
        enable_grad_compression(net, Int8Compression())  # idempotent
        with pytest.raises(ValueError, match="already has"):
            enable_grad_compression(net, OneBitCompression())

    def test_solver_fused_still_guarded(self):
        # the SGD-family guard on fit_fused is unchanged by compression
        conf = (NeuralNetConfiguration.builder()
                .seed(7).updater(Sgd(learning_rate=0.05))
                .weight_init("xavier").list()
                .optimization_algo("lbfgs")
                .layer(DenseLayer(n_out=16, activation="tanh"))
                .layer(OutputLayer(n_out=3, loss="mcxent"))
                .set_input_type(InputType.feed_forward(4))
                .build())
        net = MultiLayerNetwork(conf).init()
        with pytest.raises(ValueError, match="SGD-family"):
            net.fit_fused((jnp.zeros((2, 4, 4)), jnp.zeros((2, 4, 3))))


# ============================================ fused-path compression parity
class TestFusedCompression:
    """ISSUE 11 satellite (PR 9 leftover): cstate threads through the
    lax.scan carry, so the fused multi-batch paths accept
    grad_compression and match the unfused compressed step BITWISE."""

    def _batches(self, k=4, b=12, seed=0):
        rng = np.random.default_rng(seed)
        xs = rng.random((k, b, 4)).astype(np.float32)
        ys = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (k, b))]
        return [DataSet(xs[i], ys[i]) for i in range(k)]

    @pytest.mark.parametrize("scheme", ALL_SCHEMES,
                             ids=lambda s: type(s).__name__)
    def test_fit_fused_matches_per_batch_bitwise(self, scheme):
        seq = _net()
        enable_grad_compression(seq, scheme)
        fused = seq.clone()
        batches = self._batches()
        for ds in batches:
            seq.fit(ds)
        fused.fit_fused(batches)
        assert fused.iteration == seq.iteration == len(batches)
        assert fused.compress_state is not None
        for a, b in zip(
                jax.tree_util.tree_leaves(
                    [seq.params, seq.opt_state, seq.compress_state]),
                jax.tree_util.tree_leaves(
                    [fused.params, fused.opt_state, fused.compress_state])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fit_fused_masked_group_compresses(self):
        # masked variant: the compressed masked scan runs and evolves the
        # residual exactly like the per-batch masked step
        from deeplearning4j_tpu.nn.conf.recurrent import (LSTM,
                                                          RnnOutputLayer)
        conf = (NeuralNetConfiguration.builder()
                .seed(3).updater(Sgd(learning_rate=0.05))
                .weight_init("xavier").list()
                .layer(LSTM(n_out=6, activation="tanh"))
                .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(3)).build())
        seq = MultiLayerNetwork(conf).init()
        enable_grad_compression(
            seq, ThresholdCompression(target_sparsity=0.1))
        fused = seq.clone()
        rng = np.random.default_rng(3)
        batches = []
        for _ in range(3):
            x = rng.standard_normal((4, 6, 3)).astype(np.float32)
            y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (4, 6))]
            m = np.zeros((4, 6), np.float32)
            m[:, :4] = 1.0
            batches.append(DataSet(x, y, features_mask=m, labels_mask=m))
        for ds in batches:
            seq.fit(ds)
        fused.fit_fused(batches)
        for a, b in zip(
                jax.tree_util.tree_leaves(
                    [seq.params, seq.compress_state]),
                jax.tree_util.tree_leaves(
                    [fused.params, fused.compress_state])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_fit_tbptt_fused_matches_per_window_bitwise(self):
        from deeplearning4j_tpu.nn.conf.recurrent import (LSTM,
                                                          RnnOutputLayer)

        def make():
            conf = (NeuralNetConfiguration.builder()
                    .seed(21).updater(Sgd(learning_rate=0.05))
                    .weight_init("xavier").list()
                    .layer(LSTM(n_out=8, activation="tanh"))
                    .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
                    .set_input_type(InputType.recurrent(4))
                    .backprop_type("tbptt", fwd_length=5, back_length=5)
                    .build())
            net = MultiLayerNetwork(conf).init()
            enable_grad_compression(
                net, ThresholdCompression(target_sparsity=0.1))
            return net

        rng = np.random.default_rng(5)
        x = rng.random((3, 10, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (3, 10))]
        seq = make()
        fused = seq.clone()
        seq.fit(DataSet(x, y))          # 2 windows via the per-window loop
        fused.fit_tbptt_fused(x, y)     # same 2 windows, one dispatch
        assert fused.iteration == seq.iteration == 2
        for a, b in zip(
                jax.tree_util.tree_leaves(
                    [seq.params, seq.opt_state, seq.compress_state]),
                jax.tree_util.tree_leaves(
                    [fused.params, fused.opt_state, fused.compress_state])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ====================================== convergence parity + determinism
class TestConvergenceParity:
    """Tier-1 acceptance: error-feedback compressed runs reach a loss
    within a stated delta of dense in the same step budget."""

    DELTA = 0.05  # full-data loss gap after 40 small-net steps

    @pytest.mark.parametrize("scheme", ALL_SCHEMES,
                             ids=lambda s: type(s).__name__)
    def test_mln_within_delta_of_dense(self, scheme):
        batches, full = _batches()
        dense = _net()
        dense.fit(batches, num_epochs=8)
        d_loss = dense.score_dataset(full)
        comp = _net()
        enable_grad_compression(comp, scheme)
        comp.fit(batches, num_epochs=8)
        c_loss = comp.score_dataset(full)
        init_loss = _net().score_dataset(full)
        assert c_loss < init_loss  # it actually trained
        assert abs(c_loss - d_loss) < self.DELTA, \
            f"{type(scheme).__name__}: dense {d_loss:.4f} vs {c_loss:.4f}"
        st = compression_stats(comp)
        assert st["steps"] == 40
        assert st["last_ratio"] > 1.0

    @pytest.mark.parametrize("scheme",
                             [ThresholdCompression(target_sparsity=0.05),
                              Int8Compression()],
                             ids=lambda s: type(s).__name__)
    def test_graph_within_delta_of_dense(self, scheme):
        batches, full = _batches()
        dense = _graph()
        dense.fit(batches, num_epochs=8)
        d_loss = dense.score_dataset(full)
        comp = _graph()
        enable_grad_compression(comp, scheme)
        comp.fit(batches, num_epochs=8)
        c_loss = comp.score_dataset(full)
        assert abs(c_loss - d_loss) < self.DELTA
        assert compression_stats(comp)["steps"] == 40

    def test_tbptt_window_steps_compress(self):
        from deeplearning4j_tpu.models import TextGenerationLSTM
        net = TextGenerationLSTM(total_unique_characters=12, units=8,
                                 tbptt_length=4).init()
        enable_grad_compression(net,
                                ThresholdCompression(target_sparsity=0.05))
        rng = np.random.default_rng(0)
        x = np.eye(12, dtype=np.float32)[rng.integers(0, 12, (4, 8))]
        y = np.eye(12, dtype=np.float32)[rng.integers(0, 12, (4, 8))]
        net.fit(DataSet(x, y))
        assert compression_stats(net)["steps"] == 2  # 8/4 windows


class TestDeterminism:
    def test_same_seed_compressed_runs_bitwise_identical(self):
        batches, _ = _batches()
        runs = []
        for _ in range(2):
            net = _net(seed=3)
            enable_grad_compression(
                net, ThresholdCompression(target_sparsity=0.05))
            net.fit(batches, num_epochs=3)
            runs.append(net)
        _assert_bitwise(runs[0].params, runs[1].params)
        _assert_bitwise(runs[0].opt_state, runs[1].opt_state)
        _assert_bitwise(runs[0].compress_state, runs[1].compress_state)


# ============================================== zero-host-sync trace gate
class TestTraceClean:
    def test_compressed_step_has_zero_sync_points(self):
        """Tier-1 acceptance: the compressed-path step loop contains zero
        host-device sync points and no recompiles (trace_check)."""
        batches, _ = _batches()
        net = _net()
        enable_grad_compression(net, ThresholdCompression())
        net.fit(batches)  # compile outside the monitored region
        with trace_check(model=net) as report:
            net.fit(batches, num_epochs=2)
        assert report.sync_points == [], report.summary()
        assert report.recompiles == [], report.summary()


# ======================================= checkpoint / resume / reshard
class TestCheckpointRideAlong:
    def test_whole_zip_round_trip_restores_scheme_and_residuals(self,
                                                                tmp_path):
        batches, _ = _batches()
        scheme = ThresholdCompression(target_sparsity=0.05)
        net = _net()
        enable_grad_compression(net, scheme)
        net.fit(batches, num_epochs=2)
        cm = CheckpointManager(str(tmp_path), async_write=False)
        cm.save(net)
        restored = cm.restore_latest()
        assert restored.grad_compression == scheme
        _assert_bitwise(net.compress_state, restored.compress_state)
        cm.close()

    def test_resumed_refit_matches_uninterrupted_bitwise(self, tmp_path):
        """Restore mid-run and continue: the compressed trajectory
        (params, opt state AND residuals) matches the uninterrupted
        compressed run exactly."""
        batches, _ = _batches()
        scheme = Int8Compression()
        ref = _net()
        enable_grad_compression(ref, scheme)
        ref.fit(batches, num_epochs=4)

        cm = CheckpointManager(str(tmp_path), save_every_n_steps=7,
                               async_write=False)
        net = _net()
        enable_grad_compression(net, scheme)
        net.fit(batches, num_epochs=2, checkpoint_manager=cm)
        restored = cm.restore_latest()
        restored.fit(batches, num_epochs=4)
        _assert_bitwise(ref.params, restored.params)
        _assert_bitwise(ref.compress_state, restored.compress_state)
        cm.close()

    def test_train_until_kill_resume_bitwise(self, tmp_path):
        """Tier-1 acceptance: kill-and-resume via train_until with
        compression on restores residuals and matches the uninterrupted
        compressed run bitwise."""
        batches, _ = _batches()
        scheme = ThresholdCompression(target_sparsity=0.05)
        ref = _net()
        enable_grad_compression(ref, scheme)
        ref.fit(batches, num_epochs=4)

        cm = CheckpointManager(str(tmp_path), save_every_n_steps=3,
                               async_write=False)
        crashed = _net()
        enable_grad_compression(crashed, scheme)
        crashed.set_listeners(FaultInjector(kill_at_step=7))
        s = train_until(crashed, batches, num_epochs=4,
                        checkpoint_manager=cm)
        assert s.completed and s.restarts == 1
        assert s.model.grad_compression == scheme
        _assert_bitwise(ref.params, s.model.params)
        _assert_bitwise(ref.opt_state, s.model.opt_state)
        _assert_bitwise(ref.compress_state, s.model.compress_state)
        cm.close()

    def test_checkpoint_predating_compression_resets_deterministically(
            self, tmp_path):
        """The documented elastic/restore policy: a checkpoint whose
        metadata carries the scheme but no state (saved before the first
        compressed step) restores zeros — deterministic reset."""
        scheme = OneBitCompression()
        net = _net()
        enable_grad_compression(net, scheme)  # state not initialized yet
        cm = CheckpointManager(str(tmp_path), async_write=False)
        cm.save(net)
        restored = cm.restore_latest()
        assert restored.grad_compression == scheme
        assert restored.compress_state is not None
        for leaf in jax.tree_util.tree_leaves(
                restored.compress_state["residual"]):
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.zeros_like(np.asarray(leaf)))
        cm.close()

    def test_sharded_reshard_restores_residuals_any_world(self):
        """Elastic N→M interaction (fast path): a 4-host shard set of a
        compressed model reassembles into a 1-process world with the
        residual state intact, and state_sha covers it."""
        batches, _ = _batches()
        scheme = ThresholdCompression(target_sparsity=0.05)
        net = _net()
        enable_grad_compression(net, scheme)
        net.fit(batches, num_epochs=2)
        payloads = [shard_zip_bytes(s, {"batch_in_epoch": 0})
                    for s in simulated_shard_snapshots(net, 4)]
        restored, meta = restore_from_payloads(payloads)
        assert restored.grad_compression == scheme
        _assert_bitwise(net.compress_state, restored.compress_state)
        assert state_sha(restored) == state_sha(net)
        # the digest COVERS the residual: perturbing it must change it
        restored.compress_state["residual"][0]["W"] = (
            restored.compress_state["residual"][0]["W"] + 1.0)
        assert state_sha(restored) != state_sha(net)

    def test_sharded_manager_round_trip(self):
        batches, _ = _batches()
        net = _net()
        enable_grad_compression(net, Int8Compression())
        net.fit(batches)
        cm = CheckpointManager(storage=ObjectStoreBackend(), sharded=True)
        cm.save(net)
        restored = cm.restore_latest()
        _assert_bitwise(net.compress_state, restored.compress_state)
        cm.close()


# =============================================== wrappers + mesh placement
class TestParallelWrappers:
    def test_parallel_wrapper_grad_compression(self, devices):
        batches, full = _batches()
        pw = ParallelWrapper(
            _net(), grad_compression=ThresholdCompression(
                target_sparsity=0.05))
        pw.fit(batches, num_epochs=3)
        st = compression_stats(pw.model)
        assert st["steps"] == 15
        assert st["last_ratio"] > 1.0
        assert pw.model.score_dataset(full) < 1.2

    def test_cluster_trainer_grad_compression(self, devices):
        batches, _ = _batches()
        ct = ClusterTrainer(_net(), grad_compression=Int8Compression())
        ct.fit_local_shard(batches, num_epochs=2)
        assert compression_stats(ct.model)["steps"] == 10

    def test_wrapper_adopts_model_scheme(self, devices):
        """A model that already carries a scheme (e.g. restored from a
        compressed checkpoint) trains compressed through a wrapper built
        WITHOUT the kwarg — the elastic worker's path."""
        batches, _ = _batches()
        net = _net()
        enable_grad_compression(net, OneBitCompression())
        pw = ParallelWrapper(net)
        pw.fit(batches)
        assert compression_stats(net)["steps"] == 5


# ========================================================== obs / metrics
class TestObsMetrics:
    def test_metrics_expose_ratio_bytes_and_residual_norm(self):
        from deeplearning4j_tpu.obs import prometheus_text
        from deeplearning4j_tpu.obs.registry import get_registry
        batches, _ = _batches()
        net = _net()
        enable_grad_compression(net,
                                ThresholdCompression(target_sparsity=0.05))
        net.fit(batches, num_epochs=2)
        d = get_registry().as_dict()
        assert d["grad_compress_ratio"]["value"] > 1.0
        assert d["grad_compress_steps"]["value"] >= 10
        assert d["grad_compress_bytes_dense_total"]["value"] > \
            d["grad_compress_bytes_wire_total"]["value"] > 0
        assert d["grad_residual_norm"]["value"] > 0
        assert d["grad_compress_threshold"]["value"] > 0
        txt = prometheus_text(get_registry())
        for name in ("grad_compress_ratio", "grad_compress_bytes_wire_total",
                     "grad_residual_norm"):
            assert name in txt

    def test_restore_rebaselines_bytes_counters(self, tmp_path):
        """Kill-and-resume must not re-count the pre-crash byte history:
        the checkpoint restore path reseeds the absorber's delta baseline
        at the restored accumulators, so the process-wide counters grow by
        exactly the NEW bytes."""
        from deeplearning4j_tpu.obs.registry import get_registry
        batches, _ = _batches()
        net = _net()
        enable_grad_compression(net,
                                ThresholdCompression(target_sparsity=0.05))
        cm = CheckpointManager(str(tmp_path), async_write=False)
        net.fit(batches, num_epochs=2)
        cm.save(net)
        saved_bytes = compression_stats(net)["dense_bytes"]
        reg = get_registry()
        before = reg.as_dict()["grad_compress_bytes_dense_total"]["value"]
        restored = cm.restore_latest()
        # scrape between restore and the first new step: the restored
        # history must not be counted a second time
        assert reg.as_dict()["grad_compress_bytes_dense_total"]["value"] \
            == before
        restored.fit(batches, num_epochs=3)  # restored: total target
        new_bytes = compression_stats(restored)["dense_bytes"] - saved_bytes
        assert new_bytes > 0
        after = reg.as_dict()["grad_compress_bytes_dense_total"]["value"]
        assert after - before == pytest.approx(new_bytes)
        cm.close()

    def test_overhead_probe_feeds_histogram(self):
        from deeplearning4j_tpu.obs.registry import get_registry
        net = _net()
        enable_grad_compression(net, Int8Compression())
        ensure_compress_state(net)
        ms = measure_compression_overhead(net, repeats=2)
        assert ms > 0
        hist = get_registry().metric("grad_compress_ms")
        assert hist is not None and hist.count >= 2


# ===================================================== the acceptance bar
def _lenet_step():
    from deeplearning4j_tpu.models import LeNet
    rng = np.random.default_rng(5)
    x = rng.standard_normal((8, 28 * 28)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 8)]
    return LeNet(num_classes=10).init(), [DataSet(x, y)] * 4


def _charrnn_windows():
    from deeplearning4j_tpu.models import TextGenerationLSTM
    rng = np.random.default_rng(5)
    x = np.eye(16, dtype=np.float32)[rng.integers(0, 16, (4, 16))]
    y = np.eye(16, dtype=np.float32)[rng.integers(0, 16, (4, 16))]
    # T > tbptt_length: fit() runs the per-window step, the compressed
    # path of a sequence model
    return (TextGenerationLSTM(total_unique_characters=16, units=32,
                               tbptt_length=8).init(), [DataSet(x, y)] * 3)


@pytest.mark.parametrize("make", [_lenet_step, _charrnn_windows],
                         ids=["lenet", "charrnn"])
def test_default_threshold_policy_sends_a_quarter_of_the_bytes(make):
    """The DEFAULT ThresholdCompression moves at most a quarter of the
    dense float32 all-reduce's bytes on the zoo CNN and on the charRNN's
    tBPTT windows (analytic accounting of the wire format)."""
    net, batches = make()
    enable_grad_compression(net, ThresholdCompression())
    net.fit(batches)
    st = compression_stats(net)
    assert st["steps"] >= 4
    assert st["dense_bytes"] >= 4.0 * st["wire_bytes"] > 0, st

"""Fault-tolerant training & serving: storage backends, chaos harness,
auto-resume driver, serving hot-swap.

The acceptance contract: kill training K>=3 times at MIXED points (fixed
step, epoch boundary, seeded-random step) with FLAKY storage underneath the
checkpoints, recover every crash through ``train_until``, and the final
params are BITWISE-identical to the uninterrupted run — for both
MultiLayerNetwork and ComputationGraph. On the serving side: a checkpoint
hot-swap under concurrent client traffic drops ZERO requests, compiles
nothing new, and ``stats()`` reports the new step.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import jax
import pytest

from deeplearning4j_tpu.checkpoint import (
    CheckpointError, CheckpointManager, FaultInjector, FlakyBackend,
    LocalFSBackend, ObjectStoreBackend, PermanentStorageError,
    RestartBudgetExceeded, RestartPolicy, RetryingBackend, SimulatedCrash,
    StorageNotFoundError, TransientStorageError, flip_object_byte,
    tear_object, train_until)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import GraphBuilder, MergeVertex
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.utils.backoff import backoff_delay


def _net(seed=7):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(learning_rate=0.05)).weight_init("xavier")
            .list()
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
    return DataSet(x, y).split(batch)


def _leaves(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


def _assert_bitwise(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


# ------------------------------------------------------------ backoff helper
class TestBackoffHelper:
    def test_schedule_is_capped_exponential_with_jitter(self):
        import random
        rng = random.Random(0)
        for attempt in range(8):
            cap = min(4.0, 0.25 * 2 ** attempt)
            for _ in range(20):
                d = backoff_delay(attempt, base_s=0.25, cap_s=4.0, rng=rng)
                assert 0.5 * cap <= d <= cap

    def test_jitter_one_is_deterministic(self):
        assert backoff_delay(3, base_s=0.5, cap_s=100.0, jitter=1.0) == 4.0
        assert backoff_delay(10, base_s=0.5, cap_s=2.0, jitter=1.0) == 2.0

    def test_validation(self):
        with pytest.raises(ValueError):
            backoff_delay(-1)
        with pytest.raises(ValueError):
            backoff_delay(0, jitter=2.0)


# --------------------------------------------------------- storage backends
class TestObjectStoreBackend:
    def test_put_get_list_delete_semantics(self):
        b = ObjectStoreBackend()
        with pytest.raises(StorageNotFoundError):
            b.get("missing")
        b.put("a/1", b"one")
        b.put("a/2", b"two")
        b.put("b/1", b"three")
        assert b.get("a/1") == b"one"
        assert b.list("a/") == ["a/1", "a/2"]
        assert b.list() == ["a/1", "a/2", "b/1"]
        b.delete("a/1")
        b.delete("a/1")  # idempotent
        assert not b.exists("a/1") and b.exists("a/2")

    def test_puts_snapshot_the_bytes(self):
        b = ObjectStoreBackend()
        buf = bytearray(b"hello")
        b.put("x", buf)
        buf[0] = 0
        assert b.get("x") == b"hello"

    def test_manager_roundtrip_and_retention_through_object_store(self):
        store = {}
        cm = CheckpointManager(storage=ObjectStoreBackend(store),
                               keep_last=2, async_write=False)
        net = _net()
        batches = _batches(160, 32)
        for ds in batches:
            net.fit(ds)
            cm.save(net)
        # retention pruned the store itself, not just the journal
        zips = [k for k in store if k.startswith("ckpt-")]
        assert len(zips) == 2 and "manifest.json" in store
        restored = cm.restore_latest()
        _assert_bitwise(net.params, restored.params)
        assert restored._resume_state.step == 5
        cm.close()

    def test_fresh_manager_same_bucket_sees_the_run(self):
        """Two managers over one store dict model two processes over one
        bucket — the serving-side deployment shape."""
        store = {}
        cm = CheckpointManager(storage=ObjectStoreBackend(store),
                               async_write=False)
        net = _net()
        net.fit(_batches(64, 32))
        cm.save(net)
        cm.close()
        cm2 = CheckpointManager(storage=ObjectStoreBackend(store))
        assert [e["step"] for e in cm2.checkpoints()] == [2]
        _assert_bitwise(net.params, cm2.restore_latest().params)
        cm2.close()

    def test_torn_and_bitrot_fallback_identical_through_object_store(self):
        """The durability contract is backend-independent: a torn or
        bit-rotted NEWEST object makes restore fall back to the previous
        complete checkpoint, exactly like the local-filesystem tests."""
        store = {}
        backend = ObjectStoreBackend(store)
        cm = CheckpointManager(storage=backend, async_write=False)
        net = _net()
        batches = _batches(96, 32)
        net.fit(batches[0])
        cm.save(net)
        net.fit(batches[1])
        newest = cm.save(net)
        tear_object(backend, newest)
        assert cm.restore_latest()._resume_state.step == 1
        # heal, then silent bit rot instead
        net.fit(batches[2])
        newest = cm.save(net)
        flip_object_byte(backend, newest, offset=200)
        assert cm.restore_latest()._resume_state.step == 1
        cm.close()

    def test_manifest_rebuild_from_object_scan(self):
        store = {}
        cm = CheckpointManager(storage=ObjectStoreBackend(store),
                               async_write=False)
        net = _net()
        net.fit(_batches(96, 32)[0])
        cm.save(net, metric=2.5)
        cm.close()
        del store["manifest.json"]
        cm2 = CheckpointManager(storage=ObjectStoreBackend(store))
        assert [(e["step"], e["metric"]) for e in cm2.checkpoints()] == \
            [(1, 2.5)]
        assert cm2.restore_latest()._resume_state.step == 1
        cm2.close()

    def test_refresh_and_latest_step_follow_a_foreign_writer(self):
        store = {}
        writer = CheckpointManager(storage=ObjectStoreBackend(store),
                                   async_write=False)
        reader = CheckpointManager(storage=ObjectStoreBackend(store))
        assert reader.latest_step() is None
        net = _net()
        net.fit(_batches(64, 32))
        writer.save(net)
        assert reader.latest_step() is None  # journal cached
        reader.refresh()
        assert reader.latest_step() == 2
        writer.close()
        reader.close()


class TestRetryingBackend:
    def test_scripted_transient_faults_are_retried_and_recovered(self):
        flaky = FlakyBackend(ObjectStoreBackend())
        flaky.script_failures(2)
        rb = RetryingBackend(flaky, max_retries=4, base_backoff_s=0.0)
        rb.put("x", b"data")
        assert rb.get("x") == b"data"
        assert flaky.faults_injected == 2
        assert rb.retries == 2 and rb.gave_up == 0

    def test_budget_exhaustion_reraises_last_transient(self):
        flaky = FlakyBackend(ObjectStoreBackend())
        flaky.script_failures(10)
        rb = RetryingBackend(flaky, max_retries=2, base_backoff_s=0.0)
        with pytest.raises(TransientStorageError):
            rb.put("x", b"data")
        assert rb.gave_up == 1 and rb.attempts == 3

    def test_permanent_errors_are_not_retried(self):
        flaky = FlakyBackend(ObjectStoreBackend())
        flaky.script_failures(1, PermanentStorageError("403 forbidden"))
        rb = RetryingBackend(flaky, max_retries=5, base_backoff_s=0.0)
        with pytest.raises(PermanentStorageError):
            rb.put("x", b"data")
        assert rb.retries == 0 and rb.attempts == 1

    def test_not_found_is_an_answer_not_a_fault(self):
        rb = RetryingBackend(ObjectStoreBackend(), max_retries=5,
                             base_backoff_s=0.0)
        with pytest.raises(StorageNotFoundError):
            rb.get("missing")
        assert rb.retries == 0  # no backoff stall on a definitive miss

    def test_backoff_delays_follow_the_capped_exponential_schedule(self):
        slept = []
        flaky = FlakyBackend(ObjectStoreBackend())
        flaky.script_failures(3)
        rb = RetryingBackend(flaky, max_retries=3, base_backoff_s=0.1,
                             max_backoff_s=0.25, sleep=slept.append)
        rb.put("x", b"d")
        caps = [0.1, 0.2, 0.25]
        assert len(slept) == 3
        for d, cap in zip(slept, caps):
            assert 0.5 * cap <= d <= cap

    def test_per_op_timeout_bounds_a_hung_write(self):
        flaky = FlakyBackend(ObjectStoreBackend(), put_latency_s=0.5)
        rb = RetryingBackend(flaky, max_retries=1, base_backoff_s=0.0,
                             op_timeout_s=0.05)
        t0 = time.monotonic()
        with pytest.raises(TransientStorageError, match="deadline"):
            rb.put("x", b"d")
        assert time.monotonic() - t0 < 2.0  # not 2 x 0.5s of latency


# ------------------------------------------------------------ fault injector
class TestFaultInjectorModes:
    def test_requires_a_mode_and_validates(self):
        with pytest.raises(ValueError):
            FaultInjector()
        with pytest.raises(ValueError):
            FaultInjector(kill_at_step=0)
        with pytest.raises(ValueError):
            FaultInjector(kill_at_epoch=0)
        with pytest.raises(ValueError):
            FaultInjector(kill_probability=0.0)

    def test_kill_at_epoch_fires_at_the_boundary_before_the_epoch_save(
            self, tmp_path):
        """The epoch-boundary crash window: the last step's checkpoint is
        durable, the epoch counter has NOT advanced, no epoch-boundary
        save ran."""
        cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=1,
                               async_write=False)
        net = _net().set_listeners(FaultInjector(kill_at_epoch=2))
        batches = _batches(96, 32)  # 3 per epoch
        with pytest.raises(SimulatedCrash, match="end of epoch 2"):
            net.fit(batches, num_epochs=4, checkpoint_manager=cm)
        last = cm.checkpoints()[-1]
        assert (last["step"], last["epoch"]) == (6, 1)
        cm.close()

    def test_kill_probability_is_seeded_deterministic(self):
        def run(seed):
            net = _net().set_listeners(
                FaultInjector(kill_probability=0.2, seed=seed))
            try:
                net.fit(_batches(320, 32), num_epochs=4)
            except SimulatedCrash:
                return net.iteration
            return None
        a, b = run(3), run(3)
        assert a is not None and a == b  # same seed, same kill point
        # a different seed lands elsewhere (seeds chosen so the points
        # differ: Random(3) first dips under 0.2 at draw 6, Random(5) at 7)
        assert run(5) != a

    def test_max_kills_disarms_the_injector(self):
        inj = FaultInjector(kill_at_step=1, max_kills=1)
        net = _net().set_listeners(inj)
        with pytest.raises(SimulatedCrash):
            net.fit(_batches(96, 32))
        net.fit(_batches(96, 32))  # disarmed: trains through
        assert inj.kills == 1


# ---------------------------------------------------------------- train_until
class TestTrainUntil:
    def test_clean_run_completes_with_initial_checkpoint(self, tmp_path):
        cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=2)
        net = _net()
        summary = train_until(net, _batches(), num_epochs=2,
                              checkpoint_manager=cm)
        assert summary.completed and summary.restarts == 0
        assert summary.crashes == []
        # the up-front step-0 checkpoint is in the journal
        assert cm.checkpoints()[0]["step"] == 0
        assert summary.model.epoch == 2
        cm.close()

    def test_single_kill_resumes_bitwise(self, tmp_path):
        batches = _batches()
        E = 2
        ref = _net(seed=7)
        ref.fit(batches, num_epochs=E)

        cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=3)
        crashed = _net(seed=7).set_listeners(FaultInjector(kill_at_step=7))
        summary = train_until(
            crashed, batches, num_epochs=E, checkpoint_manager=cm,
            restart_policy=RestartPolicy(max_restarts=2, backoff_s=0.0))
        cm.close()
        assert summary.completed and summary.restarts == 1
        rec = summary.crashes[0]
        assert rec.error_type == "SimulatedCrash"
        assert rec.restored_step == 6  # saves at 3, 6; killed at 7
        _assert_bitwise(ref.params, summary.model.params)
        _assert_bitwise(ref.opt_state, summary.model.opt_state)
        assert (ref.iteration, ref.epoch) == \
            (summary.model.iteration, summary.model.epoch)

    def test_restart_budget_escalates_with_history(self, tmp_path):
        cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=1,
                               async_write=False)
        net = _net()

        def rearm(model, attempt):
            model.set_listeners(FaultInjector(kill_at_step=1))

        net.set_listeners(FaultInjector(kill_at_step=1))
        with pytest.raises(RestartBudgetExceeded) as ei:
            train_until(net, _batches(), num_epochs=2, checkpoint_manager=cm,
                        restart_policy=RestartPolicy(max_restarts=2,
                                                     backoff_s=0.0),
                        on_restart=rearm)
        s = ei.value.summary
        assert not s.completed
        assert len(s.crashes) == 3  # 2 restarts + the give-up record
        assert all(c.error_type == "SimulatedCrash" for c in s.crashes)
        cm.close()

    def test_crash_before_any_checkpoint_without_initial_save_is_loud(
            self, tmp_path):
        cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=100)
        net = _net().set_listeners(FaultInjector(kill_at_step=1))
        with pytest.raises(RestartBudgetExceeded, match="no restorable"):
            train_until(net, _batches(), num_epochs=1, checkpoint_manager=cm,
                        save_initial=False,
                        restart_policy=RestartPolicy(max_restarts=3,
                                                     backoff_s=0.0))
        cm.close()

    def test_fence_drops_saves_from_stale_models(self, tmp_path):
        """The zombie-writer guard train_until relies on: once the manager
        is fenced to the recovered model, an abandoned fit thread's model
        can neither commit checkpoints nor corrupt the resume-state
        triggers behind the live run's back."""
        cm = CheckpointManager(tmp_path / "ck", async_write=False)
        live, zombie = _net(seed=1), _net(seed=2)
        batches = _batches(64, 32)
        live.fit(batches)
        zombie.fit(batches)
        cm.fence(live)
        assert cm.save(zombie) is None  # dropped, not committed
        cm.step_end(zombie, batch_in_epoch=7)   # must not move triggers
        cm.epoch_end(zombie)
        assert cm.saves_fenced == 1
        assert cm.checkpoints() == []
        assert cm.save(live) is not None        # the fenced-to model works
        assert cm._batch_in_epoch == 0          # zombie's 7 never landed
        cm.fence(None)
        assert cm.save(zombie) is not None      # lifted
        cm.close()

    def test_transient_restore_outage_consumes_budget_not_the_run(self):
        """A storage outage DURING recovery (every committed checkpoint
        briefly unreadable) must retry under the restart budget, not give
        up instantly — the outage ends and the run still finishes
        bitwise."""
        batches = _batches()
        ref = _net(seed=7)
        ref.fit(batches, num_epochs=2)

        flaky = FlakyBackend(ObjectStoreBackend())  # NO retrying wrapper
        cm = CheckpointManager(storage=flaky, save_every_n_steps=3,
                               async_write=False)

        net = _net(seed=7).set_listeners(FaultInjector(kill_at_step=7))
        outage = {"armed": True}
        orig_restore = cm.restore_latest

        def restore_with_one_outage(*a, **k):
            if outage["armed"]:
                outage["armed"] = False
                # the whole first restore pass sees a dead store: one get
                # failure per journal entry walks the fallback to None
                flaky.script_failures(len(cm.checkpoints()))
            return orig_restore(*a, **k)

        cm.restore_latest = restore_with_one_outage
        summary = train_until(
            net, batches, num_epochs=2, checkpoint_manager=cm,
            restart_policy=RestartPolicy(max_restarts=4, backoff_s=0.0))
        cm.close()
        assert summary.completed
        assert any(c.error_type == "RestoreFailed" for c in summary.crashes)
        _assert_bitwise(ref.params, summary.model.params)

    def test_backoff_between_restarts_is_recorded(self, tmp_path):
        cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=1,
                               async_write=False)
        net = _net().set_listeners(FaultInjector(kill_at_step=2))
        t0 = time.monotonic()
        summary = train_until(
            net, _batches(), num_epochs=1, checkpoint_manager=cm,
            restart_policy=RestartPolicy(max_restarts=2, backoff_s=0.05,
                                         max_backoff_s=0.1))
        assert summary.completed
        assert summary.crashes[0].backoff_s > 0
        assert time.monotonic() - t0 >= summary.crashes[0].backoff_s
        cm.close()

    def test_watchdog_turns_a_hang_into_a_restart(self, tmp_path):
        """A fit attempt that wedges (hung collective, dead peer) exceeds
        the watchdog deadline, becomes CollectiveTimeoutError, and
        train_until recovers it like any crash — bitwise."""
        from deeplearning4j_tpu.parallel.watchdog import CollectiveWatchdog

        release = threading.Event()

        class HangOnce:
            def __init__(self):
                self.armed = True

            def iteration_done(self, model, iteration, epoch):
                if self.armed:
                    self.armed = False
                    release.wait(30)
                    # the abandoned zombie thread must not keep training
                    # (and checkpointing!) behind the recovered run's back
                    raise SimulatedCrash("zombie fit thread cleanup")

            def on_epoch_start(self, model):
                pass

            def on_epoch_end(self, model):
                pass

        batches = _batches()
        ref = _net(seed=7)
        ref.fit(batches, num_epochs=2)

        cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=3)
        net = _net(seed=7).set_listeners(HangOnce())
        # the deadline must cover a HEALTHY attempt (first-step jit compile
        # included, ~0.5s on this shared CPU host) but fire on the hang
        summary = train_until(
            net, batches, num_epochs=2, checkpoint_manager=cm,
            watchdog=CollectiveWatchdog(timeout_s=5.0),
            restart_policy=RestartPolicy(max_restarts=2, backoff_s=0.0))
        release.set()  # unhang the zombie; it raises before checkpointing
        assert summary.completed and summary.restarts == 1
        assert summary.crashes[0].error_type == "CollectiveTimeoutError"
        time.sleep(0.2)  # let the zombie thread die before asserting
        _assert_bitwise(ref.params, summary.model.params)
        cm.close()


# -------------------------------------------------------- chaos (headline)
class TestChaos:
    def test_k3_mixed_kills_with_flaky_storage_bitwise_multilayer(self):
        """Acceptance: 3 kills (fixed step, epoch boundary, seeded-random
        step) with seeded transient storage faults + write latency under
        every checkpoint op, all recovered by train_until — final params,
        updater state, counters and rng chain bitwise-equal to the
        uninterrupted run."""
        batches = _batches()  # 5 per epoch
        E = 4
        ref = _net(seed=7)
        ref.fit(batches, num_epochs=E)

        store = {}
        flaky = FlakyBackend(ObjectStoreBackend(store), seed=2,
                             transient_rate=0.15, put_latency_s=0.001)
        backend = RetryingBackend(flaky, max_retries=8, base_backoff_s=0.0)
        cm = CheckpointManager(storage=backend, save_every_n_steps=1)

        injectors = [FaultInjector(kill_at_epoch=2),
                     FaultInjector(kill_probability=0.5, seed=11),
                     None]

        def rearm(model, attempt):
            inj = injectors[attempt - 1]
            if inj is not None:
                model.set_listeners(inj)

        net = _net(seed=7).set_listeners(FaultInjector(kill_at_step=4))
        summary = train_until(
            net, batches, num_epochs=E, checkpoint_manager=cm,
            restart_policy=RestartPolicy(max_restarts=6, backoff_s=0.0),
            on_restart=rearm)
        cm.close()

        assert summary.completed and summary.restarts == 3
        kinds = [c.error for c in summary.crashes]
        assert "killed training after step 4" in kinds[0]
        assert "end of epoch 2" in kinds[1]
        assert "randomly killed" in kinds[2]
        assert flaky.faults_injected > 0  # the chaos actually happened
        assert backend.gave_up == 0

        _assert_bitwise(ref.params, summary.model.params)
        _assert_bitwise(ref.opt_state, summary.model.opt_state)
        _assert_bitwise(ref.state, summary.model.state)
        assert (ref.iteration, ref.epoch) == \
            (summary.model.iteration, summary.model.epoch)
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(ref._rng)),
            np.asarray(jax.random.key_data(summary.model._rng)))

    def test_mixed_kills_with_flaky_storage_bitwise_graph(self):
        """Same contract for ComputationGraph (Adam moments must survive
        the crash/restore cycles exactly)."""
        batches = _batches(128, 64)  # 2 per epoch
        E = 3
        ref = _graph(seed=5)
        ref.fit(batches, num_epochs=E)

        flaky = FlakyBackend(ObjectStoreBackend(), seed=9,
                             transient_rate=0.15)
        cm = CheckpointManager(
            storage=RetryingBackend(flaky, max_retries=8,
                                    base_backoff_s=0.0),
            save_every_n_steps=1)

        injectors = [FaultInjector(kill_at_epoch=2), None]

        def rearm(model, attempt):
            if injectors[attempt - 1] is not None:
                model.set_listeners(injectors[attempt - 1])

        net = _graph(seed=5).set_listeners(FaultInjector(kill_at_step=3))
        summary = train_until(
            net, batches, num_epochs=E, checkpoint_manager=cm,
            restart_policy=RestartPolicy(max_restarts=4, backoff_s=0.0),
            on_restart=rearm)
        cm.close()

        assert summary.completed and summary.restarts == 2
        assert flaky.faults_injected > 0
        _assert_bitwise(ref.params, summary.model.params)
        _assert_bitwise(ref.opt_state, summary.model.opt_state)
        assert (ref.iteration, ref.epoch) == \
            (summary.model.iteration, summary.model.epoch)


# ------------------------------------------------------------ serving swap
class TestHotSwap:
    def _serving_stack(self, store):
        """Trainer commits epoch 1 to the bucket; a separate serving-side
        manager restores it — the two-process deployment shape."""
        batches = _batches()
        trainer_cm = CheckpointManager(storage=ObjectStoreBackend(store),
                                       async_write=False)
        net = _net(seed=7)
        net.fit(batches, num_epochs=1)
        trainer_cm.save(net)
        serve_cm = CheckpointManager(storage=ObjectStoreBackend(store))
        served = serve_cm.restore_latest(load_updater=False)
        return batches, trainer_cm, net, serve_cm, served

    def test_zero_downtime_swap_under_concurrent_traffic(self, devices):
        """Acceptance: every in-flight and subsequent request across a
        swap succeeds (zero dropped/failed dispatches), stats() reports
        the new checkpoint step, and the swap compiles nothing new."""
        store = {}
        batches, trainer_cm, net, serve_cm, served = \
            self._serving_stack(store)
        x = batches[0].features
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        pi = ParallelInference(served, batch_limit=8, queue_timeout_ms=2)
        pi.start_hot_swap(serve_cm)  # manual polls: deterministic test
        pi.warmup(np.asarray(x[:4]))
        st0 = pi.stats()
        assert st0["hot_swap"] == {"enabled": True, "swaps": 0,
                                   "current_checkpoint_step": 5,
                                   "poll_errors": 0,
                                   "consecutive_poll_errors": 0,
                                   "last_poll_delay_s": None}

        errors, served_count = [], [0]
        stop = threading.Event()

        def client():
            while not stop.is_set():
                try:
                    out = pi.output_batched(np.asarray(x[:3]))
                    assert out.shape == (3, 3)
                    served_count[0] += 1
                except BaseException as e:  # any failure fails the test
                    errors.append(e)
                    return

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        # trainer commits a newer checkpoint mid-traffic; serving polls
        net.fit(batches, num_epochs=3)
        trainer_cm.save(net)
        assert pi.poll_checkpoint() is True
        assert pi.poll_checkpoint() is False  # idempotent at same step
        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        st = pi.stats()
        pi.shutdown()
        trainer_cm.close()
        serve_cm.close()

        assert errors == []
        assert served_count[0] > 0
        assert st["hot_swap"]["swaps"] == 1
        # trainer was at epoch 1 / step 5; a plain (non-resumed) fit adds
        # num_epochs=3 more epochs of 5 steps
        assert st["hot_swap"]["current_checkpoint_step"] == 20
        assert st["model_compiles"] == st0["model_compiles"]  # warm swap
        # and the served params ARE the new checkpoint's
        np.testing.assert_allclose(np.asarray(pi.output(x[:5])),
                                   np.asarray(net.output(x[:5])),
                                   rtol=1e-6, atol=1e-7)

    def test_background_poller_swaps_on_its_own(self, devices):
        store = {}
        batches, trainer_cm, net, serve_cm, served = \
            self._serving_stack(store)
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        pi = ParallelInference(served, checkpoint_manager=serve_cm,
                               checkpoint_poll_secs=0.05)
        net.fit(batches, num_epochs=2)
        trainer_cm.save(net)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if pi.stats()["hot_swap"]["swaps"] >= 1:
                break
            time.sleep(0.05)
        st = pi.stats()
        pi.shutdown()
        trainer_cm.close()
        serve_cm.close()
        assert st["hot_swap"]["swaps"] == 1
        # epoch-1 serving baseline (step 5) + 2 more trained epochs
        assert st["hot_swap"]["current_checkpoint_step"] == 15
        assert st["hot_swap"]["poll_errors"] == 0

    def test_corrupt_newer_checkpoint_never_swaps_or_downgrades(
            self, devices):
        """restore_latest falls back past a rotted newest object — the
        poller must then NOT swap (the fallback is at-or-before the served
        step), rather than churning a re-swap or a parameter DOWNGRADE on
        every poll."""
        store = {}
        batches, trainer_cm, net, serve_cm, served = \
            self._serving_stack(store)
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        backend = ObjectStoreBackend(store)
        pi = ParallelInference(served)
        pi.start_hot_swap(serve_cm)
        net.fit(batches, num_epochs=2)
        newest = trainer_cm.save(net)  # step 15...
        flip_object_byte(backend, newest, offset=300)  # ...then bit rot
        assert pi.poll_checkpoint() is False  # fallback == served step 5
        assert pi.poll_checkpoint() is False  # and stays quiet, no churn
        assert pi.stats()["hot_swap"]["swaps"] == 0
        assert pi.stats()["hot_swap"]["current_checkpoint_step"] == 5
        pi.shutdown()
        trainer_cm.close()
        serve_cm.close()

    def test_poll_backoff_schedule_is_capped_exponential(self, devices):
        """_next_poll_delay: healthy → the configured cadence; erroring →
        cadence + capped-exponential-jitter backoff (utils/backoff.py),
        non-decreasing in the error streak, capped, reset on success."""
        store = {}
        _, trainer_cm, _, serve_cm, served = self._serving_stack(store)
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        pi = ParallelInference(served)
        assert pi._next_poll_delay(0.5, 0) == 0.5
        delays = [pi._next_poll_delay(0.5, k, cap_s=8.0)
                  for k in range(1, 9)]
        assert all(d > 0.5 for d in delays)
        # jitter draws from [d/2, d] with d doubling per streak step, so
        # the schedule's LOWER bound is non-decreasing and the cap binds
        for k, d in enumerate(delays, start=1):
            full = min(8.0, 0.5 * 2.0 ** (k - 1))
            assert 0.5 + full / 2 <= d <= 0.5 + full, (k, d)
        assert max(delays) <= 0.5 + 8.0  # capped, never minutes-long
        pi.shutdown()
        trainer_cm.close()
        serve_cm.close()

    def test_poller_backs_off_on_flaky_store_and_recovers(self, devices):
        """Satellite acceptance: a scripted FlakyBackend makes every poll
        fail — the poller counts errors, stretches its cadence, keeps
        serving, and once the store heals it resets and swaps in the
        newer checkpoint."""
        store = {}
        batches, trainer_cm, net, serve_cm, served = \
            self._serving_stack(store)
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        # the SERVING manager's storage becomes flaky mid-flight: wrap
        # reads via a fresh manager over a FlakyBackend on the same bucket
        flaky = FlakyBackend(ObjectStoreBackend(store),
                             ops=("get", "list"))
        flaky_cm = CheckpointManager(storage=flaky)
        pi = ParallelInference(served)
        pi.start_hot_swap(flaky_cm, poll_secs=0.02)
        flaky.script_failures(3)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            hs = pi.stats()["hot_swap"]
            if hs["poll_errors"] >= 3:
                break
            time.sleep(0.02)
        hs = pi.stats()["hot_swap"]
        assert hs["poll_errors"] == 3
        assert hs["last_poll_delay_s"] > 0.02  # backed off the cadence
        assert pi.output(np.asarray(batches[0].features[:2])).shape == (2, 3)
        # the store heals; a newer checkpoint commits; the poller resets
        # its streak and picks the swap up on its own
        net.fit(batches, num_epochs=2)
        trainer_cm.save(net)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            hs = pi.stats()["hot_swap"]
            if hs["swaps"] >= 1 and hs["consecutive_poll_errors"] == 0:
                break
            time.sleep(0.02)
        hs = pi.stats()["hot_swap"]
        assert hs["swaps"] == 1
        assert hs["current_checkpoint_step"] == 15
        assert hs["consecutive_poll_errors"] == 0  # reset on success
        assert flaky.faults_injected == 3  # the chaos actually happened
        pi.shutdown()
        trainer_cm.close()
        serve_cm.close()
        flaky_cm.close()

    def test_architecture_mismatch_refuses_to_swap(self, devices):
        store = {}
        batches, trainer_cm, net, serve_cm, served = \
            self._serving_stack(store)
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        pi = ParallelInference(served)
        pi.start_hot_swap(serve_cm)
        # a DIFFERENT architecture lands in the same bucket
        other = _graph(seed=3)
        other.fit(_batches(128, 64), num_epochs=4)
        trainer_cm.save(other)
        with pytest.raises(RuntimeError, match="different architecture"):
            pi.poll_checkpoint()
        assert pi.stats()["hot_swap"]["swaps"] == 0
        out = pi.output(np.asarray(batches[0].features[:2]))
        assert out.shape == (2, 3)  # still serving the old params
        pi.shutdown()
        trainer_cm.close()
        serve_cm.close()


# ------------------------------------------------- early stopping via backends
def test_early_stopping_saver_through_flaky_object_store():
    """The early-stopping saver protocol rides the storage plumbing
    unchanged: best models become durable object-store checkpoints, with
    transient faults retried away, and get_best_model restores through
    the journal."""
    from deeplearning4j_tpu.earlystopping.conditions import (
        MaxEpochsTerminationCondition)
    from deeplearning4j_tpu.earlystopping.trainer import (
        EarlyStoppingConfiguration, EarlyStoppingTrainer)
    store = {}
    flaky = FlakyBackend(ObjectStoreBackend(store), seed=4,
                         transient_rate=0.15)
    cm = CheckpointManager(
        storage=RetryingBackend(flaky, max_retries=8, base_backoff_s=0.0),
        keep_best="min")
    config = EarlyStoppingConfiguration(
        epoch_termination_conditions=[MaxEpochsTerminationCondition(3)])
    batches = _batches(96, 32)
    result = EarlyStoppingTrainer(config, _net(), batches,
                                  validation_data=batches,
                                  checkpoint_manager=cm).fit()
    assert result.best_model is not None
    assert result.best_model._restored_from is not None
    assert result.best_model._resume_state is None  # selection, not resume
    assert any(k.startswith("ckpt-") for k in store)
    entries = [e for e in cm.checkpoints() if e["metric"] is not None]
    assert entries and min(e["metric"] for e in entries) == \
        pytest.approx(result.best_model_score)
    cm.close()


# =========================================================== elastic chaos
# 4-process elastic fleet acceptance (ISSUE 6 tentpole). Heavy multi-
# process tests: ``slow``-marked so tier-1 can never stall on them, and
# every subprocess wait goes through hard-timeout helpers (the tier-1
# guard test below enforces both properties).

_ELASTIC_WORKER = os.path.join(os.path.dirname(__file__),
                               "elastic_worker.py")


def _elastic_cfg(tmp_path, **overrides):
    cfg = {
        "store_dir": str(tmp_path / "store"),
        "out_dir": str(tmp_path / "out"),
        "num_workers": 4, "devices_per_worker": 2, "num_epochs": 6,
        "lease_ttl_s": 3.0, "collective_timeout_s": 8.0,
        "barrier_timeout_s": 8.0, "scaledown_grace_s": 4.0,
        "join_timeout_s": 45.0, "poll_s": 0.15,
    }
    cfg.update(overrides)
    os.makedirs(cfg["out_dir"], exist_ok=True)
    path = str(tmp_path / "elastic-cfg.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    return path, cfg


def _elastic_env():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _run_elastic_fleet(cfg_path, worker_ids, timeout, respawn_preempted,
                       max_restarts=8, log_dir=None):
    """Supervised elastic fleet with a HARD overall deadline — the
    supervisor kills every child on expiry, so this helper can never
    outlive ``timeout``."""
    from deeplearning4j_tpu.checkpoint.resume import RestartPolicy
    from deeplearning4j_tpu.checkpoint.supervisor import train_until_process
    return train_until_process(
        lambda i, attempt: [sys.executable, _ELASTIC_WORKER, cfg_path,
                            worker_ids[i], str(attempt)],
        num_workers=len(worker_ids),
        restart_policy=RestartPolicy(max_restarts=max_restarts,
                                     backoff_s=0.2, max_backoff_s=1.0),
        respawn_preempted=respawn_preempted,
        attempt_timeout_s=timeout, overall_timeout_s=timeout,
        env=_elastic_env(), log_dir=log_dir)


def _spawn_raw_fleet(cfg_path, worker_ids, timeout, stagger_s=0.0):
    """Unsupervised fleet (for the grow test's staggered joiner): Popen
    with a hard communicate() timeout; every child is killed on expiry."""
    procs = []
    try:
        for k, wid in enumerate(worker_ids):
            if k and stagger_s:
                time.sleep(stagger_s)
            procs.append(subprocess.Popen(
                [sys.executable, _ELASTIC_WORKER, cfg_path, wid],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=_elastic_env()))
        outs = []
        deadline = time.monotonic() + timeout
        for p in procs:
            left = max(1.0, deadline - time.monotonic())
            outs.append(p.communicate(timeout=left)[0])
        return [p.returncode for p in procs], outs
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
        pytest.fail(f"elastic fleet timed out after {timeout}s")


def _out_json(cfg, name):
    with open(os.path.join(cfg["out_dir"], name)) as f:
        return json.load(f)


def _gen_records(cfg):
    recs = []
    for fn in sorted(os.listdir(cfg["out_dir"])):
        if fn.startswith("gen-"):
            recs.append(_out_json(cfg, fn))
    return recs


@pytest.mark.slow
def test_elastic_chaos_kills_at_boundary_and_midepoch(tmp_path):
    """HEADLINE chaos acceptance: 4 local processes; w03 SIGKILLed at the
    epoch-2 boundary, w02 SIGKILLed mid-epoch (step 7) — survivors
    re-shard through shrinking membership generations and finish all 6
    epochs under train_until_process with identical final state. Every
    cross-world restore (4-shard set into a 3-world, 3-shard set into a
    2-world, and each of them into THIS single process) yields the exact
    same params/opt-state digest."""
    cfg_path, cfg = _elastic_cfg(
        tmp_path, kill={"w03": {"at_epoch": 2}, "w02": {"at_step": 7}})
    ids = [f"w{i:02d}" for i in range(4)]
    s = _run_elastic_fleet(cfg_path, ids, timeout=360,
                           respawn_preempted=False,
                           log_dir=str(tmp_path / "logs"))
    assert s.completed
    assert s.worker_status[0] == "completed"
    assert s.worker_status[1] == "completed"
    # both victims really died by SIGKILL and were not respawned
    preempted = {c.worker for c in s.crashes if c.error_type == "Preempted"}
    assert preempted == {2, 3}
    done0, done1 = _out_json(cfg, "done-w00.json"), \
        _out_json(cfg, "done-w01.json")
    assert done0["epochs"] == done1["epochs"] == cfg["num_epochs"]
    assert done0["state_sha"] == done1["state_sha"]
    gens = _gen_records(cfg)
    worlds = {g["generation"]: g["world"] for g in gens}
    assert max(worlds.values()) == 4 and min(worlds.values()) == 2
    # N→M reshard equality: every restore a worker performed must equal
    # restoring the SAME journal entry here (a 1-process world) —
    # 4-shard→3-world, 3-shard→2-world and N→1 all agree exactly
    from deeplearning4j_tpu.checkpoint import (CheckpointManager,
                                               LocalFSBackend, state_sha)
    cm = CheckpointManager(
        storage=LocalFSBackend(os.path.join(cfg["store_dir"], "ckpt")))
    checked = 0
    for g in gens:
        if not g.get("restored_from"):
            continue
        entry_file = g["restored_from"].rsplit("/", 1)[-1]
        local = cm.restore_entry(entry_file)
        assert state_sha(local) == g["state_sha"], \
            f"world-{g['world']} restore of {entry_file} diverged"
        checked += 1
    assert checked >= 2  # at least the 4->3 and ->2 transitions
    # and the final 2-shard checkpoint restores here to the final state
    final = cm.restore_latest()
    assert state_sha(final) == done0["state_sha"]
    assert final.epoch == cfg["num_epochs"]


@pytest.mark.slow
def test_elastic_membership_change_with_grad_compression(tmp_path):
    """Compressed collectives × elastic membership (ISSUE 9 satellite):
    a 4-worker fleet trains with ThresholdCompression; w03 is SIGKILLed
    at the epoch-2 boundary, survivors re-shard 4→3 and finish — no
    wedged collective (hard fleet deadline), fleet digests AGREE, and
    since ``state_sha`` covers the error-feedback residual, agreement
    proves the residual state was restored consistently across the
    membership change. Every worker-side restore equals restoring the
    same journal entry into THIS 1-process world (N→M reshard of the
    residual per the documented policy)."""
    from deeplearning4j_tpu.parallel.compress import ThresholdCompression
    cfg_path, cfg = _elastic_cfg(
        tmp_path, kill={"w03": {"at_epoch": 2}},
        grad_compression=ThresholdCompression(
            target_sparsity=0.05).to_config())
    ids = [f"w{i:02d}" for i in range(4)]
    s = _run_elastic_fleet(cfg_path, ids, timeout=360,
                           respawn_preempted=False,
                           log_dir=str(tmp_path / "logs"))
    assert s.completed
    done = [_out_json(cfg, f"done-w{i:02d}.json") for i in range(3)]
    assert all(d["epochs"] == cfg["num_epochs"] for d in done)
    assert len({d["state_sha"] for d in done}) == 1
    gens = _gen_records(cfg)
    worlds = {g["generation"]: g["world"] for g in gens}
    assert max(worlds.values()) == 4 and min(worlds.values()) == 3
    from deeplearning4j_tpu.checkpoint import (CheckpointManager,
                                               LocalFSBackend, state_sha)
    cm = CheckpointManager(
        storage=LocalFSBackend(os.path.join(cfg["store_dir"], "ckpt")))
    checked = 0
    for g in gens:
        if not g.get("restored_from"):
            continue
        local = cm.restore_entry(g["restored_from"].rsplit("/", 1)[-1])
        # the restored model must carry the scheme + residual state the
        # digest covers
        assert local.grad_compression is not None
        assert local.compress_state is not None
        assert state_sha(local) == g["state_sha"], \
            f"world-{g['world']} compressed restore diverged"
        checked += 1
    assert checked >= 1  # at least the 4->3 transition restore
    final = cm.restore_latest()
    assert state_sha(final) == done[0]["state_sha"]


@pytest.mark.slow
def test_elastic_whole_job_preemption_respawn_is_bitwise(tmp_path):
    """Scheduler-shaped whole-job preemption: BOTH workers SIGKILLed
    mid-epoch, respawned as NEW processes by the supervisor, re-forming
    the same-size world — the final state is BITWISE-identical to the
    uninterrupted elastic run (epoch-boundary sharded checkpoint + exact
    RNG/opt-state restore)."""
    ids = ["w00", "w01"]
    base = dict(num_workers=2, num_epochs=4, scaledown_grace_s=12.0,
                join_timeout_s=60.0)
    cfg_a_path, cfg_a = _elastic_cfg(tmp_path / "clean", **base)
    s = _run_elastic_fleet(cfg_a_path, ids, timeout=300,
                           respawn_preempted=True,
                           log_dir=str(tmp_path / "clean-logs"))
    assert s.completed and s.restarts == 0
    cfg_b_path, cfg_b = _elastic_cfg(
        tmp_path / "preempted", **base,
        kill={"w00": {"at_step": 5, "first_attempt_only": True},
              "w01": {"at_step": 5, "first_attempt_only": True}})
    s2 = _run_elastic_fleet(cfg_b_path, ids, timeout=300,
                            respawn_preempted=True,
                            log_dir=str(tmp_path / "preempt-logs"))
    assert s2.completed and s2.restarts >= 1  # the fleet really died
    for wid in ids:
        a, b = _out_json(cfg_a, f"done-{wid}.json"), \
            _out_json(cfg_b, f"done-{wid}.json")
        assert a["epochs"] == b["epochs"] == 4
        assert a["state_sha"] == b["state_sha"], \
            "same-world restart diverged from the uninterrupted run"


@pytest.mark.slow
def test_elastic_joiner_grows_world_at_epoch_boundary(tmp_path):
    """Membership GROWTH through the clean epoch-boundary path: two
    incumbents train (paced), a third worker arrives mid-run; the next
    boundary check re-shards to a 3-worker world (no watchdog involved)
    and everyone finishes with identical state."""
    cfg_path, cfg = _elastic_cfg(
        tmp_path, num_workers=2, num_epochs=10, step_sleep_s=0.5,
        scaledown_grace_s=2.0)
    rcs, outs = _spawn_raw_fleet(cfg_path, ["w00", "w01", "w02"],
                                 timeout=300, stagger_s=6.0)
    assert rcs == [0, 0, 0], "\n".join(o[-2000:] for o in outs)
    shas = set()
    for wid in ("w00", "w01", "w02"):
        done = _out_json(cfg, f"done-{wid}.json")
        shas.add(done["state_sha"])
    assert len(shas) == 1
    done0 = _out_json(cfg, "done-w00.json")
    worlds = [g["world"] for g in done0["generations"]]
    assert worlds[0] == 2 and worlds[-1] == 3
    # the growth happened at a boundary (a detected waiting joiner),
    # not through a watchdog escalation
    assert any("waiting" in g["ended"] for g in done0["generations"])
    joiner = _out_json(cfg, "done-w02.json")
    assert joiner["generations"][0]["restored_from"] is not None


def test_multiprocess_elastic_tests_are_slow_marked_and_bounded():
    """Tier-1 guard: the multi-process elastic tests can never hang the
    suite — each one is ``slow``-marked (excluded from tier-1) AND every
    fleet helper enforces a finite hard deadline that kills children on
    expiry."""
    import inspect
    mod = sys.modules[__name__]
    fleet_tests = [
        test_elastic_chaos_kills_at_boundary_and_midepoch,
        test_elastic_whole_job_preemption_respawn_is_bitwise,
        test_elastic_joiner_grows_world_at_epoch_boundary,
    ]
    for fn in fleet_tests:
        marks = [m.name for m in getattr(fn, "pytestmark", [])]
        assert "slow" in marks, f"{fn.__name__} must be slow-marked"
        src = inspect.getsource(fn)
        assert "timeout=" in src, f"{fn.__name__} must pass a deadline"
    # the helpers themselves: finite deadlines, kill on expiry
    raw = inspect.getsource(_spawn_raw_fleet)
    assert "communicate(timeout=" in raw and ".kill()" in raw
    sup = inspect.getsource(_run_elastic_fleet)
    assert "overall_timeout_s=timeout" in sup
    # and the supervisor's overall deadline really kills the fleet
    # (asserted behaviorally in tests/test_elastic.py's hung-worker test)
    from deeplearning4j_tpu.checkpoint import supervisor as sup_mod
    assert "kill_all()" in inspect.getsource(sup_mod.train_until_process)

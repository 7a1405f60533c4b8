"""checkpoint/ subsystem: async crash-consistent checkpointing, exact resume.

The contract under test is the subsystem's core claim: kill training at an
ARBITRARY step, ``restore_latest()``, resume — and the final params are
BITWISE-equal to the uninterrupted run (same rng split chain, same
counters), for both MultiLayerNetwork and ComputationGraph. Around that:
torn/corrupt checkpoints and manifests must DEGRADE (fall back to the last
complete checkpoint), never restore garbage; retention must prune while
pinning the best; and the early-stopping saver protocol must work.
"""

import os

import numpy as np
import jax
import pytest

from deeplearning4j_tpu.checkpoint import (CheckpointManager, FaultInjector,
                                           ManifestError, SimulatedCrash,
                                           flip_byte, load_manifest,
                                           tear_file)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import GraphBuilder, MergeVertex
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd


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


# ------------------------------------------------- crash → resume ≡ bitwise
def test_crash_resume_bitwise_multilayer(tmp_path):
    """Acceptance: kill at step 7 of a 2-epoch / 5-batch-per-epoch run,
    restore the step-6 checkpoint, resume — params, updater state AND
    counters end bitwise-equal to the uninterrupted run."""
    batches = _batches()  # 5 batches of 32
    assert len(batches) == 5
    E = 2

    ref = _net(seed=7)
    ref.fit(batches, num_epochs=E)

    cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=3)
    crashed = _net(seed=7).set_listeners(FaultInjector(kill_at_step=7))
    with pytest.raises(SimulatedCrash):
        crashed.fit(batches, num_epochs=E, checkpoint_manager=cm)
    cm.close()

    cm2 = CheckpointManager(tmp_path / "ck")
    resumed = cm2.restore_latest()
    rs = resumed._resume_state
    # checkpoints landed at steps 3 and 6; step 6 is batch 1 of epoch 1
    assert (rs.step, rs.epoch, rs.batch_in_epoch) == (6, 1, 1)
    resumed.fit(batches, num_epochs=E, checkpoint_manager=cm2)
    cm2.close()

    _assert_bitwise(ref.params, resumed.params)
    _assert_bitwise(ref.opt_state, resumed.opt_state)
    _assert_bitwise(ref.state, resumed.state)
    assert (ref.iteration, ref.epoch) == (resumed.iteration, resumed.epoch)
    # the continued rng chain must also be identical (next fit stays exact)
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(ref._rng)),
                                  np.asarray(jax.random.key_data(resumed._rng)))


def test_crash_resume_bitwise_graph(tmp_path):
    """Same contract for ComputationGraph (Adam updater: moments must
    restore exactly too)."""
    batches = _batches(128, 64)  # 2 batches per epoch
    E = 3

    ref = _graph(seed=5)
    ref.fit(batches, num_epochs=E)

    cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=2)
    crashed = _graph(seed=5).set_listeners(FaultInjector(kill_at_step=4))
    with pytest.raises(SimulatedCrash):
        crashed.fit(batches, num_epochs=E, checkpoint_manager=cm)
    cm.close()

    cm2 = CheckpointManager(tmp_path / "ck")
    resumed = cm2.restore_latest()
    # the crash fires in the step-4 listener, BEFORE step_end(4) could
    # checkpoint — the newest durable checkpoint is step 2
    assert resumed._resume_state.step == 2
    resumed.fit(batches, num_epochs=E, checkpoint_manager=cm2)
    cm2.close()

    _assert_bitwise(ref.params, resumed.params)
    _assert_bitwise(ref.opt_state, resumed.opt_state)
    assert (ref.iteration, ref.epoch) == (resumed.iteration, resumed.epoch)


def test_crash_resume_parallel_wrapper(tmp_path, devices):
    """ParallelWrapper.fit(checkpoint_manager=) checkpoints sharded
    training and resumes it mid-epoch (allclose: sharded reduction order
    may differ from nothing here, but keep the tolerance explicit)."""
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    batches = _batches(192, 48)  # 4 shardable batches per epoch

    ref = _net(seed=13)
    ParallelWrapper(ref, mesh=make_mesh()).fit(batches, num_epochs=2)

    cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=2)
    crashed = _net(seed=13).set_listeners(FaultInjector(kill_at_step=6))
    pw = ParallelWrapper(crashed, mesh=make_mesh())
    with pytest.raises(SimulatedCrash):
        pw.fit(batches, num_epochs=2, checkpoint_manager=cm)
    cm.close()

    cm2 = CheckpointManager(tmp_path / "ck")
    resumed = cm2.restore_latest()
    assert resumed._resume_state.step == 4  # step 6 crashed pre-step_end
    ParallelWrapper(resumed, mesh=make_mesh()).fit(
        batches, num_epochs=2, checkpoint_manager=cm2)
    cm2.close()
    for a, b in zip(_leaves(ref.params), _leaves(resumed.params)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    assert ref.iteration == resumed.iteration


def test_cluster_fit_local_shard_checkpoint_resume(tmp_path, devices):
    """ClusterTrainer.fit_local_shard(checkpoint_manager=) — the multi-host
    entry point — checkpoints and resumes (single-process here, so the
    process-0 gate and barrier are the no-op fast path)."""
    from deeplearning4j_tpu.parallel import ClusterTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    batches = _batches(192, 48)

    ref = _net(seed=17)
    ClusterTrainer(ref, mesh=make_mesh()).fit_local_shard(batches, num_epochs=2)

    cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=3)
    crashed = _net(seed=17).set_listeners(FaultInjector(kill_at_step=5))
    with pytest.raises(SimulatedCrash):
        ClusterTrainer(crashed, mesh=make_mesh()).fit_local_shard(
            batches, num_epochs=2, checkpoint_manager=cm)
    cm.close()

    cm2 = CheckpointManager(tmp_path / "ck")
    resumed = cm2.restore_latest()
    assert resumed._resume_state.step == 3
    ClusterTrainer(resumed, mesh=make_mesh()).fit_local_shard(
        batches, num_epochs=2, checkpoint_manager=cm2)
    cm2.close()
    for a, b in zip(_leaves(ref.params), _leaves(resumed.params)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    assert ref.iteration == resumed.iteration


# --------------------------------------------------- durability / fallback
def test_torn_checkpoint_falls_back_to_previous(tmp_path):
    """A truncated (torn-write) newest checkpoint must not restore: the
    sha256 in the journal catches it and the previous complete checkpoint
    is returned instead."""
    d = str(tmp_path / "ck")
    cm = CheckpointManager(d, async_write=False)
    net = _net()
    batches = _batches(96, 32)
    net.fit(batches[0])
    cm.save(net)
    net.fit(batches[1])
    newest = cm.save(net)
    tear_file(os.path.join(d, newest))
    restored = cm.restore_latest()
    assert restored._resume_state.step == 1  # fell back past step 2
    cm.close()


def test_bitflip_detected_by_checksum(tmp_path):
    """Silent corruption (same size, one byte flipped) — only the sha
    catches this; restore must fall back, not return wrong params."""
    d = str(tmp_path / "ck")
    cm = CheckpointManager(d, async_write=False)
    net = _net()
    batches = _batches(96, 32)
    net.fit(batches[0])
    cm.save(net)
    net.fit(batches[1])
    newest = cm.save(net)
    flip_byte(os.path.join(d, newest), offset=200)
    restored = cm.restore_latest()
    assert restored._resume_state.step == 1
    cm.close()


def test_corrupt_manifest_rebuilds_and_scan_falls_back(tmp_path):
    """A torn manifest must not lose the run: a fresh manager rebuilds the
    journal from the surviving files, and even with a torn newest FILE on
    top of it the zip CRC layer rejects the file and restore falls back."""
    d = str(tmp_path / "ck")
    cm = CheckpointManager(d, async_write=False)
    net = _net()
    batches = _batches(96, 32)
    net.fit(batches[0])
    cm.save(net, metric=3.0)
    net.fit(batches[1])
    newest = cm.save(net, metric=1.0)
    cm.close()
    with open(os.path.join(d, "manifest.json"), "w") as f:
        f.write("{torn")
    with pytest.raises(ManifestError):
        load_manifest(d)
    tear_file(os.path.join(d, newest))
    cm2 = CheckpointManager(d)  # rebuilds the manifest from a scan
    assert load_manifest(d) is not None
    # the rebuild recovers full metadata from each readable zip (the torn
    # one is skipped), so step/metric-dependent surfaces keep working
    entries = cm2.checkpoints()
    assert [(e["step"], e["metric"]) for e in entries] == [(1, 3.0)]
    assert all("size" in e and e["sha256"] for e in entries)
    restored = cm2.restore_latest()
    assert restored._resume_state.step == 1
    assert cm2.restore_best()._restored_from.step == 1
    cm2.close()


def test_missing_manifest_rebuilds_full_entries(tmp_path):
    """A DELETED manifest (crash before the first journal write, or user
    cleanup) must behave like a torn one: the rebuild recovers full
    entries from the zips, so restore_best/checkpoints() work, not just
    restore_latest."""
    d = str(tmp_path / "ck")
    cm = CheckpointManager(d, async_write=False)
    net = _net()
    batches = _batches(96, 32)
    net.fit(batches[0])
    cm.save(net, metric=2.0)
    net.fit(batches[1])
    cm.save(net, metric=7.0)
    cm.close()
    os.remove(os.path.join(d, "manifest.json"))
    cm2 = CheckpointManager(d)
    assert [(e["step"], e["metric"]) for e in cm2.checkpoints()] == \
        [(1, 2.0), (2, 7.0)]
    assert cm2.restore_best()._restored_from.step == 1
    assert cm2.restore_latest()._resume_state.step == 2
    cm2.close()


def test_early_stopping_parallel_trainer_accepts_checkpoint_manager(
        tmp_path, devices):
    from deeplearning4j_tpu.earlystopping.conditions import (
        MaxEpochsTerminationCondition)
    from deeplearning4j_tpu.earlystopping.trainer import (
        EarlyStoppingConfiguration)
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.trainer import (
        EarlyStoppingParallelTrainer)
    cm = CheckpointManager(tmp_path / "ck")
    config = EarlyStoppingConfiguration(
        epoch_termination_conditions=[MaxEpochsTerminationCondition(2)])
    batches = _batches(96, 48)
    trainer = EarlyStoppingParallelTrainer(config, _net(seed=29), batches,
                                           validation_data=batches,
                                           mesh=make_mesh(),
                                           checkpoint_manager=cm)
    result = trainer.fit()
    assert result.best_model is not None
    assert result.best_model._restored_from is not None
    cm.close()


def test_restore_latest_empty_dir_returns_none(tmp_path):
    cm = CheckpointManager(tmp_path / "empty")
    assert cm.restore_latest() is None
    assert cm.restore_best() is None
    cm.close()


def test_checkpoint_restores_rng_and_counters_exactly(tmp_path):
    """The restored model must carry the exact PRNG key, iteration and
    epoch — the ingredients of bitwise resume."""
    cm = CheckpointManager(tmp_path / "ck", async_write=False)
    net = _net()
    net.fit(_batches(64, 32), num_epochs=2)
    cm.save(net)
    restored = cm.restore_latest()
    cm.close()
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(net._rng)),
        np.asarray(jax.random.key_data(restored._rng)))
    assert (restored.iteration, restored.epoch) == (net.iteration, net.epoch)
    _assert_bitwise(net.params, restored.params)
    _assert_bitwise(net.opt_state, restored.opt_state)


# ---------------------------------------------------------------- retention
def test_retention_keep_last_prunes_and_keep_best_pins(tmp_path):
    d = str(tmp_path / "ck")
    cm = CheckpointManager(d, keep_last=2, keep_best="min", async_write=False)
    net = _net()
    batches = _batches(160, 32)
    for ds, metric in zip(batches, [5.0, 1.0, 4.0, 3.0, 2.0]):
        net.fit(ds)
        cm.save(net, metric=metric)
    entries = cm.checkpoints()
    # best (metric 1.0, step 2) pinned + the last two (steps 4, 5)
    assert [(e["step"], e["metric"]) for e in entries] == \
        [(2, 1.0), (4, 3.0), (5, 2.0)]
    on_disk = sorted(f for f in os.listdir(d) if f.endswith(".zip"))
    assert len(on_disk) == 3
    best = cm.restore_best()
    assert best._restored_from.step == 2
    # model SELECTION must not arm crash-resume: a later fit() on the best
    # model trains normally instead of reinterpreting num_epochs/skipping
    assert best._resume_state is None
    assert cm.restore_latest()._resume_state.step == 5
    cm.close()


def test_save_every_secs_trigger(tmp_path):
    """save_every_secs=0 degenerates to every step — the time trigger path."""
    cm = CheckpointManager(tmp_path / "ck", save_every_secs=0.0,
                           async_write=False)
    net = _net()
    net.fit(_batches(96, 32), checkpoint_manager=cm)
    # one per step_end (3) + the epoch_end boundary save
    assert len(cm.checkpoints()) == 4
    assert cm.checkpoints()[-1]["batch_in_epoch"] == 0  # epoch boundary
    cm.close()


def test_step_trigger_is_threshold_not_modulo(tmp_path):
    """tbptt batches advance iteration by several windows per step_end; an
    exact-modulo trigger would fire at lcm(windows, n) or never. The
    trigger is '>= n steps since last save'."""
    cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=10,
                           async_write=False)
    net = _net()
    net.fit(_batches(32, 32))  # materialize params; iteration -> 1
    for it in (7, 14, 21, 28):  # tbptt-style stride of 7
        net.iteration = it
        cm.step_end(net, batch_in_epoch=1)
    assert [e["step"] for e in cm.checkpoints()] == [14, 28]
    cm.close()


def test_resume_skip_raises_on_short_stream():
    """A stream shorter than the skip count violates the must-replay
    precondition of bitwise resume — loud error, not a silent no-op
    epoch."""
    from deeplearning4j_tpu.checkpoint.manager import skip_consumed_batches
    assert list(skip_consumed_batches([1, 2, 3], 2)) == [3]
    with pytest.raises(ValueError, match="ended after 2"):
        skip_consumed_batches([1, 2], 3)


def test_saver_usage_defaults_keep_best_so_retention_cannot_prune_it(tmp_path):
    cm = CheckpointManager(tmp_path / "ck", keep_last=2, async_write=False)
    net = _net()
    batches = _batches(160, 32)
    for ds, score in zip(batches, [5.0, 1.0, 4.0, 3.0, 2.0]):
        net.fit(ds)
        cm.save_best_model(net, score)  # saver protocol arms keep_best
    assert cm.keep_best == "min"
    assert cm.restore_best()._restored_from.step == 2  # metric 1.0 survived
    cm.close()


# -------------------------------------------------------------- async path
def test_async_flush_commits_everything_and_matches_live(tmp_path):
    cm = CheckpointManager(tmp_path / "ck", save_every_n_steps=1)
    net = _net()
    net.fit(_batches(96, 32), checkpoint_manager=cm)
    cm.flush()
    assert len(cm.checkpoints()) == 3
    assert cm.saves_committed == cm.saves_requested == 3
    restored = cm.restore_latest()
    cm.close()
    _assert_bitwise(net.params, restored.params)


def test_async_write_error_surfaces_on_training_thread(tmp_path):
    """A failing writer must raise CheckpointError at the next save/flush,
    not vanish into the worker. (A plain rmtree is silently HEALED — the
    writer recreates the directory — so squat a file on the path.)"""
    import shutil
    from deeplearning4j_tpu.checkpoint import CheckpointError
    d = str(tmp_path / "ck")
    cm = CheckpointManager(d, save_every_n_steps=1)
    net = _net()
    net.fit(_batches(32, 32), checkpoint_manager=cm)
    cm.flush()
    shutil.rmtree(d)
    open(d, "w").close()  # a FILE where the directory was
    net.fit(_batches(32, 32), checkpoint_manager=cm)  # enqueue doomed write
    with pytest.raises(CheckpointError):
        cm.flush()
    cm.close()


def test_context_manager_and_double_close(tmp_path):
    with CheckpointManager(tmp_path / "ck", save_every_n_steps=1) as cm:
        _net().fit(_batches(64, 32), checkpoint_manager=cm)
    cm.close()  # idempotent
    assert len(cm.checkpoints()) == 2


# --------------------------------------------------- early-stopping backend
def test_early_stopping_accepts_checkpoint_manager_as_saver(tmp_path):
    from deeplearning4j_tpu.earlystopping.conditions import (
        MaxEpochsTerminationCondition)
    from deeplearning4j_tpu.earlystopping.trainer import (
        EarlyStoppingConfiguration, EarlyStoppingTrainer)
    cm = CheckpointManager(tmp_path / "ck", keep_best="min")
    config = EarlyStoppingConfiguration(
        epoch_termination_conditions=[MaxEpochsTerminationCondition(3)])
    batches = _batches(96, 32)
    trainer = EarlyStoppingTrainer(config, _net(), batches,
                                   validation_data=batches,
                                   checkpoint_manager=cm)
    result = trainer.fit()
    assert result.best_model is not None
    # the "best model" came back through a durable checkpoint, WITHOUT a
    # consumable resume marker (fine-tuning it must train normally)
    assert result.best_model._restored_from is not None
    assert result.best_model._resume_state is None
    entries = [e for e in cm.checkpoints() if e["metric"] is not None]
    assert entries and min(e["metric"] for e in entries) == \
        pytest.approx(result.best_model_score)
    out = result.best_model.output(batches[0].features)
    assert out.shape == (32, 3)
    cm.close()

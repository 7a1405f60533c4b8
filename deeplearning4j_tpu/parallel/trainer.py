"""Data/tensor-parallel training.

Parity surface: reference ParallelWrapper
(deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java:58 — worker
threads, device affinity :137, averaging/gradient-sharing dispatch loop
:210-265) and the Spark training masters
(ParameterAveragingTrainingMaster.java:308, SharedTrainingMaster.java:302).

TPU-native semantics: the wrapped network's *existing* jit train step is run
with the global batch sharded over the mesh's 'data' axis and params
replicated (or sharded over 'model' for tensor parallelism). XLA/GSPMD
compiles the gradient all-reduce into the step — equivalent to
averaging_frequency=1 EXACT parameter averaging, every step, with no
queues, no compression, no parameter server. DP-2's lossy threshold encoding
(EncodedGradientsAccumulator) is unnecessary on ICI bandwidth and is NOT
applied by default; for cross-slice DCN deployments pass
``grad_compression=`` (parallel/compress.py) to compile threshold/top-k/
quantized encoding with error feedback into the step.
"""

from __future__ import annotations

import contextlib
import logging
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger(__name__)

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.engine import run_epochs, step_host
from deeplearning4j_tpu.obs.trace import get_tracer
from deeplearning4j_tpu.optimize.updaters import is_sgd_family
from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, data_sharding, make_mesh, replicated, shard_batch,
    tp_shardings,
)


class _EpochHooksSuppressed:
    """Listener proxy forwarding everything but epoch hooks (used when a
    minibatch is routed through model.fit, which counts a full epoch)."""

    def __init__(self, inner):
        self._inner = inner

    def on_epoch_start(self, model):
        pass

    def on_epoch_end(self, model):
        pass

    def __getattr__(self, name):
        return getattr(self._inner, name)


class ParallelWrapper:
    """Data-parallel (optionally tensor-parallel) training wrapper.

    Example::

        mesh = make_mesh()                      # all chips on 'data'
        pw = ParallelWrapper(net, mesh=mesh)
        pw.fit(iterator, num_epochs=3)

    Unlike the reference there are no replicas: params live once, sharded or
    replicated across the mesh; ``net.params`` stays valid throughout.
    """

    def __init__(self, model, mesh: Optional[Mesh] = None,
                 tensor_parallel: bool = False,
                 prefetch_buffer: int = 2,
                 collect_stats: bool = False,
                 grad_compression=None):
        """``grad_compression`` (a parallel/compress.py
        ``GradientCompression`` scheme, e.g. ``ThresholdCompression()``)
        compiles lossy gradient encoding with error feedback into the
        train step — the TPU-native analogue of the reference's
        threshold-encoded gradient sharing. Worth it when the all-reduce
        crosses DCN (multi-slice); pure overhead on a single ICI slice.
        A model restored from a compressed checkpoint already carries its
        scheme; passing a DIFFERENT one here raises."""
        from deeplearning4j_tpu.parallel.stats import TrainingStats
        self.model = model
        self.mesh = mesh if mesh is not None else make_mesh()
        self.tensor_parallel = tensor_parallel
        self.prefetch_buffer = prefetch_buffer
        self.grad_compression = grad_compression
        self._placed = False
        self._warned_ragged = False
        # phase timing (reference CommonSparkTrainingStats; enable with
        # collect_stats=True, read via .stats)
        self.stats = TrainingStats() if collect_stats else None
        if self.stats is not None:
            # obs: absorbed at scrape time like ParallelInference.stats(),
            # so /metrics carries the phase breakdown with no per-step writes
            from deeplearning4j_tpu.obs.registry import (get_registry,
                                                         watch_training_stats)
            watch_training_stats(get_registry(), self.stats)

    # ---- parameter placement ----
    def _place_params(self):
        if self._placed:
            return
        m = self.model
        if m.params is None:
            m.init()
        if self.tensor_parallel:
            p_sh = tp_shardings(self.mesh, m.params)
        else:
            p_sh = jax.tree_util.tree_map(lambda a: replicated(self.mesh), m.params)
        m.params = jax.device_put(m.params, p_sh)
        m.state = jax.device_put(
            m.state, jax.tree_util.tree_map(lambda a: replicated(self.mesh), m.state))
        # optimizer state mirrors param shardings (moments have param shapes);
        # scalar counters replicate
        def opt_sh(a):
            return replicated(self.mesh)
        if self.tensor_parallel:
            # re-init optimizer state on the sharded params so moment tensors
            # inherit the param shardings
            m.opt_state = m.init_opt_state(m.params)
        else:
            m.opt_state = jax.device_put(
                m.opt_state, jax.tree_util.tree_map(opt_sh, m.opt_state))
        self._place_compress_state()
        self._placed = True

    def _place_compress_state(self):
        """Enable + place the gradient-compression state: the wrapper's
        scheme (or one the model already carries, e.g. restored from a
        compressed checkpoint) is validated by ``enable_grad_compression``,
        the residual/controller state is initialized if absent, and its
        arrays are placed over the mesh — the residual mirrors the param
        placement (tp shardings under tensor parallelism, replicated
        otherwise); controller/accumulator scalars replicate."""
        m = self.model
        scheme = (self.grad_compression if self.grad_compression is not None
                  else getattr(m, "grad_compression", None))
        if scheme is None:
            return
        from deeplearning4j_tpu.parallel.compress import (
            enable_grad_compression, ensure_compress_state)
        enable_grad_compression(m, scheme)
        cs = ensure_compress_state(m)
        residual = cs["residual"]
        if residual is not None:
            if self.tensor_parallel:
                r_sh = tp_shardings(self.mesh, residual)
            else:
                r_sh = jax.tree_util.tree_map(
                    lambda a: replicated(self.mesh), residual)
            residual = jax.device_put(residual, r_sh)
        rest = {k: cs[k] for k in ("ctrl", "acc")}
        rest = jax.device_put(rest, jax.tree_util.tree_map(
            lambda a: replicated(self.mesh), rest))
        m.compress_state = {"residual": residual, **rest}

    def _shard_dataset(self, ds: DataSet) -> DataSet:
        n = ds.features.shape[0]
        dp = self.mesh.shape[DATA_AXIS]
        if n % dp:
            raise ValueError(
                f"Global batch {n} not divisible by data-parallel size {dp}")

        def put(a):
            return None if a is None else shard_batch(self.mesh, a)

        return DataSet(put(ds.features), put(ds.labels),
                       put(ds.features_mask), put(ds.labels_mask))

    def _model_fit_batch(self, sharded: DataSet):
        """One training step WITHOUT the model's own epoch-listener side
        effects (model.fit(DataSet) counts a full epoch, so routing batches
        through it would fire epoch hooks once per minibatch). Uses the
        model's internal batch path for the standard SGD case; tbptt/solver
        configs fall back to model.fit."""
        m = self.model
        # is_sgd_family is the ONE normalized-name dispatch shared with
        # fit()'s solver dispatch and the compression guards — not another
        # ad-hoc lowercase string tuple (a graph's configuration names no
        # algorithm: it is always the jitted step)
        if (m.conf.backprop_type == "standard"
                and is_sgd_family(getattr(m.conf, "optimization_algo",
                                          "stochastic_gradient_descent"))):
            m._fit_batch(m._get_jitted("train"), sharded)
        else:
            # tbptt/solver configs go through model.fit; suppress its
            # per-call epoch side effects (hooks + epoch counter) so the
            # wrapper's once-per-epoch semantics hold for every config
            saved_listeners = m.listeners
            epoch0 = m.epoch
            m.listeners = [_EpochHooksSuppressed(l) for l in saved_listeners]
            try:
                m.fit(sharded)
            finally:
                m.listeners = saved_listeners
                m.epoch = epoch0

    def _is_ragged(self, ds: DataSet) -> bool:
        """Whether this batch cannot shard evenly. Overridden by
        ClusterTrainer with a PROCESS-LOCAL predicate so every host reaches
        the same drop/train decision without a coordination collective."""
        return bool(ds.num_examples() % self.mesh.shape[DATA_AXIS])

    def _phase(self, name: str):
        """TrainingStats' timer for one phase; nothing when stats are off."""
        return (contextlib.nullcontext() if self.stats is None
                else self.stats.time(name))

    def _train_batch(self, ds: DataSet, examples: int):
        """Place, shard and train one batch (inside ``with self.mesh``).
        The wrapper's own hand-over is a ``train.stage`` span (obs/trace.py)
        beside the model's; dispatch, post and listeners are the model's
        ``_fit_batch``'s. ``examples`` is what TrainingStats counts."""
        with get_tracer().span("train.stage", step=self.model.iteration), \
                self._phase("data_placement"):
            self._place_params()
            sharded = self._shard_dataset(ds)
        with self._phase("train_dispatch"):
            self._model_fit_batch(sharded)
        if self.stats is not None:
            self.stats.examples += examples
            self.stats.minibatches += 1

    def fit_batch(self, ds: DataSet, drop_ragged: bool = False) -> bool:
        """Train on ONE global batch (sharded over the mesh); returns whether
        the batch was trained. ``drop_ragged`` drops batches that don't
        divide the data-parallel size instead of raising — static shapes are
        the TPU contract, so a ragged tail is dropped, not recompiled."""
        dp = self.mesh.shape[DATA_AXIS]
        if self._is_ragged(ds) and drop_ragged:
            if not self._warned_ragged:
                log.warning(
                    "Dropping ragged batch of %d examples (global batch must "
                    "divide data-parallel size %d)", ds.num_examples(), dp)
                self._warned_ragged = True
            return False
        with self.mesh:
            self._train_batch(ds, ds.num_examples())
        return True

    # ---- training (reference ParallelWrapper.fit dispatch loop :210) ----
    def fit(self, data, num_epochs: int = 1, prefetch: bool = False,
            checkpoint_manager=None):
        """``prefetch=True`` wraps the iterator in a DevicePrefetchIterator
        (perf/prefetch.py): batch N+1's sharded device_put is issued while
        step N runs, so host→device transfer stops serializing the step
        loop. Ragged batches pass through on host and keep the usual
        drop-ragged policy.

        ``checkpoint_manager`` (checkpoint.CheckpointManager) checkpoints
        after trained batches per its triggers and resumes a restored model
        at the exact step — same semantics as MultiLayerNetwork.fit
        (num_epochs is the run's TOTAL target when resuming)."""
        self._place_params()
        explicit_single = isinstance(data, DataSet)
        if explicit_single:
            data = [data]

        def epoch_drained(seen, trained, resumed_mid_epoch):
            _raise_if_no_batches(seen)
            if trained == 0 and not resumed_mid_epoch:
                raise ValueError(
                    "Every batch this epoch was dropped as ragged — the "
                    f"batch size never divides the data-parallel size "
                    f"{self.mesh.shape[DATA_AXIS]}; pick a divisible batch")

        def epoch_done():
            if self.stats is not None:
                # steps dispatch asynchronously: one sync per epoch shows
                # the true device time under "epoch_sync"
                with self.stats.time("epoch_sync"):
                    jax.block_until_ready(self.model.params)
                self._record_compile_counters()

        # a single explicit ragged DataSet raises (dropping it would train
        # on nothing); iterator tail batches drop-remainder
        run_epochs(self.model, data, num_epochs,
                   lambda ds: self.fit_batch(ds,
                                             drop_ragged=not explicit_single),
                   prefetch=({"mesh": self.mesh}
                             if prefetch and not explicit_single else None),
                   checkpoint_manager=checkpoint_manager,
                   epoch_drained=epoch_drained, epoch_done=epoch_done)
        return self

    def _record_compile_counters(self):
        """Surface the model's compile/dispatch counts in TrainingStats —
        'N minibatches, 1 compile' becomes assertable next to the phase
        timings (perf/compile_watch.py)."""
        cw = getattr(self.model, "compile_watch", None)
        if self.stats is not None and cw is not None:
            self.stats.set_counter("model_compiles", cw.compiles())
            self.stats.set_counter("model_dispatches", cw.dispatches())

    def output(self, x) -> np.ndarray:
        self._place_params()
        with self.mesh:
            return self.model.output(shard_batch(self.mesh, x))


class ClusterTrainer(ParallelWrapper):
    """Multi-host training (reference: the Spark training masters +
    jax.distributed). Each host runs the same program; the mesh spans all
    hosts' devices and each host feeds its local shard of the global batch.

    Replaces: SparkDl4jMultiLayer.fit(RDD) + ParameterAveragingTrainingMaster
    (sync averaging becomes the compiled all-reduce) and SharedTrainingMaster
    (async Aeron gradient sharing is intentionally not reproduced — see module
    docstring).

    Usage (per host)::

        ClusterTrainer.initialize(coordinator_address="host0:1234",
                                  num_processes=4, process_id=rank)
        trainer = ClusterTrainer(net)           # mesh over ALL global devices
        trainer.fit_local_shard(local_iterator) # per-host local data
    """

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # whether this epoch's first batch passed the equal-shard check
        # (see _verify_equal_local_shards)
        self._epoch_shards_verified = False

    @staticmethod
    def initialize(coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None):
        """jax.distributed.initialize wrapper (DCN bootstrap). No-op when
        single-process."""
        if num_processes is None or num_processes <= 1:
            return
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)

    # ---- multi-host batch assembly ----
    def _verify_equal_local_shards(self, n_local: int, _gather=None):
        """Pre-assembly guard: every host must feed the SAME local batch
        size, or ``make_array_from_process_local_data`` fails (or hangs a
        peer) deep inside assembly. One all-gather of the local count at
        the FIRST batch of each epoch raises a named UnequalShardError on
        every host simultaneously. The check must be an
        unconditionally-aligned collective: every host runs it at the
        same batch index or none does — a value-keyed cache would turn it
        into a conditional collective that deadlocks in exactly the
        unequal case it exists to catch. (Mid-epoch size changes are not
        re-verified for the same reason; mismatched per-host sequences of
        sizes are a systematic sharding bug visible at batch one.)
        ``_gather`` is injectable for tests."""
        if self._epoch_shards_verified:
            return
        import jax as _jax
        if _gather is None:
            if _jax.process_count() == 1:
                self._epoch_shards_verified = True
                return

            def _gather(n):
                from jax.experimental import multihost_utils
                return np.asarray(multihost_utils.process_allgather(
                    np.array([n], np.int64))).ravel()
        from deeplearning4j_tpu.parallel.sharding import (
            check_equal_local_shards)
        check_equal_local_shards(_gather(n_local))
        self._epoch_shards_verified = True

    def _assemble_global(self, ds: DataSet) -> DataSet:
        """Build the global sharded batch from this process's LOCAL rows
        (``jax.make_array_from_process_local_data``); single-process falls
        back to a plain sharded device_put."""
        self._verify_equal_local_shards(ds.num_examples())

        def gput(a):
            if a is None:
                return None
            if jax.process_count() == 1:
                return shard_batch(self.mesh, a)
            arr = np.asarray(a)
            return jax.make_array_from_process_local_data(
                data_sharding(self.mesh, arr.ndim), arr)
        return DataSet(gput(ds.features), gput(ds.labels),
                       features_mask=gput(ds.features_mask),
                       labels_mask=gput(ds.labels_mask))

    # ParallelWrapper.fit_batch / EarlyStoppingParallelTrainer route here:
    # in cluster mode the incoming DataSet is the process-LOCAL shard
    def _shard_dataset(self, ds: DataSet) -> DataSet:
        if getattr(ds, "_staged_global", False):
            # assembled one batch ahead by the prefetch stage; the marker
            # (not an array-type test) distinguishes this from a USER
            # device-resident local DataSet, which must still assemble
            return ds
        n_global = ds.num_examples() * jax.process_count()
        dp = self.mesh.shape[DATA_AXIS]
        if n_global % dp:
            raise ValueError(
                f"Global batch {n_global} (local {ds.num_examples()} x "
                f"{jax.process_count()} processes) not divisible by "
                f"data-parallel size {dp}")
        return self._assemble_global(ds)

    def _is_ragged(self, ds: DataSet) -> bool:
        """PROCESS-LOCAL ragged predicate: local rows vs this host's share
        of the data axis. Every host must feed the same local batch size
        (shard_iterator guarantees it) — with equal shards this decision is
        identical on all hosts, so no host can drop a batch its peers train
        (which would orphan their collective and hang them). Unequal local
        shards raise a named UnequalShardError BEFORE assembly
        (_verify_equal_local_shards) listing every host's count, instead
        of failing opaquely inside make_array_from_process_local_data."""
        local_share = max(1, self.mesh.shape[DATA_AXIS]
                          // max(1, jax.process_count()))
        return bool(ds.num_examples() % local_share)

    def fit(self, data, num_epochs: int = 1, prefetch: bool = False,
            checkpoint_manager=None):
        """Train from an ORDINARY global iterator: every process walks the
        same iterator and this trainer internally takes the process's row
        shard of each batch (parallel/sharding.py), so user code needs no
        manual pre-sharding (reference SparkDl4jMultiLayer.fit(RDD)
        ergonomics).

        ``prefetch=True`` stages batch N+1's global-batch assembly
        (``make_array_from_process_local_data`` — an async transfer, like
        device_put) while step N runs; see ``fit_local_shard``.
        ``checkpoint_manager`` checkpoints per its triggers — in cluster
        mode only process 0 writes, the others barrier under the watchdog
        deadline (checkpoint/manager.py)."""
        from deeplearning4j_tpu.parallel.sharding import shard_iterator
        if isinstance(data, DataSet):
            data = [data]
        local = shard_iterator(data) if jax.process_count() > 1 else data
        return self.fit_local_shard(local, num_epochs=num_epochs,
                                    prefetch=prefetch,
                                    checkpoint_manager=checkpoint_manager)

    def _stage_local_batch(self, ds: DataSet) -> DataSet:
        """Prefetch hook (perf/prefetch.py place_fn): assemble the global
        sharded batch EARLY so its host→device transfer overlaps the
        in-flight step. Ragged batches return unchanged — host-side — so
        the dispatch-time divisibility error stays loud and clear."""
        if self._is_ragged(ds):
            return ds
        staged = self._shard_dataset(ds)
        staged._staged_global = True  # consumed by _shard_dataset/stats
        return staged

    def score_local_shard(self, ds: DataSet) -> float:
        """Loss over a validation batch given as per-process local rows
        (the multi-host analogue of ``model.score_dataset``). Goes through
        ``_shard_dataset`` so a ragged validation batch raises the same
        clear divisibility error as the training path."""
        self._place_params()
        with self.mesh:
            return float(self.model.score_dataset(self._shard_dataset(ds)))

    def fit_local_shard(self, data, num_epochs: int = 1,
                        collective_timeout_s: Optional[float] = None,
                        watchdog_every: int = 10, prefetch: bool = False,
                        checkpoint_manager=None):
        """Feed per-host local batches; assembles the global sharded array
        from process-local data (multi-host path of ICI+DCN training).

        ``collective_timeout_s`` arms a CollectiveWatchdog (SURVEY §5): every
        ``watchdog_every`` batches the host syncs the dispatched step under a
        deadline, so a hung DCN collective (dead peer / partition) raises a
        diagnostic CollectiveTimeoutError instead of blocking forever.

        ``prefetch=True`` runs the global-batch assembly
        (``_stage_local_batch``) one batch ahead through a
        DevicePrefetchIterator, so batch N+1's host→device transfer
        overlaps step N instead of serializing the loop.
        ``checkpoint_manager`` checkpoints after each step per its triggers
        (process 0 writes, peers barrier) and resumes a restored model at
        the exact step, skipping the batches its checkpoint already
        consumed."""
        wd = None
        if collective_timeout_s is not None:
            from deeplearning4j_tpu.parallel.watchdog import CollectiveWatchdog
            wd = CollectiveWatchdog(timeout_s=collective_timeout_s)
        self._place_params()
        if isinstance(data, DataSet):
            data = [data]
        tracer = get_tracer()
        step_no = 0

        def turn(ds, seen):
            nonlocal step_no
            above = tracer.current()

            # _model_fit_batch, not model.fit: per-epoch hooks and the
            # epoch counter must fire once per EPOCH, not once per
            # minibatch (same contract as ParallelWrapper.fit)
            def one_step():
                # a prefetch-staged batch is already the GLOBAL array:
                # normalize the examples counter back to process-local
                # rows so the metric doesn't change meaning with the
                # prefetch flag
                n_local = ds.num_examples()
                if getattr(ds, "_staged_global", False):
                    n_local //= max(1, jax.process_count())
                # under a watchdog this runs on its worker thread: attach
                # keeps the step in its turn's tree
                with tracer.attach(above), step_host(tracer, self.model,
                                                     n_local):
                    self._train_batch(ds, n_local)
            if wd is None:
                one_step()
            else:
                # the dispatch itself can block synchronously on a dead
                # peer's collective rendezvous, so the deadline must wrap
                # the whole call, not just a later sync
                wd.call(one_step, what=f"cluster step {step_no + 1} dispatch")
            step_no += 1
            if wd is not None and step_no % max(1, watchdog_every) == 0:
                wd.sync(self.model.params, what=f"cluster step {step_no}")
            if checkpoint_manager is not None:
                checkpoint_manager.step_end(self.model, batch_in_epoch=seen)

        def epoch_start():
            # every host re-verifies at its first batch — an ALIGNED
            # once-per-epoch collective (see _verify_equal_local_shards)
            self._epoch_shards_verified = False

        with self.mesh:
            # the elastic worker trains through THIS loop, so its crash
            # ring / event log carry the per-step breakdown too; a batch
            # is assembled (place_fn) one ahead, never for the skipped
            run_epochs(self.model, data, num_epochs, turn=turn,
                       prefetch=({"place_fn": self._stage_local_batch}
                                 if prefetch else None),
                       checkpoint_manager=checkpoint_manager,
                       epoch_start=epoch_start,
                       epoch_drained=lambda seen, *_: _raise_if_no_batches(
                           seen),
                       epoch_done=self._record_compile_counters)
            if wd is not None:
                # tail steps after the last every-N sync must not escape the
                # deadline — a hang there would otherwise surface only at
                # the caller's next (unguarded) host sync
                wd.sync(self.model.params, what=f"epoch end (step {step_no})")
        return self


def _raise_if_no_batches(seen: int) -> None:
    if seen == 0:
        raise ValueError(
            "No batches this epoch — the data iterable is empty or a "
            "one-shot generator exhausted by a previous epoch; pass a "
            "re-iterable DataSetIterator")


from deeplearning4j_tpu.earlystopping.trainer import EarlyStoppingTrainer


class EarlyStoppingParallelTrainer(EarlyStoppingTrainer):
    """Early stopping composed with data-parallel training (reference
    deeplearning4j-scaleout-parallelwrapper/.../EarlyStoppingParallelTrainer.java:44).

    Each training batch routes through a ParallelWrapper (global batch
    sharded over the mesh); validation scoring runs on the same
    replicated-parameter model, so savers/conditions see identical
    semantics to the single-device EarlyStoppingTrainer.
    """

    def __init__(self, config, model, train_data, validation_data=None,
                 score_calculator=None, mesh: Optional[Mesh] = None,
                 tensor_parallel: bool = False, cluster: bool = False,
                 checkpoint_manager=None):
        """``cluster=True`` routes batches through a ClusterTrainer (multi-
        host assembly of per-process local shards) and, when no explicit
        score_calculator is given, scores validation data through the same
        multi-host path (local rows per process, global loss).
        ``checkpoint_manager`` plugs checkpoint/ in as the saver backend,
        exactly as on the base EarlyStoppingTrainer."""
        trainer_holder = []
        if cluster and score_calculator is None and validation_data is not None:
            def score_calculator(m):
                total, n = 0.0, 0
                for ds in validation_data:
                    total += (trainer_holder[0].score_local_shard(ds)
                              * ds.num_examples())
                    n += ds.num_examples()
                return total / max(n, 1)
        super().__init__(config, model, train_data, validation_data,
                         score_calculator,
                         checkpoint_manager=checkpoint_manager)
        if cluster:
            self.wrapper = ClusterTrainer(model, mesh=mesh,
                                          tensor_parallel=tensor_parallel)
            trainer_holder.append(self.wrapper)
        else:
            self.wrapper = ParallelWrapper(model, mesh=mesh,
                                           tensor_parallel=tensor_parallel)

    def _fit_batch(self, ds) -> bool:
        # per-batch path: no epoch-listener double fire, ragged tails dropped
        # (the base trainer raises if an entire epoch trains nothing)
        return self.wrapper.fit_batch(ds, drop_ragged=True)

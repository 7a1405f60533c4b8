"""Parallel inference.

Parity surface: reference parallelism/ParallelInference.java:32 (round-robin
device-pinned replicas, :97-134 observables/worker loop) +
BatchedInferenceObservable / BasicInferenceObservable dynamic batching.

TPU-native: one jit-compiled forward with the batch sharded over the mesh
replaces per-device replicas. Dynamic batching keeps the reference's shape:
requests enqueue as observables; a background worker coalesces up to
``batch_limit`` requests (waiting at most ``queue_timeout_ms`` for
stragglers) into ONE device dispatch and distributes the per-request slices.

Shape stability: every dispatch pads to a canonical bucket size
(perf/bucketing.BucketPolicy — on by default), so a serving mix of request
sizes 1..32 compiles a handful of programs instead of one per distinct
coalesced size; ``warmup()`` pre-compiles every bucket before traffic
arrives, and ``stats()`` reports batch-size percentiles, per-bucket dispatch
counts and the model's compile counters.
"""

from __future__ import annotations

import queue
import random
import threading
import time
from collections import Counter, deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.parallel.mesh import data_sharding, make_mesh, replicated
from deeplearning4j_tpu.perf.bucketing import BucketPolicy, pad_to_bucket


class QueueFullError(RuntimeError):
    """The bounded request queue stayed full past the admission timeout.

    Raised by :meth:`ParallelInference.submit` instead of blocking forever
    (the pre-bound queue grew without limit under a stalled worker). A
    serving front-end maps this to HTTP 429 — shed load, never queue it
    unboundedly."""


class DeadlineExpiredError(TimeoutError):
    """The request's deadline passed before its batch dispatched.

    Expired requests are evicted at batch formation — they never occupy a
    device-batch slot they cannot use — and their ``get()`` raises this.
    A serving front-end maps it to HTTP 504."""


class InferenceObservable:
    """Per-request future (reference BasicInferenceObservable /
    BatchedInferenceObservable's per-caller view)."""

    def __init__(self):
        self._done = threading.Event()
        self._out = None
        self._err: Optional[BaseException] = None

    def _resolve(self, out):
        self._out = out
        self._done.set()

    def _fail(self, err: BaseException):
        self._err = err
        self._done.set()

    def is_done(self) -> bool:
        return self._done.is_set()

    def get(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._done.wait(timeout):
            raise TimeoutError("inference result not ready")
        if self._err is not None:
            raise self._err
        return self._out


class ParallelInference:
    """``output()`` for synchronous sharded calls; ``submit()`` /
    ``output_batched()`` for the dynamic-batching path.

    inference_mode: "batched" coalesces concurrent requests on a worker
    thread (reference InferenceMode.BATCHED); "sequential" dispatches each
    request on the caller's thread (InferenceMode.SEQUENTIAL).

    queue_depth / queue_put_timeout_ms: the request queue is BOUNDED —
    when no slot frees within the timeout, ``submit`` raises
    :class:`QueueFullError` instead of growing host memory without limit.
    Per-request deadlines (``submit(x, deadline=...)``) are honored at
    batch formation: expired requests are evicted before device dispatch
    (:class:`DeadlineExpiredError`), never wasting a batch slot. The
    ``serving`` subsystem maps these to HTTP 429/504.

    bucket_policy: perf.BucketPolicy controlling the canonical dispatch
    sizes (default: power-of-two buckets with floor 8). Pass ``None`` to
    disable bucketing — every distinct padded batch size then compiles its
    own program, which is almost never what you want in serving.

    fold_bn: serve a BN-folded COPY of the model (perf/fusion.fold_bn) —
    every Conv→BatchNorm pair collapses into the conv's weights/bias, so
    serving dispatches pay no per-request normalize traffic at all. The
    caller's model object is untouched; exact within fp tolerance
    (analysis/lint.py DLT005 flags serving sites that skip this).

    quantize: a ``quant.CalibrationRecord`` — serve an int8-quantized COPY
    of the model (``quant.quantize``, which BN-folds first): per-channel
    int8 weights, calibrated per-tensor activation scales, int32
    accumulation. The quantized graph shares the bucket ladder and
    ``warmup()`` unchanged, and checkpoint hot-swap re-applies the SAME
    record to every newer fp32 checkpoint it swaps in, so a training
    job's commits keep serving quantized (see quant/ docs for the
    accuracy-gate step that should precede this).

    tuning: a ``perf.autotune.TuningRecord`` (or None to inherit the
    model's ``_tuning_record`` restored from a zip/checkpoint): the
    record's serving bucket ladder becomes the bucket policy and is warmed
    at construction, so a tuned endpoint compiles NOTHING at serve time.
    A record searched on a different architecture is refused
    (``StaleTuningRecordError``).

    checkpoint hot-swap: ``start_hot_swap(checkpoint_manager)`` watches the
    manager's journal for a newer step and atomically swaps the new params
    in BETWEEN dispatches — no request is dropped, none observes a
    mid-batch mix of old and new weights, and because only param VALUES
    change (same model object, same bucketed shapes), the warmed compiled
    programs are reused: a swap compiles nothing. ``stats()["hot_swap"]``
    reports swap count and the step currently being served."""

    _DEFAULT_POLICY = object()

    def __init__(self, model, mesh=None, batch_limit: int = 32,
                 queue_timeout_ms: int = 5, inference_mode: str = "batched",
                 bucket_policy=_DEFAULT_POLICY,
                 batch_size_history: int = 1024, fold_bn: bool = False,
                 quantize=None, checkpoint_manager=None,
                 checkpoint_poll_secs: Optional[float] = None,
                 queue_depth: int = 1024,
                 queue_put_timeout_ms: float = 50.0,
                 tuning=None):
        if inference_mode not in ("batched", "sequential"):
            raise ValueError(f"unknown inference_mode '{inference_mode}'")
        if int(queue_depth) < 1:
            raise ValueError(f"queue_depth must be >= 1; got {queue_depth}")
        if queue_put_timeout_ms < 0:
            raise ValueError("queue_put_timeout_ms must be >= 0")
        self._tuning = tuning
        if tuning is None:
            # a model restored from a zip/checkpoint carrying tuning.json
            # brings its record along — inherit it unless overridden
            self._tuning = tuning = getattr(model, "_tuning_record", None)
        if tuning is not None:
            # a tuning is only valid for the architecture it was searched
            # on (StaleTuningRecordError on mismatch — the quant/ stale-
            # record contract); checked BEFORE fold/quantize rebuild the
            # model, against the raw conf the record was searched on
            from deeplearning4j_tpu.perf.autotune import verify_tuning
            verify_tuning(model.conf, tuning)
            if (bucket_policy is ParallelInference._DEFAULT_POLICY
                    and tuning.buckets):
                bucket_policy = BucketPolicy(buckets=tuning.buckets)
            if getattr(tuning, "pallas_kernels", None) is not None:
                # the record's measured kernel-layer winner (perf/pallas):
                # configure BEFORE the warmup below so every warmed ladder
                # program is traced under the inherited selection — steady
                # state then compiles nothing
                from deeplearning4j_tpu.perf import pallas as _pk
                _pk.configure(enabled=tuning.pallas_kernels)
        self._fold_bn = bool(fold_bn)
        self._quantize = quantize
        # read checkpoint provenance BEFORE folding/quantizing: both
        # rebuild the model and do not carry _restored_from over, and
        # losing it here would make the first hot-swap poll re-swap the
        # very checkpoint this server already serves
        restored_from = getattr(model, "_restored_from", None)
        if quantize is not None:
            from deeplearning4j_tpu.quant import quantize as _quantize_net
            model = _quantize_net(model, quantize)  # BN-folds internally
        elif fold_bn:
            from deeplearning4j_tpu.perf.fusion import fold_bn as _fold_bn
            model = _fold_bn(model)
        from deeplearning4j_tpu.quant.lowering import is_quantized
        self.quantized = is_quantized(model)
        self.model = model
        self.mesh = mesh if mesh is not None else make_mesh()
        self.batch_limit = batch_limit
        self.queue_timeout_ms = queue_timeout_ms
        self.inference_mode = inference_mode
        self.bucket_policy = (BucketPolicy()
                              if bucket_policy is ParallelInference._DEFAULT_POLICY
                              else bucket_policy)
        if model.params is None:
            model.init()
        repl = jax.tree_util.tree_map(lambda a: replicated(self.mesh), model.params)
        model.params = jax.device_put(model.params, repl)
        # BOUNDED admission queue: a stalled worker (wedged device call,
        # slow model) must turn into fast typed rejections upstream, not
        # unbounded host-memory growth with every request waiting forever
        self.queue_depth = int(queue_depth)
        self.queue_put_timeout_ms = float(queue_put_timeout_ms)
        self._q: "queue.Queue" = queue.Queue(maxsize=self.queue_depth)
        self.queue_rejections = 0
        self.deadline_evictions = 0
        self._worker: Optional[threading.Thread] = None
        self._worker_lock = threading.Lock()
        self._stop = threading.Event()
        # observables the worker has dequeued but not yet resolved; shutdown
        # fails these too if the worker never comes back (wedged device call)
        self._inflight: List[InferenceObservable] = []
        self._inflight_lock = threading.Lock()
        # observability (exercised by the latency/throughput tests).
        # batch_sizes is BOUNDED: sustained serving must not grow host
        # memory; percentile summaries come from the retained window.
        self.requests_served = 0
        self.batches_dispatched = 0
        self.batch_sizes: "deque" = deque(maxlen=max(1, batch_size_history))
        # pre-pad ROW counts per dispatch (batch_sizes counts coalesced
        # REQUESTS): the histogram a learned bucket ladder trains on
        self.row_sizes: "deque" = deque(maxlen=max(1, batch_size_history))
        self.bucket_dispatches: Counter = Counter()
        self.unwarmed_dispatches = 0
        self._warmed: set = set()
        # sequential mode dispatches on arbitrary caller threads: counter
        # updates are read-modify-write and need the lock
        self._stats_lock = threading.Lock()
        # hot-swap: _model_lock serializes device dispatches against param
        # swaps — a swap waits for the in-flight batch and the next batch
        # sees the new params, so no dispatch ever runs a mid-swap mix
        self._model_lock = threading.Lock()
        self._swap_cm = None
        self._swap_thread: Optional[threading.Thread] = None
        self._swap_stop = threading.Event()
        self.swaps = 0
        self.swap_poll_errors = 0
        # poll backoff under a broken store (utils/backoff.py): seeded per
        # instance so the schedule is reproducible, jittered so a fleet of
        # servers polling one dead store doesn't re-synchronize its retries
        self._swap_backoff_rng = random.Random(0xD14)
        self.swap_consecutive_errors = 0
        self.swap_last_poll_delay: Optional[float] = None
        self.current_checkpoint_step = (None if restored_from is None
                                        else int(restored_from.step))
        # obs: hot-path instruments are shared process-wide (the registry
        # is the source of truth for the Prometheus scrape); stats() is
        # additionally absorbed at collect time so its sections (hot-swap,
        # buckets, attention) need no per-dispatch writes
        from deeplearning4j_tpu.obs.registry import (absorb_inference_stats,
                                                     get_registry)
        from deeplearning4j_tpu.obs.trace import get_tracer
        # configure_tracer mutates the global Tracer in place, so the handle
        # stays valid; caching it keeps the global lookup off the dispatch
        # hot path (the fit loops hoist it the same way)
        self._tracer = get_tracer()
        reg = get_registry()
        self._m_queue_depth = reg.gauge(
            "serving_queue_depth", unit="requests",
            help="requests waiting in the batching queue after a coalesce")
        self._m_occupancy = reg.histogram(
            "serving_batch_occupancy", unit="requests",
            help="coalesced requests per dispatched batch (batch_limit is "
                 "the ceiling)",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
        self._m_pad_waste = reg.histogram(
            "serving_pad_waste_rows", unit="rows",
            help="padding rows added per dispatch to reach the bucket "
                 "target (bucket ladder pad waste)",
            buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512))
        absorb_inference_stats(reg, self)
        if tuning is not None and tuning.buckets:
            # warm the RECORDED ladder now, so a tuned endpoint pays zero
            # compiles at serve time (the TuningRecord contract); best-
            # effort — models whose input shape the conf cannot describe
            # (multi-input graphs, index sequences) warm on first traffic
            ex = self._tuning_example()
            if ex is not None:
                try:
                    self.warmup(ex, buckets=tuning.buckets)
                except Exception:
                    import logging
                    logging.getLogger(__name__).warning(
                        "tuning-ladder warmup failed; serving continues "
                        "(first dispatch per bucket will compile)",
                        exc_info=True)
        if checkpoint_manager is not None:
            self.start_hot_swap(checkpoint_manager,
                                poll_secs=checkpoint_poll_secs)

    def _tuning_example(self) -> Optional[np.ndarray]:
        """A zero example with the conf-described feature shape, for
        warming the TuningRecord's bucket ladder; None when the conf does
        not pin a single float input shape."""
        conf = self.model.conf
        it = getattr(conf, "input_type", None)
        if it is None:
            its = getattr(conf, "input_types", None) or ()
            if len(its) != 1:
                return None
            it = its[0]
        if it is None:
            return None
        if it.kind in ("rnn", "cnn1d") and it.timeseries_length is None:
            return None  # no canonical length to warm at
        try:
            shape = it.example_shape(1)
        except ValueError:
            return None
        return np.zeros(shape, np.float32)

    # --------------------------------------------------------- shape policy
    def _pad_target(self, n: int) -> int:
        """Dispatch size for an n-row batch: the policy's bucket, rounded up
        to divide the mesh's data axis (the sequential path used to pad only
        to the axis multiple — one compiled program PER SIZE; now both paths
        share the bucket ladder). Zero-row batches bypass the ladder and
        keep their (valid, if unusual) empty dispatch."""
        dp = self.mesh.shape["data"]
        t = (self.bucket_policy.bucket(n)
             if self.bucket_policy is not None and n >= 1 else n)
        return t + (-t) % dp

    def _record_dispatch_shape(self, target: int, n_rows: int):
        with self._stats_lock:
            self.bucket_dispatches[target] += 1
            self.row_sizes.append(n_rows)
            if target not in self._warmed:
                self.unwarmed_dispatches += 1
        self._m_pad_waste.observe(max(0, target - n_rows))

    # ------------------------------------------------------------ sync path
    def _dispatch(self, arr, target: int, record: bool = True):
        """Pad to EXACTLY ``target`` rows, shard, run the model, slice the
        real rows back out. The single choke point for device dispatches —
        warmup and live traffic go through it with the same shapes, so a
        warmed target is guaranteed to be the compiled one. ``record=False``
        (warmup) keeps the dispatch out of the serving counters PER CALL,
        so concurrent live worker dispatches keep recording correctly."""
        n = arr.shape[0]
        with self.mesh:
            arr = pad_to_bucket(jnp.asarray(arr), target)
            if record:
                self._record_dispatch_shape(target, n)
            arr = jax.device_put(arr, data_sharding(self.mesh, arr.ndim))
            # _model_lock: a checkpoint hot-swap can never land mid-batch —
            # it waits here for the in-flight dispatch, and the very next
            # dispatch serves the new params
            with self._tracer.span("serving.dispatch", rows=n,
                                   target=target):
                with self._model_lock:
                    out = self.model.output(arr)
            if isinstance(out, list):
                # ComputationGraph.output: one array per network output.
                # Batches are padded, coalesced and split by ROW, so a
                # graph is served through its one output
                if len(out) != 1:
                    raise ValueError(
                        f"ParallelInference serves single-output models; "
                        f"this graph has {len(out)} outputs")
                out = out[0]
            return out[:n] if target != n else out

    def output(self, x) -> np.ndarray:
        """Synchronous sharded inference (reference ParallelInference.output),
        padded to the bucket ladder so repeat traffic reuses compiled
        programs."""
        arr = jnp.asarray(x)
        return self._dispatch(arr, self._pad_target(arr.shape[0]))

    def warmup(self, example, buckets=None) -> List[int]:
        """Pre-compile the forward program for every bucket BEFORE traffic
        arrives, so no live request ever pays a multi-second XLA compile.

        ``example``: an array with a leading batch axis — ideally a
        REPRESENTATIVE request, because the default bucket set assumes the
        worst coalesced batch is ``batch_limit`` requests of this size
        (``batch_limit`` caps coalesced REQUESTS, not rows). Pass explicit
        ``buckets`` (batch sizes to warm) when traffic mixes request sizes;
        warm up to your worst-case coalesced row count (see
        tests/test_perf.py::test_warmed_serving_wave_compiles_nothing).
        Returns the warmed dispatch sizes."""
        ex = np.asarray(example)
        if ex.ndim < 1:
            raise ValueError("warmup example needs a leading batch axis")
        feat_shape = ex.shape[1:]
        if buckets is None:
            max_rows = max(1, self.batch_limit) * max(1, ex.shape[0])
            if self.bucket_policy is None:
                buckets = [max_rows]
            else:
                buckets = self.bucket_policy.buckets_up_to(max_rows)
        for b in sorted({int(b) for b in buckets}):
            target = self._pad_target(b)
            if target in self._warmed:
                continue
            # dispatch EXACTLY target rows (not through output(), whose
            # re-bucketing could compile a different shape than live
            # traffic dispatches when target isn't a policy fixed point),
            # unrecorded so warmup doesn't pollute the serving counters
            self._dispatch(np.zeros((target,) + feat_shape, ex.dtype),
                           target, record=False)
            with self._stats_lock:  # stats()/recording iterate this set
                self._warmed.add(target)
        with self._stats_lock:
            return sorted(self._warmed)

    def learned_bucket_policy(self, max_compiles: int = 8) -> BucketPolicy:
        """Latency-aware ladder learned from the recorded pre-pad row-count
        histogram (``BucketPolicy.from_histogram``): at most ``max_compiles``
        buckets placed where this server's traffic actually mass — swap it
        in (new ParallelInference, or warmup a canary) when the static pow2
        ladder over- or under-buckets the observed mix."""
        with self._stats_lock:
            rows = list(self.row_sizes)
        rows = [r for r in rows if r >= 1]
        if not rows:
            raise ValueError(
                "no dispatches recorded yet — serve some traffic (or seed "
                "row_sizes) before learning a bucket ladder")
        return BucketPolicy.from_histogram(rows, max_compiles=max_compiles)

    # ------------------------------------------------- checkpoint hot-swap
    def start_hot_swap(self, checkpoint_manager,
                       poll_secs: Optional[float] = None):
        """Serve newer checkpoints without dropping traffic: watch
        ``checkpoint_manager``'s journal and swap params in atomically
        between dispatches when a newer step commits.

        With ``poll_secs`` a daemon poller calls :meth:`poll_checkpoint`
        on that cadence; leave it ``None`` to poll manually (deterministic
        tests, or an external control plane deciding when to roll). The
        manager may point at the same store a TRAINING process writes to
        (its journal is re-read via ``refresh()`` each poll), which is the
        deployment shape: trainer commits, servers pick it up live."""
        self._swap_cm = checkpoint_manager
        if poll_secs is not None and self._swap_thread is None:
            self._swap_stop.clear()
            self._swap_thread = threading.Thread(
                target=self._hot_swap_loop, args=(float(poll_secs),),
                name="ckpt-hot-swap", daemon=True)
            self._swap_thread.start()
        return self

    def stop_hot_swap(self):
        self._swap_stop.set()
        t, self._swap_thread = self._swap_thread, None
        if t is not None and t.is_alive():
            t.join(timeout=5)

    def _next_poll_delay(self, poll_secs: float, consecutive_errors: int,
                         cap_s: float = 30.0) -> float:
        """Poll cadence given the current error streak: the configured
        ``poll_secs`` while healthy, plus a capped-exponential-jitter
        backoff (utils/backoff.py) once the store starts erroring — a dead
        backend must not be hammered at full poll rate, and recovery resets
        to the configured cadence."""
        if consecutive_errors <= 0:
            return poll_secs
        from deeplearning4j_tpu.utils.backoff import backoff_delay
        return poll_secs + backoff_delay(consecutive_errors - 1,
                                         base_s=max(poll_secs, 0.05),
                                         cap_s=cap_s,
                                         rng=self._swap_backoff_rng)

    def _hot_swap_loop(self, poll_secs: float):
        delay = poll_secs
        while not self._swap_stop.wait(delay):
            try:
                self.poll_checkpoint()
                with self._stats_lock:
                    self.swap_consecutive_errors = 0
            except Exception:
                # the serving path must outlive a broken store; the error
                # count is surfaced in stats() for alerting
                with self._stats_lock:
                    self.swap_poll_errors += 1
                    self.swap_consecutive_errors += 1
                import logging
                logging.getLogger(__name__).exception(
                    "checkpoint hot-swap poll failed; serving continues "
                    "on the current params")
            with self._stats_lock:
                delay = self._next_poll_delay(poll_secs,
                                              self.swap_consecutive_errors)
                self.swap_last_poll_delay = delay

    def poll_checkpoint(self) -> bool:
        """One hot-swap probe: is there a newer committed checkpoint than
        the step being served? If so, restore it OFF the dispatch path,
        then atomically swap params/state in between dispatches. Returns
        whether a swap happened.

        The swap reuses everything already compiled: the model OBJECT (and
        its jit cache, warmed buckets, compile counters) is untouched —
        only param/state VALUES change, at unchanged shapes, so the warmup
        ladder stays valid and the swap compiles nothing new."""
        cm = self._swap_cm
        if cm is None:
            return False
        cm.refresh()
        refresh_err = getattr(cm, "last_refresh_error", None)
        if refresh_err is not None:
            # the journal re-read failed: this probe learned NOTHING (the
            # manager deliberately keeps serving its known journal) —
            # surface the store fault so the poll loop counts it and
            # backs off instead of hammering a dead store at full cadence
            raise refresh_err
        step = cm.latest_step()
        if step is None or (self.current_checkpoint_step is not None
                            and step <= self.current_checkpoint_step):
            return False
        # the expensive part — fetch + deserialize + (maybe) fold + device
        # placement — happens OUTSIDE the model lock: traffic keeps being
        # served on the old params while the new ones are prepared
        restored = cm.restore_latest(load_updater=False)
        if restored is None:
            return False
        # restore_latest may have FALLEN BACK past a torn/corrupt newest
        # entry to a checkpoint at-or-before the one being served — without
        # this guard a rotted newest object would re-swap (or DOWNGRADE to
        # an older surviving checkpoint) on every poll, forever
        restored_step = restored._restored_from.step
        if self.current_checkpoint_step is not None \
                and restored_step <= self.current_checkpoint_step:
            return False
        if self._quantize is not None:
            # the newer (fp32) checkpoint gets the SAME lowering this
            # server was built with: quantize folds + int8-lowers, so the
            # swapped-in tree matches the serving model's structurally
            from deeplearning4j_tpu.quant import quantize as _quantize_net
            restored = _quantize_net(restored, self._quantize)
        elif self._fold_bn:
            from deeplearning4j_tpu.perf.fusion import fold_bn as _fold_bn
            restored = _fold_bn(restored)
        if (jax.tree_util.tree_structure(restored.params)
                != jax.tree_util.tree_structure(self.model.params)):
            raise RuntimeError(
                "hot-swap checkpoint params have a different structure "
                "than the serving model — the store holds a different "
                "architecture; refusing to swap")
        repl = jax.tree_util.tree_map(lambda a: replicated(self.mesh),
                                      restored.params)
        new_params = jax.device_put(restored.params, repl)
        new_state = restored.state
        new_step = restored_step
        with self._model_lock:
            self.model.params = new_params
            self.model.state = new_state
        with self._stats_lock:
            self.swaps += 1
            self.current_checkpoint_step = int(new_step)
        return True

    @staticmethod
    def _size_summary(sizes) -> dict:
        summary = {"count": len(sizes)}
        if sizes:
            summary.update(
                mean=round(float(np.mean(sizes)), 2),
                p50=float(np.percentile(sizes, 50)),
                p95=float(np.percentile(sizes, 95)),
                max=int(max(sizes)))
        return summary

    def stats(self) -> dict:
        """Serving observability: request/dispatch counts, batch-size and
        row-count percentiles over the retained window, per-bucket dispatch
        counts, warmed buckets, and the model's compile/dispatch
        counters."""
        with self._stats_lock:
            # every mutable counter is read under the SAME lock the worker
            # mutates under — dict(bucket_dispatches) racing a new-key
            # insert would raise "dictionary changed size during iteration"
            sizes = list(self.batch_sizes)
            rows = list(self.row_sizes)
            requests_served = self.requests_served
            batches_dispatched = self.batches_dispatched
            warmed = sorted(self._warmed)
            bucket_dispatches = dict(self.bucket_dispatches)
            unwarmed = self.unwarmed_dispatches
            swaps = self.swaps
            current_step = self.current_checkpoint_step
            swap_errors = self.swap_poll_errors
            rejected = self.queue_rejections
            expired = self.deadline_evictions
            swap_consec = self.swap_consecutive_errors
            swap_delay = self.swap_last_poll_delay
        out = {
            "requests_served": requests_served,
            "batches_dispatched": batches_dispatched,
            "quantized": self.quantized,
            "queue": {
                "depth": self.queue_depth,
                "size": self._q.qsize(),
                "rejected": rejected,
                "expired": expired,
            },
            "batch_size": self._size_summary(sizes),
            "row_size": self._size_summary(rows),
            "bucket_policy": (None if self.bucket_policy is None
                              else repr(self.bucket_policy)),
            "tuning": {
                "applied": self._tuning is not None,
                "buckets": (list(self._tuning.buckets)
                            if self._tuning is not None else None),
            },
            "warmed_buckets": warmed,
            "bucket_dispatches": bucket_dispatches,
            "unwarmed_dispatches": unwarmed,
            "hot_swap": {
                "enabled": self._swap_cm is not None,
                "swaps": swaps,
                "current_checkpoint_step": current_step,
                "poll_errors": swap_errors,
                "consecutive_poll_errors": swap_consec,
                "last_poll_delay_s": (None if swap_delay is None
                                      else round(swap_delay, 4)),
            },
        }
        cw = getattr(self.model, "compile_watch", None)
        if cw is not None:
            out["model_compiles"] = cw.compiles()
            out["model_dispatches"] = cw.dispatches()
        # attention kernel-path counters (nn/conf/attention.py _attend): a
        # serving model silently skipping the Pallas flash kernel
        # (attention.flash_fallback > 0) is visible here, not just as a
        # latency regression. Read from THIS model's watch (bump_active
        # routes trace-time events to the tracing model), so two models in
        # one process never misattribute each other's kernel paths.
        if cw is not None:
            att = cw.counters("attention.")
            if att:
                out["attention"] = att
            # fused conv+BN block trace hits (nn/conf/convolutional.py
            # FusedConvBNActivation.apply): a serving model expected to run
            # fused (or folded — folded graphs count ZERO here) is
            # verifiable from stats rather than from step latency
            fus = cw.counters("fusion.")
            if fus:
                out["fusion"] = fus
        # last analysis.trace_check report for this model, if one ran
        report = getattr(self.model, "last_trace_report", None)
        if report is not None:
            out["trace_hazards"] = report.counts()
        return out

    # -------------------------------------------------------- batched path
    def submit(self, x, deadline: Optional[float] = None
               ) -> InferenceObservable:
        """Enqueue one request; returns its observable (reference
        ParallelInference.java:97 observable provider).

        ``deadline``: absolute ``time.monotonic()`` timestamp after which
        the caller no longer wants the answer. Expired requests are
        evicted at batch formation — BEFORE device dispatch, so they never
        occupy a batch slot they cannot use — and their ``get()`` raises
        :class:`DeadlineExpiredError`.

        Full-queue semantics: block up to ``queue_put_timeout_ms`` for a
        slot, then raise :class:`QueueFullError` — load is shed to the
        caller, never queued unboundedly."""
        obs = InferenceObservable()
        if self.inference_mode == "sequential":
            try:
                if deadline is not None and time.monotonic() >= deadline:
                    with self._stats_lock:
                        self.deadline_evictions += 1
                    raise DeadlineExpiredError(
                        "request deadline expired before dispatch")
                obs._resolve(self.output(np.asarray(x)))
            except BaseException as e:  # surfaced at .get()
                obs._fail(e)
            with self._stats_lock:
                self.requests_served += 1
            return obs
        item = (np.asarray(x), obs, deadline)
        give_up = time.monotonic() + self.queue_put_timeout_ms / 1000.0
        while True:
            # enqueue + worker liveness under ONE lock: a concurrent
            # shutdown() (same lock) can then never strand this request
            # between the put and the worker start. The put itself is
            # non-blocking — a submitter waiting for a slot must never
            # hold the lock shutdown() needs.
            with self._worker_lock:
                try:
                    self._q.put_nowait(item)
                except queue.Full:
                    pass
                else:
                    self._ensure_worker_locked()
                    return obs
            remaining = give_up - time.monotonic()
            if remaining <= 0:
                with self._stats_lock:
                    self.queue_rejections += 1
                raise QueueFullError(
                    f"request queue full (queue_depth={self.queue_depth})"
                    f" after {self.queue_put_timeout_ms:g}ms — the worker "
                    "is not draining fast enough; shed load upstream")
            time.sleep(min(0.001, remaining))

    def output_batched(self, x) -> np.ndarray:
        """Blocking convenience over submit() (reference
        BatchedInferenceObservable callers)."""
        return self.submit(x).get()

    _SENTINEL = object()

    def shutdown(self):
        """Stop the worker after draining; pending observables either get
        served by the final drain or failed, never left hanging."""
        self.stop_hot_swap()
        with self._worker_lock:
            w = self._worker
            if w is not None and w.is_alive():
                self._stop.set()
                try:  # wake the worker promptly; a FULL queue already
                    self._q.put_nowait(ParallelInference._SENTINEL)
                except queue.Full:  # keeps it busy and re-checking _stop
                    pass
                w.join(timeout=10)
                if w.is_alive():
                    # worker is wedged (e.g. inside a device call): fail the
                    # requests it already dequeued so their get() unblocks
                    with self._inflight_lock:
                        stuck, self._inflight = self._inflight, []
                    for obs in stuck:
                        if not obs.is_done():
                            obs._fail(RuntimeError(
                                "ParallelInference worker did not stop within "
                                "10s at shutdown; in-flight request abandoned"))
            self._worker = None
            # fail anything the worker did not reach (its get() callers
            # would otherwise block forever)
            leftovers = []
            try:
                while True:
                    leftovers.append(self._q.get_nowait())
            except queue.Empty:
                pass
            for item in leftovers:
                if item is not ParallelInference._SENTINEL:
                    item[1]._fail(RuntimeError(
                        "ParallelInference shut down before request served"))

    # ------------------------------------------------------------- worker
    def _ensure_worker_locked(self):
        """Caller holds _worker_lock."""
        if self._worker is None or not self._worker.is_alive():
            self._stop.clear()
            self._worker = threading.Thread(target=self._worker_loop,
                                            daemon=True)
            self._worker.start()

    def _collect(self):
        """Take up to batch_limit requests, waiting queue_timeout_ms for
        stragglers after the first arrives (the reference's batching
        window)."""
        try:
            first = self._q.get(timeout=0.05)
        except queue.Empty:
            return []
        if first is ParallelInference._SENTINEL:
            return []
        items = [first]
        deadline = time.monotonic() + self.queue_timeout_ms / 1000.0
        while len(items) < self.batch_limit:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                nxt = self._q.get(timeout=remaining)
            except queue.Empty:
                break
            if nxt is ParallelInference._SENTINEL:
                break
            items.append(nxt)
        return items

    def _worker_loop(self):
        while not self._stop.is_set():
            items = self._collect()
            if not items:
                continue
            # deadline eviction at BATCH FORMATION: an expired request is
            # answered (DeadlineExpiredError) before device dispatch and
            # never occupies a batch slot it cannot use — the batch that
            # does dispatch carries only requests whose callers still want
            # the answer
            now = time.monotonic()
            expired = [it for it in items
                       if it[2] is not None and now >= it[2]]
            items = [it for it in items
                     if it[2] is None or now < it[2]]
            if expired:
                # count BEFORE failing: a caller woken by get() must see
                # the eviction already reflected in stats()
                with self._stats_lock:
                    self.deadline_evictions += len(expired)
            for _, obs, dl in expired:
                obs._fail(DeadlineExpiredError(
                    f"request deadline expired {now - dl:.3f}s before "
                    "batch dispatch"))
            if not items:
                continue
            # what's STILL queued after this coalesce = the backlog a new
            # request joins; occupancy tells whether batching is working
            self._m_queue_depth.set(self._q.qsize())
            self._m_occupancy.observe(len(items))
            xs = [i[0] for i in items]
            sizes = [len(x) for x in xs]
            with self._inflight_lock:
                self._inflight = [obs for _, obs, _ in items]
            try:
                out = self.output(np.concatenate(xs, axis=0))
                ofs = 0
                for (x, obs, _), n in zip(items, sizes):
                    obs._resolve(out[ofs:ofs + n])
                    ofs += n
            except BaseException as e:
                for _, obs, _ in items:
                    obs._fail(e)
            finally:
                with self._inflight_lock:
                    self._inflight = []
            with self._stats_lock:  # stats() iterates these concurrently
                self.requests_served += len(items)
                self.batches_dispatched += 1
                self.batch_sizes.append(len(items))

"""Training listeners.

Parity surface: reference ``optimize/api/IterationListener.java`` /
``TrainingListener.java`` and ``optimize/listeners/``:
ScoreIterationListener, PerformanceListener (samples/sec —
PerformanceListener.java:19-23), CollectScoresIterationListener,
TimeIterationListener, EvaluativeListener (in eval module).
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional, Tuple

log = logging.getLogger(__name__)


class TrainingListener:
    """Hook interface (reference TrainingListener.java)."""

    def iteration_done(self, model, iteration: int, epoch: int):
        pass

    def on_epoch_start(self, model):
        pass

    def on_epoch_end(self, model):
        pass

    def on_forward_pass(self, model, activations):
        pass

    def on_gradient_calculation(self, model):
        pass

    def reads_features(self, iteration: int) -> bool:
        """Whether ``iteration_done(model, iteration, ...)`` will read
        ``model._last_features``, the one-row sample of the step's batch.
        The fit loops take that sample (a device program behind the step)
        only on the iterations some listener answers True for; on all
        others ``_last_features`` is None."""
        return False


def any_reads_features(listeners, iteration: int) -> bool:
    """The fit loops' question before ``train.post``: will some listener
    read the feature sample on ``iteration``? A listener without
    ``reads_features`` (duck-typed, not a ``TrainingListener``) reads
    nothing."""
    for listener in listeners:
        reads = getattr(listener, "reads_features", None)
        if reads is not None and reads(iteration):
            return True
    return False


class ScoreIterationListener(TrainingListener):
    """Log score every N iterations (reference ScoreIterationListener.java)."""

    def __init__(self, print_iterations: int = 10):
        self.print_iterations = max(1, print_iterations)

    def iteration_done(self, model, iteration, epoch):
        if iteration % self.print_iterations == 0:
            log.info("Score at iteration %d is %s", iteration, model.score())


class PerformanceListener(TrainingListener):
    """Throughput reporting (reference PerformanceListener.java:19-23):
    samples/sec, batches/sec, iteration time. Feeds BASELINE measurements."""

    def __init__(self, frequency: int = 1, report_score: bool = False):
        self.frequency = max(1, frequency)
        self.report_score = report_score
        self._last_time: Optional[float] = None
        self.samples_per_sec: Optional[float] = None
        self.batches_per_sec: Optional[float] = None

    def iteration_done(self, model, iteration, epoch):
        now = time.perf_counter()
        if self._last_time is not None and iteration % self.frequency == 0:
            dt = max(now - self._last_time, 1e-9)
            batch = getattr(model, "last_batch_size", None)
            self.batches_per_sec = self.frequency / dt
            if batch:
                self.samples_per_sec = batch * self.frequency / dt
            msg = (f"iteration {iteration}: {self.batches_per_sec:.1f} batches/sec"
                   + (f", {self.samples_per_sec:.1f} samples/sec" if batch else ""))
            if self.report_score:
                msg += f", score {model.score()}"
            log.info(msg)
        if iteration % self.frequency == 0:
            self._last_time = now


class CollectScoresIterationListener(TrainingListener):
    """Collect (iteration, score) pairs (reference CollectScoresIterationListener.java)."""

    def __init__(self, frequency: int = 1):
        self.frequency = max(1, frequency)
        self.scores: List[Tuple[int, float]] = []

    def iteration_done(self, model, iteration, epoch):
        if iteration % self.frequency == 0:
            self.scores.append((iteration, float(model.score())))


class TimeIterationListener(TrainingListener):
    """ETA logging (reference TimeIterationListener.java)."""

    def __init__(self, iteration_count: int):
        self.iteration_count = iteration_count
        self.start = time.perf_counter()

    def iteration_done(self, model, iteration, epoch):
        elapsed = time.perf_counter() - self.start
        done = iteration + 1
        remaining = (self.iteration_count - done) * elapsed / max(done, 1)
        log.info("Remaining time: %d min %d sec", int(remaining // 60), int(remaining % 60))


class EvaluativeListener(TrainingListener):
    """Periodically evaluate on a held-out iterator during training
    (reference optimize/listeners/EvaluativeListener.java:61 — frequency +
    InvocationType ITERATION_END / EPOCH_END, callback hook).

    ``evaluations`` are zero-arg factories (e.g. ``Evaluation``) so each
    invocation starts fresh; results are kept in ``history`` and passed to
    ``callback(model, evals)`` if provided.
    """

    ITERATION_END = "iteration_end"
    EPOCH_END = "epoch_end"

    def __init__(self, iterator, frequency: int = 1,
                 invocation_type: str = EPOCH_END,
                 evaluations=None, callback=None):
        self.iterator = iterator
        self.frequency = max(1, frequency)
        self.invocation_type = invocation_type
        self.evaluations = evaluations or []
        self.callback = callback
        self.history: List[list] = []
        self._count = 0

    def _invoke(self, model):
        self._count += 1
        if self._count % self.frequency != 0:
            return
        if hasattr(self.iterator, "reset"):
            self.iterator.reset()
        if self.evaluations:
            evals = [f() for f in self.evaluations]
            for ds in self.iterator:
                preds = model.output(
                    ds.features,
                    features_mask=getattr(ds, "features_mask", None))
                for e in evals:
                    e.eval(ds.labels, preds, mask=getattr(ds, "labels_mask", None))
        else:
            evals = [model.evaluate(self.iterator)]
        self.history.append(evals)
        for e in evals:
            if hasattr(e, "accuracy"):
                log.info("EvaluativeListener: accuracy %.4f", e.accuracy())
        if self.callback is not None:
            self.callback(model, evals)

    def iteration_done(self, model, iteration, epoch):
        if self.invocation_type == self.ITERATION_END:
            self._invoke(model)

    def on_epoch_end(self, model):
        if self.invocation_type == self.EPOCH_END:
            self._invoke(model)


class SleepyTrainingListener(TrainingListener):
    """Debug throttling (reference SleepyTrainingListener.java)."""

    def __init__(self, sleep_ms: int = 0):
        self.sleep_ms = sleep_ms

    def iteration_done(self, model, iteration, epoch):
        if self.sleep_ms:
            time.sleep(self.sleep_ms / 1000.0)


class ProfilerListener(TrainingListener):
    """Capture an XLA/device profile for a window of training iterations
    (TPU-native replacement for the reference's instrumentation hooks —
    SURVEY §5 tracing/profiling: jax.profiler traces open in TensorBoard /
    Perfetto and show per-op device time, HBM usage and fusion decisions).

    Traces iterations [start_iteration, start_iteration + num_iterations).
    """

    def __init__(self, log_dir: str, start_iteration: int = 10,
                 num_iterations: int = 5):
        self.log_dir = log_dir
        self.start_iteration = start_iteration
        self.num_iterations = num_iterations
        self._active = False
        self.completed = False

    def iteration_done(self, model, iteration, epoch):
        import jax
        if self.completed:
            return
        if not self._active and iteration >= self.start_iteration:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
            self._stop_at = iteration + self.num_iterations
            return
        if self._active and iteration >= self._stop_at:
            # block so the traced window contains the real device work, not
            # just async dispatch
            jax.block_until_ready(model.params)
            jax.profiler.stop_trace()
            self._active = False
            self.completed = True
            log.info("Profiler trace written to %s", self.log_dir)

    def close(self, model=None):
        """Finalize a window left open because training ended inside it.
        (Epoch boundaries deliberately do NOT stop the trace — a window may
        span epochs.)"""
        if self._active:
            import jax
            if model is not None:
                jax.block_until_ready(model.params)
            jax.profiler.stop_trace()
            self._active = False
            self.completed = True


class CheckpointListener(TrainingListener):
    """Periodic checkpointing with bounded retention + resume (reference
    CheckpointListener semantics; the save format is
    utils/serialization.write_model, which carries params, updater state and
    iteration/epoch counters — restoring continues training where it
    stopped, the SURVEY §5 checkpoint/resume + elasticity story).

    ``every_n_iterations`` or ``every_n_epochs`` must be set; ``keep_last``
    bounds disk use.

    Superseded for production use by ``checkpoint.CheckpointManager``
    (``fit(..., checkpoint_manager=)``): that subsystem writes
    asynchronously off the step loop, commits atomically behind a
    checksummed journal (torn writes fall back instead of restoring
    garbage), saves the rng/step state for EXACT-step resume, and is
    multi-host aware. This listener stays for reference-parity and simple
    single-host save-every-N use.
    """

    def __init__(self, checkpoint_dir: str, every_n_iterations: int = 0,
                 every_n_epochs: int = 0, keep_last: int = 3,
                 save_updater: bool = True):
        if not every_n_iterations and not every_n_epochs:
            raise ValueError("Set every_n_iterations or every_n_epochs")
        import os
        os.makedirs(checkpoint_dir, exist_ok=True)
        self.checkpoint_dir = checkpoint_dir
        self.every_n_iterations = every_n_iterations
        self.every_n_epochs = every_n_epochs
        self.keep_last = keep_last
        self.save_updater = save_updater
        # adopt checkpoints from previous runs so keep_last bounds disk use
        # across restore_last resume cycles, not just within one process
        self.saved_paths: List[str] = sorted(
            (os.path.join(checkpoint_dir, f)
             for f in os.listdir(checkpoint_dir)
             if f.startswith("checkpoint_") and f.endswith(".zip")),
            key=os.path.getmtime)

    def _save(self, model, tag: str):
        import os
        from deeplearning4j_tpu.utils.serialization import write_model
        path = os.path.join(self.checkpoint_dir, f"checkpoint_{tag}.zip")
        write_model(model, path, save_updater=self.save_updater)
        # re-saving an adopted/duplicate tag must not leave a stale entry the
        # retention loop could later use to delete the fresh file
        if path in self.saved_paths:
            self.saved_paths.remove(path)
        self.saved_paths.append(path)
        while len(self.saved_paths) > self.keep_last:
            old = self.saved_paths.pop(0)
            try:
                os.remove(old)
            except OSError:
                pass

    def iteration_done(self, model, iteration, epoch):
        if self.every_n_iterations and iteration > 0 \
                and iteration % self.every_n_iterations == 0:
            self._save(model, f"iter_{iteration}")

    def on_epoch_end(self, model):
        if self.every_n_epochs and (model.epoch + 1) % self.every_n_epochs == 0:
            self._save(model, f"epoch_{model.epoch}")

    @staticmethod
    def last_checkpoint(checkpoint_dir: str) -> Optional[str]:
        """Most recent checkpoint path in a directory, or None."""
        import os
        files = [os.path.join(checkpoint_dir, f)
                 for f in os.listdir(checkpoint_dir)
                 if f.startswith("checkpoint_") and f.endswith(".zip")]
        return max(files, key=os.path.getmtime) if files else None

    @staticmethod
    def restore_last(checkpoint_dir: str):
        """Restore the most recent checkpoint (resume path). Raises if the
        directory has none."""
        from deeplearning4j_tpu.utils.serialization import restore
        path = CheckpointListener.last_checkpoint(checkpoint_dir)
        if path is None:
            raise FileNotFoundError(f"No checkpoints in {checkpoint_dir}")
        return restore(path)


class ConvolutionalIterationListener(TrainingListener):
    """Capture convolutional activation grids for the UI's /activations
    module (reference ConvolutionIterationListener.java feeding
    ConvolutionalListenerModule.java:32).

    Every ``frequency`` iterations, runs the first sample of the last fit
    minibatch forward, tiles each conv layer's channels into one grayscale
    grid, and stores it as a base64 PNG update record (type id
    ``ActivationsListener``) in ``storage``."""

    def __init__(self, storage, frequency: int = 10,
                 session_id: Optional[str] = None, max_layers: int = 4,
                 max_channels: int = 64):
        import socket as _socket
        import uuid as _uuid
        try:
            import PIL  # noqa: F401
            self._png_ok = True
        except ImportError:
            log.warning("Pillow not available: ConvolutionalIterationListener "
                        "disabled (no PNG encoder)")
            self._png_ok = False
        self.storage = storage
        self.frequency = max(1, int(frequency))
        self.session_id = session_id or str(_uuid.uuid4())
        self.worker_id = _socket.gethostname()
        self.max_layers = max_layers
        self.max_channels = max_channels

    @staticmethod
    def _tile_png(act) -> str:
        """(H, W, C) activation -> tiled grayscale grid PNG (base64)."""
        import base64
        import io

        import numpy as np
        from PIL import Image
        a = np.asarray(act, np.float32)
        h, w, c = a.shape
        cols = int(np.ceil(np.sqrt(c)))
        rows = int(np.ceil(c / cols))
        grid = np.zeros((rows * (h + 1), cols * (w + 1)), np.float32)
        for i in range(c):
            ch = a[:, :, i]
            lo, hi = float(ch.min()), float(ch.max())
            ch = (ch - lo) / (hi - lo) if hi > lo else np.zeros_like(ch)
            r, col = divmod(i, cols)
            grid[r * (h + 1):r * (h + 1) + h,
                 col * (w + 1):col * (w + 1) + w] = ch
        img = Image.fromarray((grid * 255).astype(np.uint8), mode="L")
        buf = io.BytesIO()
        img.save(buf, format="PNG")
        return base64.b64encode(buf.getvalue()).decode()

    def _conv_activations(self, model):
        """name -> (H, W, C) activation of each conv-ish layer for ONE
        sample of the last minibatch."""
        import numpy as np
        x = getattr(model, "_last_features", None)
        if x is None:
            return {}
        out = {}
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        if isinstance(model, MultiLayerNetwork):
            acts = model.feed_forward(np.asarray(x)[:1])  # one act per layer
            for i, (layer, a) in enumerate(zip(model.layers, acts)):
                a = np.asarray(a)
                if a.ndim == 4:
                    out[f"layer{i}_{type(layer).__name__}"] = a[0]
        else:  # ComputationGraph: acts of every vertex for input sample
            import jax.numpy as jnp
            feats = [jnp.asarray(np.asarray(f)[:1]) for f in x] \
                if isinstance(x, (list, tuple)) else [jnp.asarray(np.asarray(x)[:1])]
            acts, _, _, _ = model._forward(model.params, model.state, feats,
                                           False, None, None)
            for name in model.order:
                a = np.asarray(acts[name])
                if a.ndim == 4:
                    out[name] = a[0]
        return dict(list(out.items())[: self.max_layers])

    def reads_features(self, iteration):
        return self._png_ok and iteration % self.frequency == 0

    def iteration_done(self, model, iteration, epoch):
        if not self.reads_features(iteration):
            return
        layers = {}
        for name, a in self._conv_activations(model).items():
            layers[name] = self._tile_png(a[:, :, : self.max_channels])
        if not layers:
            return
        from deeplearning4j_tpu.ui.server import ACTIVATIONS_TYPE_ID
        self.storage.put_update({
            "kind": "update", "session_id": self.session_id,
            "type_id": ACTIVATIONS_TYPE_ID, "worker_id": self.worker_id,
            "timestamp": int(time.time() * 1000),
            "iteration": int(iteration), "layers": layers,
        })

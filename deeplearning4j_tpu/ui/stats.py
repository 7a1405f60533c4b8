"""StatsListener: per-iteration model/system stats into a StatsStorage.

Parity surface: reference
``deeplearning4j-ui-model/.../ui/stats/BaseStatsListener.java:44`` (collection
loop, :286 iterationDone), ``StatsListener.java``, ``api/StatsReport.java``
(score, timing, memory, learning rates, per-param histograms / mean / stdev /
mean-magnitudes for Parameters, Updates and Activations) and
``api/StatsInitializationReport.java`` (session/software/hardware/model info).

TPU-native design: the listener reads stats from the HOST copies of the jitted
step's outputs. "Updates" are the applied parameter deltas between reports —
the reference reports the updater output, which under buffer donation is
consumed on-device; the delta over one report interval is the same quantity
summed, without holding a second gradients buffer. Activations are sampled by
re-running the model's forward pass on the last minibatch at report time
(amortized by ``frequency``) rather than taping every training forward.
"""

from __future__ import annotations

import json
import socket
import sys
import time
import uuid
from typing import Optional

import numpy as np

from deeplearning4j_tpu.optimize.listeners import TrainingListener

TYPE_ID = "StatsListener"


def _histogram(arr: np.ndarray, bins: int) -> dict:
    arr = np.asarray(arr, np.float64).ravel()
    arr = arr[np.isfinite(arr)]
    if arr.size == 0:
        return {"min": 0.0, "max": 0.0, "counts": [0] * bins}
    lo, hi = float(arr.min()), float(arr.max())
    if lo == hi:
        hi = lo + 1e-12
    counts, _ = np.histogram(arr, bins=bins, range=(lo, hi))
    return {"min": lo, "max": hi, "counts": counts.tolist()}


def _flatten_params(params, prefix="") -> dict:
    """Flatten a list-of-dicts (MLN) or dict-of-dicts (CG) param tree into
    ``{"0_W": array, ...}`` / ``{"vertex_W": array}`` leaf names, mirroring the
    reference's ``layerIdx_paramName`` convention. Nested dicts (e.g.
    Bidirectional's fwd/bwd sub-params) join with ``_``."""
    out = {}
    if isinstance(params, (list, tuple)):
        items = [(str(i), v) for i, v in enumerate(params)]
    elif isinstance(params, dict):
        items = list(params.items())
    else:
        if params is not None:
            out[prefix.rstrip("_") or "param"] = params
        return out
    for name, v in items:
        if isinstance(v, (dict, list, tuple)):
            out.update(_flatten_params(v, f"{prefix}{name}_"))
        elif v is not None:
            out[f"{prefix}{name}"] = v
    return out


def _stats_of(arr: np.ndarray) -> dict:
    a = np.asarray(arr, np.float64).ravel()
    a = a[np.isfinite(a)]
    if a.size == 0:
        return {"mean": 0.0, "stdev": 0.0, "mean_magnitude": 0.0}
    return {"mean": float(a.mean()),
            "stdev": float(a.std(ddof=1)) if a.size > 1 else 0.0,
            "mean_magnitude": float(np.abs(a).mean())}


class StatsListener(TrainingListener):
    """Collect score/timing/memory/param/update/activation stats every
    ``frequency`` iterations into ``storage`` (see module docstring).

    ``storage`` is any ``deeplearning4j_tpu.storage.BaseStatsStorage``.
    """

    def __init__(self, storage, frequency: int = 1,
                 session_id: Optional[str] = None,
                 worker_id: Optional[str] = None,
                 histogram_bins: int = 20,
                 collect_histograms: bool = True,
                 collect_mean_stdev: bool = True,
                 collect_activations: bool = True,
                 collect_memory: bool = True):
        self.storage = storage
        self.frequency = max(1, frequency)
        self.session_id = session_id or str(uuid.uuid4())
        self.worker_id = worker_id or socket.gethostname()
        self.histogram_bins = histogram_bins
        self.collect_histograms = collect_histograms
        self.collect_mean_stdev = collect_mean_stdev
        self.collect_activations = collect_activations
        self.collect_memory = collect_memory
        self._init_reported = False
        self._start_time: Optional[float] = None
        self._last_report_time: Optional[float] = None
        self._last_params: Optional[dict] = None
        self._examples_since = 0
        self._minibatches_since = 0
        self._total_examples = 0
        self._total_minibatches = 0

    # -------------------------------------------------------------- reports
    def _report_init(self, model):
        import jax

        dev = jax.local_devices()[0]
        record = {
            "kind": "static", "session_id": self.session_id,
            "type_id": TYPE_ID, "worker_id": self.worker_id,
            "timestamp": time.time(),
            "software": {
                "python": sys.version.split()[0],
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "hostname": socket.gethostname(),
            },
            "hardware": {
                "device_kind": dev.device_kind,
                "device_count": jax.local_device_count(),
                "platform": dev.platform,
            },
            "model": {
                "class": type(model).__name__,
                "num_params": int(model.num_params()),
                "param_shapes": {
                    k: list(np.shape(v)) for k, v in
                    _flatten_params(model.params).items()},
            },
        }
        conf = getattr(model, "conf", None)
        if conf is not None and hasattr(conf, "to_json"):
            try:
                record["model"]["config"] = json.loads(conf.to_json())
            except Exception:
                pass
        self.storage.put_static_info(record)
        self._init_reported = True
        self._start_time = time.time()
        self._last_report_time = self._start_time

    def _memory_report(self) -> dict:
        import resource

        import jax

        mem = {"host_rss_bytes":
               resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}
        try:
            ds = jax.local_devices()[0].memory_stats()
            if ds:
                mem["device_bytes_in_use"] = int(ds.get("bytes_in_use", 0))
                mem["device_bytes_limit"] = int(ds.get("bytes_limit", 0))
        except Exception:
            pass
        return mem

    def _param_group(self, flat: dict) -> dict:
        group = {}
        for name, arr in flat.items():
            a = np.asarray(arr)
            entry = {}
            if self.collect_mean_stdev:
                entry.update(_stats_of(a))
            if self.collect_histograms:
                entry["histogram"] = _histogram(a, self.histogram_bins)
            group[name] = entry
        return group

    # ------------------------------------------------------------- listener
    def reads_features(self, iteration: int) -> bool:
        return self.collect_activations and iteration % self.frequency == 0

    def iteration_done(self, model, iteration: int, epoch: int):
        if not self._init_reported:
            self._report_init(model)
        batch = getattr(model, "last_batch_size", None) or 0
        self._examples_since += batch
        self._minibatches_since += 1
        self._total_examples += batch
        self._total_minibatches += 1
        if iteration % self.frequency != 0:
            return
        t0 = time.perf_counter()
        now = time.time()
        dt = max(now - (self._last_report_time or now), 1e-9)

        flat = {k: np.asarray(v)
                for k, v in _flatten_params(model.params).items()}
        record = {
            "kind": "update", "session_id": self.session_id,
            "type_id": TYPE_ID, "worker_id": self.worker_id,
            "timestamp": now, "iteration": int(iteration),
            "epoch": int(epoch),
            "score": model.score(),
            "performance": {
                "total_runtime_ms": (now - self._start_time) * 1000.0,
                "total_examples": self._total_examples,
                "total_minibatches": self._total_minibatches,
                "examples_per_second": self._examples_since / dt,
                "minibatches_per_second": self._minibatches_since / dt,
            },
            "parameters": self._param_group(flat),
        }
        if self._last_params is not None:
            updates = {k: flat[k] - self._last_params[k]
                       for k in flat if k in self._last_params
                       and flat[k].shape == self._last_params[k].shape}
            record["updates"] = self._param_group(updates)
            # update:parameter mean-magnitude ratio — the dashboard's canonical
            # learning-health chart (reference TrainModule ratio plot)
            record["update_ratios"] = {
                k: (record["updates"][k]["mean_magnitude"]
                    / max(record["parameters"][k].get("mean_magnitude", 0.0), 1e-12))
                for k in record.get("updates", {})
                if "mean_magnitude" in record["updates"][k]}
        if self.reads_features(iteration):
            acts = self._sample_activations(model)
            if acts:
                record["activations"] = acts
        if self.collect_memory:
            record["memory"] = self._memory_report()
        record["stats_collection_duration_ms"] = \
            (time.perf_counter() - t0) * 1000.0
        self.storage.put_update(record)
        # one source, two surfaces: the same record that feeds the
        # dashboard updates the MetricsRegistry (score / throughput
        # gauges) and flows into the trace/flight event pipeline
        from deeplearning4j_tpu.obs.registry import publish_stats_update
        publish_stats_update(record)
        self._last_params = flat
        self._last_report_time = now
        self._examples_since = 0
        self._minibatches_since = 0

    def _sample_activations(self, model) -> Optional[dict]:
        x = getattr(model, "_last_features", None)
        if x is None or not hasattr(model, "feed_forward"):
            return None
        try:
            acts = model.feed_forward(x)
        except Exception:
            return None
        return {str(i): self._param_group({"act": np.asarray(a)})["act"]
                for i, a in enumerate(acts)}

"""Model serialization: save/restore networks as a single zip file.

Parity surface: reference deeplearning4j-nn/.../util/ModelSerializer.java
(:37 class, :52 writeModel — config JSON + params + updater state,
:137+ restoreMultiLayerNetwork / restoreComputationGraph).

Zip layout mirrors the reference's:
- ``configuration.json``  — network config (our JSON schema)
- ``coefficients.npz``    — flat numpy archive of all params
- ``updaterState.npz``    — optimizer state (saved when save_updater=True)
- ``metadata.json``       — model class, iteration/epoch counters, format version
- ``quantization.json``   — quant/ calibration record (present iff the model
  is an int8-quantized serving graph; the int8 weights + scales already
  live in the config/coefficients entries, so restore rebuilds the exact
  quantized predict and this record lets serving re-apply the SAME
  lowering to newer fp32 checkpoints)
- ``tuning.json``         — perf/autotune TuningRecord (present iff the model
  carries one): the autotuned batch size / fusion / remat / serving bucket
  ladder, so training replicas and serving endpoints restoring this model
  inherit the tuned execution without re-searching

The checkpoint/ subsystem extends this layout with ``rngState.npz`` (the
training PRNG key via ``jax.random.key_data``) and extra metadata
(``batch_in_epoch``) so a restore resumes the EXACT step — same rng split
chain, same counters — making crash-resume bitwise-identical to an
uninterrupted run. ``snapshot_training_state`` / ``checkpoint_zip_bytes`` /
``restore_checkpoint`` below are that format; a checkpoint zip is a strict
superset of ``write_model``'s, so plain ``restore()`` also reads it.
"""

from __future__ import annotations

import io
import json
import zipfile
from typing import Union

import jax
import numpy as np

FORMAT_VERSION = 1


def _path_key(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


def _flatten_with_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_path_key(path): np.asarray(leaf) for path, leaf in flat}


def _save_npz_bytes(arrays: dict) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


# Counters that a layer's non-trained ``state`` gained after checkpoints had
# been written without them (by the leaf's own name): such a checkpoint
# restores with the leaf as the fresh model drew it, a counter at 0. Never a
# trained leaf.
_STATE_LEAVES_ADDED_LATER = ("steps_every_window",)


def _restore_into(tree, arrays: dict):
    """Rebuild a pytree with the same structure, leaves taken from arrays."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = []
    for path, leaf in flat:
        key = _path_key(path)
        if key not in arrays:
            if key.rpartition("/")[2] in _STATE_LEAVES_ADDED_LATER:
                leaves.append(leaf)
                continue
            raise ValueError(f"Missing array '{key}' in checkpoint")
        saved = arrays[key]
        if tuple(saved.shape) != tuple(np.shape(leaf)):
            raise ValueError(
                f"Shape mismatch for '{key}': checkpoint {saved.shape} vs model "
                f"{np.shape(leaf)}")
        leaves.append(saved.astype(np.asarray(leaf).dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def write_model(model, path: str, save_updater: bool = True):
    """reference ModelSerializer.writeModel :52"""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    if model.params is None:
        model.init()
    if isinstance(model, MultiLayerNetwork):
        model_type = "MultiLayerNetwork"
    elif isinstance(model, ComputationGraph):
        model_type = "ComputationGraph"
    else:
        raise TypeError(f"Cannot serialize {type(model)}")
    aug = getattr(model, "augmentation", None)
    meta = {
        "format_version": FORMAT_VERSION,
        "model_type": model_type,
        "iteration": model.iteration,
        "epoch": model.epoch,
        "has_updater": bool(save_updater),
        "augmentation": None if aug is None else aug.to_dict(),
    }
    cal = getattr(model, "_quant_calibration", None)
    tun = getattr(model, "_tuning_record", None)
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("configuration.json", model.conf.to_json())
        z.writestr("metadata.json", json.dumps(meta))
        z.writestr("coefficients.npz",
                   _save_npz_bytes(_flatten_with_paths([model.params, model.state])))
        if save_updater:
            z.writestr("updaterState.npz",
                       _save_npz_bytes(_flatten_with_paths(model.opt_state)))
        if cal is not None:
            z.writestr("quantization.json", cal.to_json())
        if tun is not None:
            z.writestr("tuning.json", tun.to_json())


def snapshot_training_state(model) -> dict:
    """Host-side snapshot of everything exact-step resume needs: params,
    layer state, updater state, the training PRNG key and the step/epoch
    counters. ``jax.device_get`` copies to HOST memory on the calling
    (training) thread, so the snapshot is immune to the train step's buffer
    donation — a checkpoint/ worker thread can serialize it later while
    training keeps mutating the live device buffers."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    if model.params is None:
        model.init()
    if isinstance(model, MultiLayerNetwork):
        model_type = "MultiLayerNetwork"
    elif isinstance(model, ComputationGraph):
        model_type = "ComputationGraph"
    else:
        raise TypeError(f"Cannot checkpoint {type(model)}")
    rng = model._rng
    comp = getattr(model, "grad_compression", None)
    cs = getattr(model, "compress_state", None)
    cal = getattr(model, "_quant_calibration", None)
    tun = getattr(model, "_tuning_record", None)
    return {
        # quant/ ride-along: a checkpointed QUANTIZED serving model (its
        # int8 weights are ordinary params) restores with the calibration
        # record it was lowered with
        "quant_calibration": None if cal is None else cal.to_dict(),
        # perf/autotune ride-along: the tuned execution config travels
        # with the checkpoint so restored replicas inherit it
        "tuning_record": None if tun is None else tun.to_dict(),
        # on-device augmentation ride-along (datasets/augment.py): the
        # augmented train step is part of the rng-exact resume contract —
        # a restored replica training WITHOUT it would silently diverge
        "augmentation": (None if getattr(model, "augmentation", None)
                         is None else model.augmentation.to_dict()),
        "model_type": model_type,
        "conf_json": model.conf.to_json(),
        "iteration": int(model.iteration),
        "epoch": int(model.epoch),
        "params": jax.device_get(model.params),
        "state": jax.device_get(model.state),
        "opt_state": jax.device_get(model.opt_state),
        "rng": None if rng is None else np.asarray(jax.random.key_data(rng)),
        # gradient-compression ride-along (parallel/compress.py): the
        # scheme config lands in metadata and the error-feedback state in
        # its own npz, so a restored model resumes the compressed run
        # bitwise (residuals included)
        "grad_compression": None if comp is None else comp.to_config(),
        "compress_state": None if cs is None else jax.device_get(cs),
    }


def checkpoint_zip_bytes(snap: dict, extra_meta: dict = None) -> bytes:
    """Serialize a ``snapshot_training_state`` dict to checkpoint-zip bytes
    (built in memory so the caller can hash and write them atomically).

    ZIP_STORED, not DEFLATED: the payload is float parameter data that
    deflate shrinks ~10% at ~8x the CPU, and on the checkpoint cadence the
    writer thread's GIL time interferes with the step loop — bytes are
    cheap, step-loop stalls are not. (``write_model`` stays DEFLATED; it is
    the archival format.)"""
    meta = {
        "format_version": FORMAT_VERSION,
        "model_type": snap["model_type"],
        "iteration": snap["iteration"],
        "epoch": snap["epoch"],
        "has_updater": snap["opt_state"] is not None,
        "has_rng": snap["rng"] is not None,
        "grad_compression": snap.get("grad_compression"),
        "has_compress_state": snap.get("compress_state") is not None,
        "has_quant_calibration": snap.get("quant_calibration") is not None,
        "has_tuning_record": snap.get("tuning_record") is not None,
        "augmentation": snap.get("augmentation"),
    }
    meta.update(extra_meta or {})
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_STORED) as z:
        z.writestr("configuration.json", snap["conf_json"])
        z.writestr("metadata.json", json.dumps(meta))
        z.writestr("coefficients.npz", _save_npz_bytes(
            _flatten_with_paths([snap["params"], snap["state"]])))
        if snap["opt_state"] is not None:
            z.writestr("updaterState.npz",
                       _save_npz_bytes(_flatten_with_paths(snap["opt_state"])))
        if snap["rng"] is not None:
            z.writestr("rngState.npz",
                       _save_npz_bytes({"key_data": snap["rng"]}))
        if snap.get("compress_state") is not None:
            z.writestr("compressState.npz", _save_npz_bytes(
                _flatten_with_paths(snap["compress_state"])))
        if snap.get("quant_calibration") is not None:
            z.writestr("quantization.json",
                       json.dumps(snap["quant_calibration"], sort_keys=True))
        if snap.get("tuning_record") is not None:
            z.writestr("tuning.json",
                       json.dumps(snap["tuning_record"], sort_keys=True))
    return buf.getvalue()


def restore_checkpoint(path, load_updater: bool = True):
    """Restore a checkpoint zip to ``(model, meta)`` — like ``restore`` but
    also rehydrates the training PRNG key, so continuing ``fit`` follows the
    exact rng split chain the interrupted run would have. ``path`` is a
    filesystem path or a binary file-like (the storage-backend restore path
    hands in a BytesIO of the fetched object). Zip member reads are
    CRC-checked, so a corrupted file raises rather than restoring
    silently-wrong params (the manifest layer above turns that into a
    fall-back to the previous checkpoint)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.conf.graph import ComputationGraphConfiguration
    with zipfile.ZipFile(path, "r") as z:
        meta = json.loads(z.read("metadata.json"))
        cfg_json = z.read("configuration.json").decode()
        if meta["model_type"] == "MultiLayerNetwork":
            model = MultiLayerNetwork(MultiLayerConfiguration.from_json(cfg_json))
        else:
            model = ComputationGraph(ComputationGraphConfiguration.from_json(cfg_json))
        model.init()
        coeff = dict(np.load(io.BytesIO(z.read("coefficients.npz"))))
        model.params, model.state = _restore_into(
            [model.params, model.state], coeff)
        if load_updater and meta.get("has_updater", True) \
                and "updaterState.npz" in z.namelist():
            upd = dict(np.load(io.BytesIO(z.read("updaterState.npz"))))
            model.opt_state = _restore_into(model.opt_state, upd)
        if meta.get("has_rng") and "rngState.npz" in z.namelist():
            rng = dict(np.load(io.BytesIO(z.read("rngState.npz"))))
            model._rng = jax.random.wrap_key_data(
                jnp.asarray(rng["key_data"]))
        if meta.get("grad_compression"):
            _restore_compression(model, meta, z)
        _restore_quant_calibration(model, z)
        _restore_tuning_record(model, z)
        _restore_augmentation(model, meta)
        model.iteration = meta.get("iteration", 0)
        model.epoch = meta.get("epoch", 0)
    return model, meta


def _restore_quant_calibration(model, z: zipfile.ZipFile):
    """Re-attach the quant/ calibration record when one rides in the zip
    (the quantized layers themselves round-trip through the config JSON +
    coefficients like any other layer)."""
    if "quantization.json" in z.namelist():
        from deeplearning4j_tpu.quant.calibrate import CalibrationRecord
        model._quant_calibration = CalibrationRecord.from_json(
            z.read("quantization.json").decode())


def _restore_tuning_record(model, z: zipfile.ZipFile):
    """Re-attach the perf/autotune TuningRecord when one rides in the zip
    (the tuned conf itself — fused layers, remat knobs — round-trips
    through the config JSON like any other configuration)."""
    if "tuning.json" in z.namelist():
        from deeplearning4j_tpu.perf.autotune import TuningRecord
        model._tuning_record = TuningRecord.from_json(
            z.read("tuning.json").decode())


def _restore_augmentation(model, meta: dict):
    """Re-enable on-device augmentation when the checkpoint metadata
    carries its config — the resumed train step must augment exactly like
    the interrupted one or the rng-exact resume silently diverges."""
    if meta.get("augmentation"):
        from deeplearning4j_tpu.datasets.augment import ImageAugmentation
        model.augmentation = ImageAugmentation.from_dict(
            meta["augmentation"])


def _restore_compression(model, meta: dict, z: zipfile.ZipFile):
    """Rebuild the gradient-compression scheme + error-feedback state from
    checkpoint metadata via the shared ride-along restore policy
    (parallel/compress.restore_compress_state)."""
    from deeplearning4j_tpu.parallel.compress import restore_compress_state
    arrays = None
    if meta.get("has_compress_state") and "compressState.npz" in z.namelist():
        arrays = dict(np.load(io.BytesIO(z.read("compressState.npz"))))
    restore_compress_state(model, meta["grad_compression"], arrays,
                           origin="checkpointed")


def restore_multi_layer_network(path: str, load_updater: bool = True):
    """reference ModelSerializer.restoreMultiLayerNetwork :137"""
    return _restore(path, expect="MultiLayerNetwork", load_updater=load_updater)


def restore_computation_graph(path: str, load_updater: bool = True):
    return _restore(path, expect="ComputationGraph", load_updater=load_updater)


def restore(path: str, load_updater: bool = True):
    return _restore(path, expect=None, load_updater=load_updater)


def _restore(path, expect, load_updater):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.conf.graph import ComputationGraphConfiguration
    with zipfile.ZipFile(path, "r") as z:
        meta = json.loads(z.read("metadata.json"))
        if expect is not None and meta["model_type"] != expect:
            raise ValueError(
                f"Checkpoint holds a {meta['model_type']}, not a {expect}")
        cfg_json = z.read("configuration.json").decode()
        if meta["model_type"] == "MultiLayerNetwork":
            model = MultiLayerNetwork(MultiLayerConfiguration.from_json(cfg_json))
        else:
            model = ComputationGraph(ComputationGraphConfiguration.from_json(cfg_json))
        model.init()
        coeff = dict(np.load(io.BytesIO(z.read("coefficients.npz"))))
        params, state = _restore_into([model.params, model.state], coeff)
        model.params, model.state = params, state
        if load_updater and meta.get("has_updater") and "updaterState.npz" in z.namelist():
            upd = dict(np.load(io.BytesIO(z.read("updaterState.npz"))))
            model.opt_state = _restore_into(model.opt_state, upd)
        _restore_quant_calibration(model, z)
        _restore_tuning_record(model, z)
        _restore_augmentation(model, meta)
        model.iteration = meta.get("iteration", 0)
        model.epoch = meta.get("epoch", 0)
    return model

"""Device mesh helpers.

This module is the TPU-native replacement for the reference's entire
parallelism plumbing (SURVEY §2.4): thread-per-device workers
(ParallelWrapper.java:124-143), `Nd4j.averageAndPropagate` parameter
averaging (:327-359), threshold-compressed gradient queues
(EncodedGradientsAccumulator.java:33) and the Aeron parameter server
(SharedTrainingMaster.java:451-469) all collapse into ONE abstraction:
a `jax.sharding.Mesh` with named axes

- ``data``  — data parallelism (batch sharding; XLA inserts the gradient
  all-reduce over ICI, exact every step)
- ``model`` — tensor parallelism (param sharding; XLA/GSPMD inserts
  all-gather/reduce-scatter where needed)

plus axis conventions for sequence parallelism (ring attention) layered on
top in ``ring_attention.py``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(dp: Optional[int] = None, tp: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a (data, model) mesh. ``dp`` defaults to n_devices // tp.

    On a v5e slice the mesh axes map onto the physical ICI torus by XLA's
    device ordering; collectives ride ICI, not DCN, within a slice.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if dp is None:
        if n % tp:
            raise ValueError(f"{n} devices not divisible by tp={tp}")
        dp = n // tp
    if dp * tp > n:
        raise ValueError(f"dp*tp = {dp * tp} exceeds {n} devices")
    arr = np.asarray(devices[:dp * tp]).reshape(dp, tp)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Shard the leading (batch) axis over 'data'."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def shard_batch(mesh: Mesh, arr):
    """Place one array with its batch axis sharded over the mesh. A host
    array goes straight to its shards — never whole onto the first
    device and from there to the others."""
    a = arr if isinstance(arr, jax.Array) else np.asarray(arr)
    return jax.device_put(a, data_sharding(mesh, a.ndim))


def tp_param_spec(key: str, shape) -> P:
    """Tensor-parallel PartitionSpec for one parameter.

    Convention (megatron-style column sharding on the output dimension):
    - matmul weights (n_in, n_out)            -> P(None, 'model')
    - conv kernels HWIO                        -> P(None, None, None, 'model')
    - biases / per-feature vectors (n,)        -> P('model')
    - everything else                          -> replicated
    GSPMD resolves the resulting contractions with all-gathers/reduce-scatters
    over the 'model' axis.
    """
    ndim = len(shape)
    if key in ("W", "U", "W_pw") and ndim == 2:
        return P(None, MODEL_AXIS)
    if key in ("W", "W_dw", "W_pw") and ndim == 4:
        return P(None, None, None, MODEL_AXIS)
    if key == "W" and ndim == 3:  # conv1d WIO
        return P(None, None, MODEL_AXIS)
    if ndim == 1 and key in ("b", "gamma", "beta"):
        return P(MODEL_AXIS)
    return P()


def tp_shardings(mesh: Mesh, params):
    """Build a params-shaped pytree of NamedShardings for tensor parallelism.

    Divisibility-aware: a param whose sharded dim is not divisible by the
    'model' axis size stays replicated (correct, just not partitioned).
    """
    tp = mesh.shape[MODEL_AXIS]

    def leaf(path, a):
        key = None
        for p in reversed(path):
            if hasattr(p, "key"):
                key = str(p.key)
                break
        spec = tp_param_spec(key or "", a.shape)
        # drop the sharding when not divisible
        for axis_idx, axis_name in enumerate(spec):
            if axis_name == MODEL_AXIS and a.shape[axis_idx] % tp:
                return NamedSharding(mesh, P())
        return NamedSharding(mesh, spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(treedef, [leaf(p, a) for p, a in flat])

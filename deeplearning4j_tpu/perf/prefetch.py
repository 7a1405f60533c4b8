"""Host→device transfer overlap.

``jnp.asarray`` inside the step loop serializes: the host blocks preparing
and shipping batch N while the device idles, then the device computes while
the host idles. ``DevicePrefetchIterator`` double-buffers instead — it
issues the (optionally mesh-sharded) ``jax.device_put`` of batch N+1 before
handing batch N to the caller, so the N+1 transfer rides alongside step N's
compute. JAX transfers are asynchronous, so "issue" costs the host almost
nothing.

Composes with the host-side ``AsyncDataSetIterator`` (ETL on a background
thread) — wrap Async around the raw iterator for host overlap, then this
around Async for device overlap:

    it = DevicePrefetchIterator(AsyncDataSetIterator(raw), mesh=mesh)

Reference analogue: AsyncDataSetIterator.java covers only the host half;
the device half did not exist because ND4J transfers were synchronous
per-op, not per-batch.
"""

from __future__ import annotations

from collections import deque
from typing import Optional

import jax.numpy as jnp

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterators import DataSetIterator


def _arrays_of(ds):
    """Every array slot of a DataSet or MultiDataSet (None where empty)."""
    if isinstance(ds, MultiDataSet):
        return [a for arrs in (ds.features, ds.labels, ds.features_masks,
                               ds.labels_masks) for a in arrs or ()]
    return [ds.features, ds.labels, ds.features_mask, ds.labels_mask]


class DevicePrefetchIterator(DataSetIterator):
    """Yield DataSets (or MultiDataSets) whose arrays are already resident
    on device.

    ``mesh`` shards the batch axis over the mesh's 'data' axis (the layout
    ParallelWrapper trains on — its own ``device_put`` then becomes a
    no-op); without a mesh, arrays land on the default device. A batch that
    does not divide the mesh's data axis passes through as host arrays
    (the trainer's ragged-batch policy, drop or raise, stays in charge).

    ``place_fn`` overrides the placement entirely: a ``ds -> ds`` callable
    whose result is yielded in the batch's place. ClusterTrainer uses this
    to issue its multi-host global-batch assembly
    (``make_array_from_process_local_data``) one batch ahead — the device
    transfer of batch N+1 then rides alongside step N exactly like the
    single-host device_put path. Returning the batch UNCHANGED marks it
    passed-through (host-side), keeping the caller's ragged policy in
    charge.

    ``lookahead`` is the number of batches in flight beyond the one being
    consumed; 1 (double buffering) is right unless transfers are much
    shorter than steps AND the source is bursty.
    """

    def __init__(self, base, mesh=None, lookahead: int = 1, place_fn=None):
        self._base = base
        self._mesh = mesh
        self._lookahead = max(1, int(lookahead))
        self._place_fn = place_fn
        self.batches_prefetched = 0
        self.batches_passed_through = 0

    # ------------------------------------------------------------ placement
    def _place_array(self, a):
        if a is None:
            return None
        if self._mesh is not None:
            from deeplearning4j_tpu.parallel.mesh import shard_batch
            return shard_batch(self._mesh, a)
        return jnp.asarray(a)

    def _place(self, ds):
        """``_stage`` under a ``prefetch.place`` span (obs/trace.py): the
        host time to ISSUE one batch's staging, where it happens — inside
        the fit loop's ``next()``, one batch ahead of the step."""
        from deeplearning4j_tpu.obs.registry import get_registry
        from deeplearning4j_tpu.obs.trace import get_tracer
        arrays = [a for a in _arrays_of(ds) if a is not None]
        nbytes = sum(int(getattr(a, "nbytes", 0)) for a in arrays)
        with get_tracer().span("prefetch.place", bytes=nbytes,
                               arrays=len(arrays)):
            out = self._stage(ds)
        get_registry().counter(
            "prefetch_bytes_total", unit="bytes",
            help="bytes of batch arrays handed to DevicePrefetchIterator's "
                 "staging (device_put / shard_batch / place_fn)").inc(nbytes)
        return out

    def _stage(self, ds):
        if self._place_fn is not None:
            out = self._place_fn(ds)
            if out is ds:  # unchanged == declined (e.g. ragged)
                self.batches_passed_through += 1
            else:
                self.batches_prefetched += 1
            return out
        if self._mesh is not None:
            from deeplearning4j_tpu.parallel.mesh import DATA_AXIS
            if ds.num_examples() % self._mesh.shape[DATA_AXIS]:
                self.batches_passed_through += 1
                return ds  # ragged: leave on host, trainer decides
        self.batches_prefetched += 1
        if isinstance(ds, MultiDataSet):
            def place_list(arrs):
                return (None if arrs is None
                        else [self._place_array(a) for a in arrs])
            return MultiDataSet(place_list(ds.features),
                                place_list(ds.labels),
                                place_list(ds.features_masks),
                                place_list(ds.labels_masks))
        return DataSet(self._place_array(ds.features),
                       self._place_array(ds.labels),
                       self._place_array(ds.features_mask),
                       self._place_array(ds.labels_mask))

    # ------------------------------------------------------------- iteration
    def _pump(self, source):
        buf: deque = deque()
        for ds in source:
            # the base applies its OWN preprocessor while iterating; one set
            # on this wrapper must also run — before device placement
            if self.pre_processor is not None:
                ds = self.pre_processor(ds)
            buf.append(self._place(ds))
            if len(buf) > self._lookahead:
                yield buf.popleft()
        while buf:
            yield buf.popleft()

    def _generate(self):
        return self._pump(self._base)

    def __iter__(self):
        # bypass DataSetIterator.__iter__'s reset plumbing: iterating the
        # base runs its own reset (the preprocessor is handled in _pump)
        return self._generate()

    # seekable/epoch-aware base (datasets/sharded.py ShardedReader,
    # possibly under AsyncDataSetIterator): forward the resume/seek
    # surface so fleet-true resume survives the device-prefetch wrapper.
    # Via __getattr__ so hasattr() reflects whether the BASE supports it.
    def __getattr__(self, name):
        if name == "bind_epoch":
            base_bind = getattr(self._base, name)  # AttributeError if not

            def bind_epoch(provider):
                base_bind(provider)
                return self
            return bind_epoch
        if name == "iter_from":
            base_iter_from = getattr(self._base, name)

            def iter_from(start_batch):
                return self._pump(base_iter_from(start_batch))
            return iter_from
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    def reset(self):
        if hasattr(self._base, "reset"):
            self._base.reset()

    def batch_size(self):
        return self._base.batch_size() if hasattr(self._base, "batch_size") \
            else None

    def input_columns(self):
        return self._base.input_columns() if hasattr(self._base,
                                                     "input_columns") else None

    def total_outcomes(self):
        return self._base.total_outcomes() if hasattr(self._base,
                                                      "total_outcomes") else None

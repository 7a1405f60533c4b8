"""Network memory reports.

Parity surface: reference ``nn/conf/memory/NetworkMemoryReport.java`` /
``LayerMemoryReport.java`` / ``MemoryReport.java`` (per-layer parameter /
activation / working memory for a configuration + minibatch size,
``MultiLayerConfiguration.getMemoryReport(InputType)``).

TPU-native design: the reference hand-models ND4J workspace usage per layer
class. Under XLA the compiler owns scheduling and fusion, so the *measured*
numbers come straight from the compiled step's buffer assignment
(``jit(...).lower(...).compile().memory_analysis()`` — argument/output/temp/
peak bytes of the actual HBM allocation), while the per-layer table keeps
the reference's analytic view (param counts/bytes + activation bytes from
shape inference). The compiled numbers are exact for the hardware the step
compiles for; the analytic ones are device-independent estimates.
"""

from __future__ import annotations

import dataclasses
import json
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class LayerMemoryReport:
    """Per-layer analytic memory (reference LayerMemoryReport.java)."""

    name: str
    layer_class: str
    num_params: int
    param_bytes: int
    # activation size for ONE example (bytes); multiply by minibatch
    activation_bytes_per_example: int
    activation_shape: tuple
    # the layer's remat= knob, when set (perf/fusion.py policies)
    remat: Optional[str] = None
    # what the rematerialised layer keeps all the same, for ONE example
    # (its type's ``remat_keeps``: the delta-rule scan's output and chunk
    # states where the kernels run and the delta-rule layers' wide
    # projections' outputs, the latent attention's output, log-sum-exp and
    # q, k, v);
    # counted into the activation total
    remat_kept_bytes_per_example: int = 0

    def to_dict(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass
class MemoryReport:
    """Network-level report (reference NetworkMemoryReport.java)."""

    model_class: str
    minibatch: int
    dtype: str
    layers: List[LayerMemoryReport]
    total_param_bytes: int
    total_activation_bytes: int        # for the given minibatch
    updater_state_bytes: int
    # measured from the compiled train step's buffer assignment (None when
    # compilation was skipped)
    compiled: Optional[dict] = None
    # bytes the train-mode loss forward actually saves for its backward
    # (jaxpr-derived via perf/fusion.training_activation_bytes; None when
    # the conf has no loss layer or the trace is unsupported). Fusion and
    # per-layer remat= knobs move THIS number — the per-layer analytic
    # column above is layout-only and cannot see them.
    training_activation_bytes: Optional[int] = None
    # FusedConvBNActivation blocks in the configuration
    fused_blocks: int = 0

    def total_fixed_bytes(self) -> int:
        return self.total_param_bytes + self.updater_state_bytes

    def total_variable_bytes(self) -> int:
        return self.total_activation_bytes

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        return json.dumps(d, indent=2)

    def to_string(self) -> str:
        lines = [
            f"Network memory report: {self.model_class} "
            f"(minibatch={self.minibatch}, dtype={self.dtype})",
            f"{'layer':<28}{'class':<26}{'params':>12}{'param MB':>10}"
            f"{'act KB/ex':>11}",
        ]
        for lr in self.layers:
            lines.append(
                f"{lr.name:<28}{lr.layer_class:<26}{lr.num_params:>12,}"
                f"{lr.param_bytes / 2**20:>10.2f}"
                f"{lr.activation_bytes_per_example / 2**10:>11.1f}"
                + (f"  remat={lr.remat}" if lr.remat else "")
                + (f" keeps {lr.remat_kept_bytes_per_example / 2**10:.1f}"
                   " KB/ex" if lr.remat_kept_bytes_per_example else ""))
        lines.append(
            f"Totals: params {self.total_param_bytes / 2**20:.2f} MB, "
            f"updater state {self.updater_state_bytes / 2**20:.2f} MB, "
            f"activations {self.total_activation_bytes / 2**20:.2f} MB "
            f"@ minibatch {self.minibatch}")
        if self.training_activation_bytes is not None:
            lines.append(
                "Training residuals (fwd->bwd saved tensors, jaxpr-derived): "
                f"{self.training_activation_bytes / 2**20:.2f} MB @ "
                f"minibatch {self.minibatch}"
                + (f" ({self.fused_blocks} fused conv+BN blocks)"
                   if self.fused_blocks else ""))
        if self.compiled:
            c = self.compiled
            lines.append(
                "Compiled train step (XLA buffer assignment): "
                f"arguments {c['argument_bytes'] / 2**20:.2f} MB, "
                f"outputs {c['output_bytes'] / 2**20:.2f} MB, "
                f"temp {c['temp_bytes'] / 2**20:.2f} MB"
                + (f", peak {c['peak_bytes'] / 2**20:.2f} MB"
                   if c.get("peak_bytes") else ""))
        return "\n".join(lines)


def _tree_bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree)
               if hasattr(a, "dtype"))


def _type_shape(it) -> tuple:
    """Per-example activation shape for an InputType (time axis of an
    unknown-length sequence counted as 1 step)."""
    if it.passes:
        return (it.passes,) + _type_shape(dataclasses.replace(it, passes=None))
    if it.kind == "cnn":
        return (it.height, it.width, it.channels)
    if it.kind in ("rnn", "cnn1d"):
        return (it.timeseries_length or 1, it.size)
    return (it.flat_size(),)


def _kept_bytes(layer, it, dtype) -> int:
    """Bytes one example adds to what ``layer`` holds from its forward to
    its backward pass by the names its rematerialisation keeps, in a
    network that computes in ``dtype``."""
    from deeplearning4j_tpu.perf.fusion import kept_names
    return int(layer.remat_kept_bytes(it, dtype)) if kept_names(layer) else 0


def _input_type_bytes(it, itemsize: int):
    shape = _type_shape(it)
    return int(np.prod(shape)) * itemsize, shape


def get_memory_report(net, minibatch: int = 32,
                      compile_step: bool = True) -> MemoryReport:
    """Build a MemoryReport for an initialized MultiLayerNetwork (reference
    MultiLayerConfiguration.getMemoryReport). ``compile_step=True`` also
    lowers + compiles the jitted train step for (minibatch, input_type)
    shapes and records XLA's measured buffer sizes."""
    if net.params is None:
        net.init()
    conf = net.conf
    itemsize = jnp.dtype(conf.dtype).itemsize
    types = conf.layer_input_types()
    reports = []
    total_act = 0
    for i, (layer, it) in enumerate(zip(net.layers, types)):
        out_t = layer.output_type(it)
        act_bytes, act_shape = _input_type_bytes(out_t, itemsize)
        # what the layer hands on beside its output (``shared_values``) is
        # its activation too: counted here, once, whoever reads it
        for shared_t in layer.shared_values(it).values():
            act_bytes += _input_type_bytes(shared_t, itemsize)[0]
        kept = _kept_bytes(layer, it, conf.dtype)
        p_bytes = _tree_bytes(net.params[i])
        n_params = sum(a.size for a in jax.tree_util.tree_leaves(net.params[i]))
        reports.append(LayerMemoryReport(
            name=f"{i}_{type(layer).__name__}",
            layer_class=type(layer).__name__,
            num_params=int(n_params),
            param_bytes=int(p_bytes),
            activation_bytes_per_example=int(act_bytes),
            activation_shape=act_shape,
            remat=getattr(layer, "remat", None),
            remat_kept_bytes_per_example=kept))
        total_act += (act_bytes + kept) * minibatch
    compiled = None
    if compile_step:
        compiled = _compiled_step_stats(net, minibatch, types[0])
    try:
        from deeplearning4j_tpu.perf.fusion import training_activation_bytes
        train_bytes = int(training_activation_bytes(conf,
                                                    minibatch=minibatch))
    except Exception:
        train_bytes = None
    return MemoryReport(
        model_class=type(net).__name__,
        minibatch=minibatch,
        dtype=conf.dtype,
        layers=reports,
        total_param_bytes=int(_tree_bytes(net.params)),
        total_activation_bytes=int(total_act),
        updater_state_bytes=int(_tree_bytes(net.opt_state)),
        compiled=compiled,
        training_activation_bytes=train_bytes,
        fused_blocks=sum(
            1 for l in net.layers
            if type(l).__name__ == "FusedConvBNActivation"))


def _abstract_layer_stats(layer, it, key, itemsize: int):
    """(num_params, param_bytes, abstract_params) for one layer WITHOUT
    allocating: parameter shapes come from jax.eval_shape of the layer's
    init — the same shape-inference-first approach as analysis/validation."""
    p, _ = jax.eval_shape(lambda k: layer.init(k, it, jnp.float32), key)
    leaves = jax.tree_util.tree_leaves(p)
    n_params = int(sum(int(np.prod(a.shape)) for a in leaves))
    p_bytes = int(sum(int(np.prod(a.shape)) * itemsize for a in leaves))
    return n_params, p_bytes, p


def conf_memory_report(conf, input_type=None, minibatch: int = 32,
                       training_bytes: bool = True) -> MemoryReport:
    """Memory report for a CONFIGURATION — no network, no device buffers.

    Consumes the shape-inference pass (``layer_input_types`` /
    ``vertex_input_types``): per-layer parameter counts/bytes come from
    ``jax.eval_shape`` of each layer's init, activations from the inferred
    ``InputType`` chain, and updater state from ``jax.eval_shape`` of the
    optax transform's init over the abstract params. Accepts a
    MultiLayerConfiguration (``input_type`` may override the configured one)
    or a ComputationGraphConfiguration. ``training_bytes=False`` skips the
    jaxpr-derived training-activation-bytes measurement (a full abstract
    trace — seconds on large graphs); callers that only need the
    param/updater/per-layer tables (perf/planner.py measures residuals
    itself) opt out."""
    itemsize = jnp.dtype(conf.dtype).itemsize
    key = jax.random.key(0)
    reports: List[LayerMemoryReport] = []
    total_act = 0
    total_params = 0
    updater_bytes = 0

    if hasattr(conf, "layers"):  # MultiLayerConfiguration
        if input_type is not None:
            conf = dataclasses.replace(conf, input_type=input_type)
        if conf.input_type is None:
            raise ValueError("memory_report requires an input_type")
        types = conf.layer_input_types()
        entries = [(f"{i}_{type(l).__name__}", l, it)
                   for i, (l, it) in enumerate(zip(conf.wired_layers(), types))]
        per_layer_updater = [
            (getattr(l, "updater", None) or conf.updater) for l in conf.layers]
    else:  # ComputationGraphConfiguration
        types_map = conf.vertex_input_types()
        entries = []
        per_layer_updater = []
        wired = conf.wired_vertices()
        for name in conf.topological_order():
            obj = wired[name][0]
            if hasattr(obj, "init"):  # Layer
                entries.append((name, obj, types_map[name][0]))
                per_layer_updater.append(
                    getattr(obj, "updater", None) or conf.updater)

    fused_blocks = 0
    for (name, layer, it), upd in zip(entries, per_layer_updater):
        n_params, p_bytes, p_abs = _abstract_layer_stats(layer, it, key,
                                                         itemsize)
        try:
            out_t = layer.output_type(it)
        except ValueError:
            out_t = it
        act_bytes, act_shape = _input_type_bytes(out_t, itemsize)
        # what the layer hands on beside its output (``shared_values``) is
        # its activation too: counted here, once, whoever reads it
        for shared_t in layer.shared_values(it).values():
            act_bytes += _input_type_bytes(shared_t, itemsize)[0]
        kept = _kept_bytes(layer, it, conf.dtype)
        reports.append(LayerMemoryReport(
            name=name, layer_class=type(layer).__name__,
            num_params=n_params, param_bytes=p_bytes,
            activation_bytes_per_example=int(act_bytes),
            activation_shape=act_shape,
            remat=getattr(layer, "remat", None),
            remat_kept_bytes_per_example=kept))
        if type(layer).__name__ == "FusedConvBNActivation":
            fused_blocks += 1
        total_act += (act_bytes + kept) * minibatch
        total_params += p_bytes
        if n_params:
            opt = jax.eval_shape(upd.to_optax().init, p_abs)
            updater_bytes += int(sum(
                int(np.prod(a.shape)) * itemsize
                for a in jax.tree_util.tree_leaves(opt)
                if hasattr(a, "shape")))

    # the measured fwd->bwd residual set (fusion/remat-aware); best-effort:
    # inference-only confs (no loss layer) and exotic label shapes skip it
    train_bytes = None
    if training_bytes:
        try:
            from deeplearning4j_tpu.perf.fusion import (
                training_activation_bytes)
            train_bytes = int(training_activation_bytes(conf,
                                                        minibatch=minibatch))
        except Exception:
            train_bytes = None

    return MemoryReport(
        model_class=type(conf).__name__,
        minibatch=minibatch,
        dtype=conf.dtype,
        layers=reports,
        total_param_bytes=int(total_params),
        total_activation_bytes=int(total_act),
        updater_state_bytes=int(updater_bytes),
        compiled=None,
        training_activation_bytes=train_bytes,
        fused_blocks=fused_blocks)


def _compiled_step_stats(net, minibatch: int, first_input_type) -> Optional[dict]:
    try:
        conf = net.conf
        it = conf.input_type or first_input_type
        if it.kind == "cnn_flat":
            shape = (minibatch, it.flat_size())
        else:
            shape = (minibatch,) + _type_shape(it)
        out_layer = net.layers[-1]
        out_t = conf.layer_input_types()[-1]
        n_out = getattr(out_layer, "n_out", None) or 1
        x = jnp.zeros(shape, jnp.float32)
        if out_layer.output_type(out_t).kind in ("rnn", "cnn1d"):
            y = jnp.zeros((minibatch, shape[1], n_out), jnp.float32)
        else:
            y = jnp.zeros((minibatch, n_out), jnp.float32)
        step = net._make_train_step()
        rng = jax.random.key(0)
        lowered = step.lower(net.params, net.state, net.opt_state, rng,
                             x, y, None, None)
        ma = lowered.compile().memory_analysis()
        if ma is None:
            return None
        return {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0)),
            "generated_code_bytes":
                int(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
    except Exception:
        return None  # backend without memory stats: analytic table only

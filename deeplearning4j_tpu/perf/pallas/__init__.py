"""Hand-written Pallas kernel layer: selection, fallback and counters.

The last chip profile on record (tools/PROFILE_r5.md) pins
the ResNet50 bf16 step within ~5% of the measured HBM bandwidth floor:
conv fwd+dW+dX alone would allow 51.4% MFU, but the
BN-train stats/normalize/residual traffic XLA refuses to fuse across
costs ~4.7 extra full activation-set HBM crossings (tools/PROFILE_r5.md).
This package holds the kernels that cross that line by hand — SURVEY
L0/§7's replacement for libnd4j's C++ kernels exactly where XLA's fusion
control runs out. Seven families, each slotted behind a boundary the repo
already parity-tests:

- **bn** (:mod:`perf.pallas.bn`): fused BN-train forward/backward behind
  the ``fused_bn_act_train`` custom-VJP interface
  (nn/conf/convolutional.py) — VMEM-resident stats + normalize +
  activation (+ residual add), backward recomputing x̂ from the saved
  conv output plus O(C) mean/inv-std.
- **adc** (:mod:`perf.pallas.adc`): the retrieval hot loop — ADC LUT
  gather-accumulate for ``PQIndex``/``IVFPQIndex`` and the int4
  nibble-unpack fused against the int8×int8→int32 dot for the int4
  tables and int4 quantized weights.
- **kda** (:mod:`perf.pallas.kda`): the chunked scan of Kimi Delta
  Attention behind ``chunked_kda`` (nn/conf/linear_attention.py), forward
  and backward behind one custom-VJP — a chunk's decayed scores and its
  triangular solve made and used in VMEM, the state carried in scratch.
- **kda_inputs** (:mod:`perf.pallas.kda_inputs`): what the delta-rule
  layers (``KimiDeltaAttention``, ``GatedDeltaNet``) do between their
  projections' products and that scan — the causal depthwise convolution,
  SiLU, the q/k head norms, KDA's decay — as one kernel forward and one
  backward behind one custom-VJP, reading the products' (time, heads x K)
  outputs and writing the scan's (batch, heads, time, K) windows (a q/k
  head to every value head it serves).
- **attention** (:mod:`perf.pallas.attention`): blocked causal attention
  with q/k heads and v heads of different widths behind
  ``blocked_causal_attention`` (nn/conf/attention.py), a forward and a
  single-pass backward kernel behind one custom-VJP — a tile pair's
  scores, probabilities and their cotangents made and used in VMEM.
- **ssd** (:mod:`perf.pallas.ssd`): the chunked scan of the Mamba-2
  state-space mixer behind ``chunked_ssd`` (nn/conf/state_space.py),
  forward and backward behind one custom-VJP — a chunk's (chunk, chunk)
  decay factors of every head made and used in VMEM, time-major windows as
  the layer's convolution writes them, the states carried in scratch.
- **selective_scan** (:mod:`perf.pallas.selective_scan`): the recurrence
  of the Mamba-1 mixer behind ``chunked_selective_scan``
  (nn/conf/state_space.py), forward and backward behind one custom-VJP —
  no matrix product: a tile's (N, 512) float32 state carried in registers
  through a block's steps, the block's states made again in VMEM for the
  adjoint steps, (time, channels) windows as the layer's neighbours write
  and read them.

Selection contract (every kernel, no exceptions):

1. The jnp/XLA reference implementation stays where it is and remains
   the default. A kernel is USED only when :func:`enabled` resolves
   true for its family — explicitly via :func:`configure`/
   :func:`override` or the ``DLT_PALLAS`` env var (every family), or
   automatically on a TPU backend (the families of
   :data:`TPU_AUTO_FAMILIES` only) — AND the call site's shape predicate
   (``bn.supported``, ``adc.pq_supported``, ``adc.int4_supported``,
   ``kda.supported``, ``kda_inputs.supported``, ``attention.supported``,
   ``ssd.supported``, ``selective_scan.supported``) says the kernel fits.
   Anywhere else the reference runs.
2. Off-TPU, a force-enabled kernel runs in Pallas **interpret mode**
   (:func:`interpret` resolves true) — this is how CPU CI bitwise/
   tolerance-parity-tests the kernel bodies (tests/test_zz_pallas.py).
   On a TPU backend nothing but an explicit ``configure(interpret=True)``
   interprets.
3. Every dispatch records which implementation served it:
   ``kernel.pallas_<family>`` / ``kernel.xla_<family>`` CompileWatch
   counters (``bump_active`` — landing on the owning model/index watch
   like the attention flash-kernel choice) which ``obs``
   ``absorb_compile_watch`` surfaces on ``/metrics``.
4. The choice is a searchable autotuner candidate
   (``perf.autotune.autotune(pallas=...)``) recorded in TuningRecord as
   ``pallas_kernels`` — ``apply_tuning`` and
   ``ParallelInference(tuning=...)`` re-apply it, so training and
   serving replicas inherit the measured winner without re-searching —
   and the HBM planner snapshots it per plan
   (``MemoryPlan.kernels``).

What the v5e compiler (jax 0.9.0 / libtpu 0.0.34) says, family by
family, is kept as tests: tests/test_chip_compile.py compiles every
auto-selected kernel for a described v5e at a main-path shape, and
chip_smoke.py's ``kernels`` phase runs each against its reference on
the chip. Measured speed: ``kda_scan`` (PERF.md §5-6, PR 27: the cell's
scan 221 -> 103 ms a step), ``blocked_attention`` (PR 29: the cell's
latent attention, see PERF.md §6), ``kda_inputs`` (PR 31: both token
cells' delta-rule layers, PERF.md §5-6), ``ssd_scan`` (PR 47) and
``selective_scan`` (PR 51: PERF.md §5-6); the retrieval kernels against XLA
at 1M rows are still unmeasured (ROADMAP Speed 3/6).
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Callable, Dict, Optional

__all__ = [
    "FAMILIES", "TPU_AUTO_FAMILIES", "INTERPRET_ONLY_FAMILIES", "enabled",
    "interpret", "configure", "override", "candidate_flags",
    "selection_snapshot", "take", "kernel_select",
]

# Kernel families this layer provides, family -> the boundary the kernel
# slots behind. Keys are the <family> leg of the kernel.pallas_<family> /
# kernel.xla_<family> dispatch counters.
FAMILIES: Dict[str, str] = {
    "bn_act": "fused_bn_act_train forward (nn/conf/convolutional.py)",
    "bn_act_bwd": "fused_bn_act_train backward (custom-VJP bwd rule)",
    "adc_pq": "PQIndex flat-ADC gather-accumulate (retrieval/pq.py)",
    "adc_ivf_pq": "IVFPQIndex per-cell-LUT gather-accumulate "
                  "(retrieval/pq.py)",
    "int4_dot": "int4 nibble-unpack fused against the int32 dot "
                "(retrieval/index.py brute table, quant/lowering.py "
                "dense weights)",
    "kda_scan": "chunked_kda's scan over chunks, forward and backward "
                "(nn/conf/linear_attention.py)",
    "blocked_attention": "blocked_causal_attention's tile pairs, forward "
                         "and backward (nn/conf/attention.py)",
    "kda_inputs": "the delta-rule layers' input path, from the products to "
                  "the scan's operands, forward and backward "
                  "(nn/conf/linear_attention.py)",
    "ssd_scan": "chunked_ssd's scan over chunks, forward and backward "
                "(nn/conf/state_space.py)",
    "selective_scan": "chunked_selective_scan's recurrence, forward and "
                      "backward (nn/conf/state_space.py)",
}

# Families the automatic rule selects on a TPU backend: those the v5e
# compiler accepts at the full-width shapes their call sites produce.
# Not selected, with the compiler's words:
# - bn_act / bn_act_bwd: whole-row channel tiles — ResNet50 batch 128
#   fits only at the 7x7 stage ("RESOURCE_EXHAUSTED ... input window
#   allocation ... bf16[401408,128]" at (128,56,56,256)); needs row
#   blocking (ROADMAP Speed 3).
# - adc_ivf_pq: data-dependent CSR row gather in-kernel, which Mosaic
#   does not lower (its flat sibling's jnp.take: "Shape mismatch in
#   input, indices and output"); needs a DMA rework (ROADMAP Speed 6).
TPU_AUTO_FAMILIES = frozenset({"adc_pq", "int4_dot", "kda_scan",
                               "blocked_attention", "kda_inputs",
                               "ssd_scan", "selective_scan"})
# No shape of these compiles, so not even an explicit enable (a
# TuningRecord's ``pallas_kernels=True`` is applied process-wide) selects
# them outside interpret mode.
INTERPRET_ONLY_FAMILIES = frozenset({"adc_ivf_pq"})

_UNSET = object()
_lock = threading.Lock()
_state = {"enabled": None, "interpret": None}  # None = resolve automatically


def _backend() -> str:
    import jax
    return jax.default_backend()


def enabled(family: Optional[str] = None) -> bool:
    """Resolved selection state for ``family`` (``None``: for any family
    at all): explicit :func:`configure` wins, then the ``DLT_PALLAS`` env
    var (``1``/``0``) — both cover every family — then the automatic
    rule: on a TPU backend the families of :data:`TPU_AUTO_FAMILIES`,
    nothing anywhere else. :data:`INTERPRET_ONLY_FAMILIES` resolve false
    whenever the kernel would be compiled."""
    if family in INTERPRET_ONLY_FAMILIES and not interpret():
        return False
    with _lock:
        e = _state["enabled"]
    if e is not None:
        return bool(e)
    env = os.environ.get("DLT_PALLAS")
    if env in ("0", "1"):
        return env == "1"
    return ((family is None or family in TPU_AUTO_FAMILIES)
            and _backend() == "tpu")


def interpret() -> bool:
    """Should ``pallas_call`` run in interpret mode? An explicit setting,
    else automatic: interpret everywhere except a TPU backend —
    force-enabling kernels on CPU (tests, CI) gets the interpreter,
    never a Mosaic compile, and a TPU never interprets unasked."""
    with _lock:
        i = _state["interpret"]
    if i is not None:
        return bool(i)
    return _backend() != "tpu"


def configure(enabled: object = _UNSET, interpret: object = _UNSET) -> None:
    """Set the process-wide selection knobs. ``None`` restores automatic
    resolution; omitted arguments are left untouched. This is what
    ``apply_tuning`` calls when a TuningRecord carries ``pallas_kernels``
    — serving/training replicas inherit the tuned choice through it."""
    with _lock:
        if enabled is not _UNSET:
            _state["enabled"] = None if enabled is None else bool(enabled)
        if interpret is not _UNSET:
            _state["interpret"] = (None if interpret is None
                                   else bool(interpret))


@contextlib.contextmanager
def override(enabled: object = _UNSET, interpret: object = _UNSET):
    """Scoped :func:`configure` — the parity tests and the autotuner's
    candidate search run each arm under this."""
    with _lock:
        prev = dict(_state)
    configure(enabled=enabled, interpret=interpret)
    try:
        yield
    finally:
        with _lock:
            _state.update(prev)


def candidate_flags() -> tuple:
    """The autotuner's searchable arms for the pallas knob: ``(False,
    True)`` when kernels could actually serve (a TPU backend, or
    selection already forced on — the CPU-CI case), else ``()`` so the
    default search space stays exactly what it was."""
    if _backend() == "tpu" or enabled():
        return (False, True)
    return ()


def selection_snapshot() -> Dict[str, str]:
    """family -> "pallas" | "xla" at this instant — what a training step
    traced right now would run. ``plan_memory`` stamps this into each
    ``MemoryPlan`` so a plan documents the kernel layer it assumed."""
    return {fam: "pallas" if enabled(fam) else "xla" for fam in FAMILIES}


# ------------------------------------------------------------- dispatch
def take(family: str, supported: bool = True) -> bool:
    """One dispatch-site decision: returns True when the Pallas kernel
    for ``family`` should serve this call (enabled for the family AND
    the call shape is ``supported``), recording
    ``kernel.pallas_<family>`` or ``kernel.xla_<family>`` on the active
    CompileWatch either way. Called at trace time for jitted bodies (the
    attention flash-kernel precedent: one count per trace, not per
    step)."""
    from deeplearning4j_tpu.perf.compile_watch import bump_active
    use = bool(supported) and enabled(family)
    bump_active(f"kernel.pallas_{family}" if use else f"kernel.xla_{family}")
    return use


class _KernelSelect:
    """Callable that picks the Pallas or XLA implementation PER CALL
    (selection config is re-read every dispatch, so a TuningRecord applied
    after an index was built still takes effect; ``supported`` sees the
    call's own arguments) and exposes a combined ``_cache_size`` so
    ``CompileWatch.wrap`` keeps exact compile counting over both
    underlying jitted functions."""

    def __init__(self, family: str, pallas_fn: Callable, xla_fn: Callable,
                 supported: Callable[..., bool]):
        self.family = family
        self.pallas_fn = pallas_fn
        self.xla_fn = xla_fn
        self.supported = supported

    def __call__(self, *args, **kwargs):
        if take(self.family, self.supported(*args, **kwargs)):
            return self.pallas_fn(*args, **kwargs)
        return self.xla_fn(*args, **kwargs)

    def _cache_size(self) -> int:
        total = 0
        for fn in (self.pallas_fn, self.xla_fn):
            total += int(fn._cache_size())
        return total


def kernel_select(family: str, pallas_fn: Callable, xla_fn: Callable,
                  supported: Callable[..., bool] = lambda *a, **k: True
                  ) -> _KernelSelect:
    """The retrieval indexes' wiring point: ``compile_watch.wrap(
    kernel_select(...), key)`` dispatches to whichever implementation the
    current selection resolves to, with per-dispatch kernel.* counters.
    ``supported(*call_args)`` is the kernel's shape predicate."""
    if family not in FAMILIES:
        raise KeyError(f"unknown pallas kernel family {family!r} "
                       f"(known: {sorted(FAMILIES)})")
    return _KernelSelect(family, pallas_fn, xla_fn, supported)

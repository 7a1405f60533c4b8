"""Device time by the layer an operation came from.

``jax.profiler.ProfileData`` names the events of a chip's ``XLA Ops`` line
by their HLO line without its ``metadata=``, so a trace alone cannot say
which layer a fusion belongs to. The compiled step's HLO TEXT can: every
instruction there carries ``metadata={op_name="jit(train_step)/.../
<LayerClass>:<vertex>/<inner scope>/..."}`` (``apply_layer``'s named scope
and the layers' own). ``scopes`` maps instruction name to ``op_name``;
``seconds_under`` adds up, on the first chip, the union of the intervals of
the operations whose ``op_name`` holds a marker (a ``while`` and the
operations of its body overlap in time: the union counts that time once).
A driver keeps the text as ``cell.program_view["hlo_text"]``; where there
is none the readers report nothing."""

from __future__ import annotations

import re
from typing import Dict, Optional

from harness import trace as tracing

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name (no leading %): op_name}."""
    return {name.lstrip("%"): op
            for name, op in _INSTRUCTION.findall(hlo_text)}


def instruction_of(event_name: str) -> str:
    """The instruction's name out of a trace event's name (an HLO line,
    ``%fusion.12 = ...``, or already a bare name)."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def program_view(ctx) -> Optional[dict]:
    return getattr(ctx["cell"], "program_view", None)


def seconds_under(ctx, marker: str) -> Optional[float]:
    """Seconds of the traced slice in which the first chip ran an
    operation whose ``op_name`` holds ``marker``; None where the driver
    kept no HLO text or the trace has no operation."""
    view = program_view(ctx)
    if not view or not view.get("hlo_text"):
        return None
    by_name = view.get("_scopes")
    if by_name is None:
        by_name = view["_scopes"] = scopes(view["hlo_text"])
    chips = [d for d in ctx["trace"].devices if d.ops]
    if not chips:
        return None
    hits = [(s, e) for name, s, e in chips[0].ops
            if marker in by_name.get(instruction_of(name), "")]
    return tracing.total(tracing.union(hits))


def ms_per_step_under(ctx, marker: str) -> Optional[float]:
    secs = seconds_under(ctx, marker)
    steps = tracing.steps(ctx["trace"])
    if secs is None or not steps:
        return None
    return 1e3 * secs / steps


def roofline_pct(ctx, marker: str, cost_per_step: dict) -> Optional[float]:
    """The least time the chip could take for ``cost_per_step`` (the larger
    of operations over the bf16 peak and bytes over the HBM peak) over the
    measured time a step of the operations under ``marker``."""
    ms = ms_per_step_under(ctx, marker)
    if not ms:
        return None
    least_s = max(cost_per_step["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                  cost_per_step["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)


def training_passes(cfg: dict) -> float:
    """Forward passes' worth of work a training step does in a layer: the
    forward, the backward at twice a forward, and the forward again where
    the layer is rematerialised."""
    return 4.0 if cfg.get("program", {}).get("remat") else 3.0

"""``hlo_ops``'s readings for SOME of a layer class's layers.

``hlo_ops.seconds_under`` finds operations by one marker in their
``op_name``. Where layers of one class differ in kind by a FIELD (the
sliding-window and the full-attention layers of one model are both
``RotaryAttention``), a reader needs the operations of the layers it names
and, of those, the ones under a scope: ``under(names, scope)`` is that
test, and the three functions beside it are ``hlo_ops``'s with a test in
the marker's place."""

from __future__ import annotations

import re
from typing import Callable, Iterable, Optional

from harness import hlo_ops
from harness import trace as tracing


def under(layer_class: str, names: Iterable[str],
          scope: str = "") -> Callable[[str], bool]:
    """Whether an ``op_name`` holds ``<layer_class>:<name>`` for one of
    ``names`` (the whole name: ``l1_attn`` is not ``l10_attn``) and, where
    given, ``scope``."""
    names = list(names)
    marker = re.compile(re.escape(layer_class) + ":(?:"
                        + "|".join(map(re.escape, names)) + r")(?!\w)")
    return lambda op: bool(names and marker.search(op) and scope in op)


def seconds_where(ctx, wanted: Callable[[str], bool]) -> Optional[float]:
    view = hlo_ops.program_view(ctx)
    if not view or not view.get("hlo_text"):
        return None
    by_name = view.get("_scopes")
    if by_name is None:
        by_name = view["_scopes"] = hlo_ops.scopes(view["hlo_text"])
    chips = [d for d in ctx["trace"].devices if d.ops]
    if not chips:
        return None
    hits = [(s, e) for name, s, e in chips[0].ops
            if wanted(by_name.get(hlo_ops.instruction_of(name), ""))]
    return tracing.total(tracing.union(hits))


def ms_per_step_where(ctx, wanted) -> Optional[float]:
    secs = seconds_where(ctx, wanted)
    steps = tracing.steps(ctx["trace"])
    if secs is None or not steps:
        return None
    return 1e3 * secs / steps


def roofline_pct_where(ctx, wanted, cost_per_step: dict) -> Optional[float]:
    ms = ms_per_step_where(ctx, wanted)
    if not ms:
        return None
    least_s = max(cost_per_step["flops"] / ctx["peaks"]["bf16_flops_per_s"],
                  cost_per_step["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)


def attention_layers(ctx, kind: str):
    """(vertex names, the blocks) of the configuration's attention layers
    of ``kind`` (``"swa"`` | ``"full"``), from its reference's ``blocks``;
    ([], []) where the reference tells no kinds apart."""
    cell = ctx["cell"]
    ref = cell.reference
    if not hasattr(ref, "attend_cost"):
        return [], []
    blks = [b for b in ref.blocks(cell.config) if b.get("attn") == kind]
    return [b["name"] + "_attn" for b in blks], blks


def attend_roofline_pct(ctx, kind: str) -> Optional[float]:
    """Roofline share of ``rattn.attend`` in the attention layers of
    ``kind``: ``attend_cost`` of the reference (the positions the mask
    keeps) a layer, the forward (twice where rematerialised) plus a
    backward of 2.5 forwards, over the measured time under the scope."""
    view = hlo_ops.program_view(ctx)
    names, blks = attention_layers(ctx, kind)
    if not view or not names:
        return None
    cell = ctx["cell"]
    sequences = cell.traffic["sequences_per_step"]
    tokens = view["tokens_per_step"] // sequences
    # training_passes takes the backward at two forwards; here it is 2.5
    passes = (hlo_ops.training_passes(cell.config) + 0.5) * sequences
    flops = nbytes = 0.0
    for b in blks:
        one = cell.reference.attend_cost(cell.config, tokens, b["window"])
        flops += one["flops"] * passes
        nbytes += one["bytes"] * passes
    return roofline_pct_where(
        ctx, under("RotaryAttention", names, "rattn.attend"),
        {"flops": flops, "bytes": nbytes})


def attention_ms_per_step(ctx, kind: str) -> Optional[float]:
    names, _ = attention_layers(ctx, kind)
    if not names:
        return None
    return ms_per_step_where(ctx, under("RotaryAttention", names))

"""Who owns an operation of a step program.

Every operation that a step program runs on the device carries, in the
``op_name`` of its HLO instruction, the ``jax.named_scope``s that were open
where the program emitted it. The rule of this module: every such operation
belongs to an OWNER that the program names. A layer's operations, forward
and backward, lie under ``<LayerClass>:<name>`` (``layer_marker``, opened by
``nn.conf.layers.apply_layer`` and by the networks around what they run for
a layer or a vertex outside it); what the program emits outside any layer
lies under one of ``NON_LAYER_SCOPES``. ``owner_of`` reads an ``op_name``
back: the tests, the benchmark's ``harness/owners.py`` and ``PERF.md`` use
this one rule, and nothing else in the tree lists scopes a second time.
"""

from __future__ import annotations

import re
from typing import Optional

#: the optimizer application (``Network._apply_updates``)
OPTIM = "optim.update"
#: the compressed gradients' encode and decode (``parallel/compress.py``)
GRAD_COMPRESS = "grad.compress"
#: the master weights' cast to the compute dtype, and its cotangent back
PARAMS_CAST = "params.cast"
#: an output layer's loss, and the penalty on the weights
LOSS_SCORE = "loss.score"
LOSS_PENALTY = "loss.penalty"

#: the scopes the program opens outside any layer, as (start of a path
#: segment, owner). The loss functions' own scopes (``loss.blocked``,
#: ``loss.exit_weighted``, ``loop.exit_gate``, ``loop.exit_head``) are the
#: loss's. There is no scope for autodiff's sums over a value's uses: each
#: ``add_any`` carries the marker of the layer whose value it sums.
NON_LAYER_SCOPES = (
    (OPTIM, "optim"),
    (GRAD_COMPRESS, "grad.compress"),
    (PARAMS_CAST, "params.cast"),
    ("loss.", "loss"),
    ("loop.exit_", "loss"),
)

_WRAPPED = re.compile(r"^\w+\((.*)\)$")          # jvp(...), transpose(...)
_LAYER = re.compile(r"^([A-Za-z_]\w*):.")


def layer_marker(obj, name) -> str:
    """The scope every operation of a layer or vertex lies under."""
    return f"{type(obj).__name__}:{name}"


def _unwrapped(segment: str) -> str:
    """``transpose(jvp(X))`` -> ``X``."""
    while True:
        m = _WRAPPED.match(segment)
        if m is None:
            return segment
        segment = m.group(1)


def owner_of(op_name: Optional[str]) -> Optional[str]:
    """The owner of an operation by its ``op_name``: the class of the
    innermost ``<LayerClass>:<name>`` marker; where there is none, the owner
    of the innermost of ``NON_LAYER_SCOPES``; else None."""
    if not op_name:
        return None
    scoped = None
    for segment in reversed(op_name.split("/")):
        segment = _unwrapped(segment)
        layer = _LAYER.match(segment)
        if layer is not None:
            return layer.group(1)
        if scoped is None:
            scoped = next((owner for start, owner in NON_LAYER_SCOPES
                           if segment.startswith(start)), None)
    return scoped


__all__ = ["owner_of", "layer_marker", "NON_LAYER_SCOPES", "OPTIM",
           "GRAD_COMPRESS", "PARAMS_CAST", "LOSS_SCORE", "LOSS_PENALTY"]

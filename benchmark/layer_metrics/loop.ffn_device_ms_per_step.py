"""Device time a step spends in operations that came from a
``GatedFeedForward`` layer of the looped block (the SwiGLU's three
products, forward, rematerialised forward and backward; every such layer
and all passes of the loop together): union of their intervals on the
first chip over the steps in the traced slice, by the ``op_name`` of the
compiled step's HLO text (``harness/hlo_ops.py``)."""

LAYER = "looped block"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    return hlo_ops.ms_per_step_under(ctx, "GatedFeedForward:")

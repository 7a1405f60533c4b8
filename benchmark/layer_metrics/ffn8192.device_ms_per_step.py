"""Device time a step spends in operations that came from a
``GatedFeedForward`` layer of a model whose every block has the dense
SwiGLU (three products at a width of 8192, forward, rematerialised forward
and backward; every such layer together): union of their intervals on the
first chip over the steps in the traced slice, by the ``op_name`` of the
compiled step's HLO text (``harness/hlo_ops.py``); nothing where the
program has no such layer (the manifest's entry lists the cell it is
reported in: the other cells' dense layers have readers of their own)."""

LAYER = "dense feed-forward"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    return hlo_ops.ms_per_step_under(ctx, "GatedFeedForward:") or None

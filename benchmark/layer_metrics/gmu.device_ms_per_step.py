"""Device time a step spends in operations that came from a
``GatedMemoryUnit`` layer (the two products of 2,560 x 5,120 and the gate
over another layer's scan output between them; forward, rematerialised
forward and backward, with the memory's cotangent on its way back to the
layer that made it): union of their intervals on the first chip over the
steps in the traced slice, by the ``op_name`` of the compiled step's HLO
text (``harness/hlo_ops.py``); nothing where the program has no such
layer."""

LAYER = "gated memory unit"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    return hlo_ops.ms_per_step_under(ctx, "GatedMemoryUnit:") or None

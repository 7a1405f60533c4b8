"""Device time a step spends in operations that came from a
``RotaryAttention`` layer of the looped block (projections, rotation, the
tile pairs and their backward; every such layer and all passes of the loop
together): union of their intervals on the first chip over the steps in
the traced slice. The operations are found by the ``op_name`` the compiled
step's HLO text gives their instruction (``harness/hlo_ops.py``); the
loop's body is one ``while``, so an operation of a layer is one
instruction that runs once a pass."""

LAYER = "looped block"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    return hlo_ops.ms_per_step_under(ctx, "RotaryAttention:")

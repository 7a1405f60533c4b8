"""Device time a step spends in operations that came from a ``Mamba1Mixer``
layer (the wide product, the convolution, the two narrow products and the
softplus, the selective scan, the gate and the output product; forward,
rematerialised forward and backward; every such layer together): union of
their intervals on the first chip over the steps in the traced slice. The
operations are found by the ``op_name`` the compiled step's HLO text gives
their instruction (``harness/hlo_ops.py``); nothing where the program has
no such layer."""

LAYER = "state-space mixer"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    return hlo_ops.ms_per_step_under(ctx, "Mamba1Mixer:") or None

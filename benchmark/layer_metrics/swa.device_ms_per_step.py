"""Device time a step spends in operations that came from a
``RotaryAttention`` layer WITH a sliding window (projections, q/k norms,
rotation, the band's tile pairs and their backward; every such layer
together): union of their intervals on the first chip over the steps in
the traced slice. Which layers slide is the configuration's own
``layer_types`` (the reference's ``blocks``); the operations are found by
the ``op_name`` the compiled step's HLO text gives their instruction
(``harness/layer_scopes.py`` over ``harness/hlo_ops.py``)."""

LAYER = "window attention"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import layer_scopes

    return layer_scopes.attention_ms_per_step(ctx, "swa")

"""Device time a step spends in operations that came from a
``RotaryAttention`` layer WITHOUT a window in a model that has both kinds
(projections, q/k norms, the scaled rotation, the causal triangle's tile
pairs and their backward): union of their intervals on the first chip over
the steps in the traced slice, as ``swa.device_ms_per_step`` reads the
sliding layers."""

LAYER = "full attention"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import layer_scopes

    return layer_scopes.attention_ms_per_step(ctx, "full")

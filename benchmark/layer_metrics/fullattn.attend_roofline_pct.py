"""Roofline share of the full-attention layers' tile pairs (the
``rattn.attend`` scope of every ``RotaryAttention`` layer without a window
in a model that has both kinds): ``attend_cost(cfg, tokens, None)`` of the
configuration's reference module (the causal triangle's kept positions,
t + 1 keys a query) over the measured device time under the scope, counted
as ``swa.attend_roofline_pct`` counts the sliding layers: the existing
kernels at whatever length the cell runs."""

LAYER = "full attention"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import layer_scopes

    return layer_scopes.attend_roofline_pct(ctx, "full")

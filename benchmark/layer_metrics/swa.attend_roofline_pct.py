"""Roofline share of the sliding-window layers' tile pairs (the
``rattn.attend`` scope of every ``RotaryAttention`` layer with a window):
the least time the chip could take for the two products over the (query,
key) POSITIONS the mask keeps (``attend_cost(cfg, tokens, window)`` of the
configuration's reference module: min(t + 1, window) keys a query, whatever
tile visits them, so the masked halves of the band's diagonal and far-edge
tiles are no credit) over the measured device time of the operations under
the scope (the repeat of k and v, layout copies and the
``mla_attend_fwd`` / ``mla_attend_bwd`` kernels on the band). The forward
makes two products a position and the backward five, so a training step is
the forward (twice where the layer is rematerialised) plus 2.5 forwards.
The larger of ops / 197 TFLOP/s and bytes / 819 GB/s. A reading over 100%
is a wrong count, not a result."""

LAYER = "window attention"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import layer_scopes

    return layer_scopes.attend_roofline_pct(ctx, "swa")

"""Roofline share of the position-free attention layers' tile pairs at
64-wide heads (the ``rattn.attend`` scope of every ``RotaryAttention``
layer that the configuration's reference lists with ``"attn": "nope"``):
``attend_cost(cfg, tokens, None)`` of the reference module (the causal
triangle's kept positions, two products of width 64 a position and query
head; k and v read once a key/value head) over the measured device time
under the scope (``layer_scopes.attend_roofline_pct``). What the program
spends there on repeating k and v over their group of 4 and on lanes half
filled is in the time and not in the cost."""

LAYER = "full attention"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import layer_scopes

    return layer_scopes.attend_roofline_pct(ctx, "nope")

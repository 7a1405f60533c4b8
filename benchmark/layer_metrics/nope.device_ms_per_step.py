"""Device time a step spends in operations that came from the
position-free attention layers of a model whose other token mixers are
state-space layers: the ``RotaryAttention`` layers that the configuration's
reference lists with ``"attn": "nope"`` (projections, the softmax scale
folded into q, the causal triangle's tile pairs at 64-wide heads and their
backward; no rotation): union of their intervals on the first chip over the
steps in the traced slice (``layer_scopes.attention_ms_per_step``); nothing
where the program's text has no such layer."""

LAYER = "full attention"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import layer_scopes

    return layer_scopes.attention_ms_per_step(ctx, "nope") or None

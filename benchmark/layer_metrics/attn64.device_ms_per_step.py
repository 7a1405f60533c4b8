"""Device time a step spends in operations that came from the attention
layers of a model whose other token mixers are short convolutions: the
``RotaryAttention`` layers that the configuration's reference lists with
``"attn": "full"`` (projections, q/k norms, the rotation, the causal
triangle's tile pairs at 64-wide heads and their backward): union of their
intervals on the first chip over the steps in the traced slice, by
``fullattn.device_ms_per_step``'s code under a name of its own (that
entry lists the cell it is reported in)."""

LAYER = "full attention"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import layer_scopes

    return layer_scopes.attention_ms_per_step(ctx, "full")

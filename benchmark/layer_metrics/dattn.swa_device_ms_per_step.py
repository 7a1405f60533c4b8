"""Device time a step spends in operations that came from the
``DifferentialAttention`` layers under the WINDOW (the self-decoder's odd layers: projections with their biases, the band's tile pairs, the lambda subtraction and the 128-wide norm, and their backward): the layers that the
configuration's reference lists with ``"attn": "swa"``, by vertex
(``harness/layer_scopes.py`` ``under``); union of their intervals on the
first chip over the steps in the traced slice; nothing where the program's
text has no such layer."""

LAYER = "differential attention"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import layer_scopes

    ref = ctx["cell"].reference
    if not hasattr(ref, "blocks"):
        return None
    names = [b["vertex"] for b in ref.blocks(ctx["cell"].config)
             if b.get("attn") == "swa" and "vertex" in b]
    return layer_scopes.ms_per_step_where(
        ctx, layer_scopes.under("DifferentialAttention", names)) or None

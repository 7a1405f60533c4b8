"""Device time a step spends in operations that came from the
``DifferentialAttention`` layers that READ another layer's keys and values (the cross-decoder's odd layers: W_q and W_o alone, the triangle's tile pairs over the borrowed k and v, the combine, and their backward): the layers that the
configuration's reference lists with ``"attn": "cross"``, by vertex
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
             if b.get("attn") == "cross" and "vertex" in b]
    return layer_scopes.ms_per_step_where(
        ctx, layer_scopes.under("DifferentialAttention", names)) or None

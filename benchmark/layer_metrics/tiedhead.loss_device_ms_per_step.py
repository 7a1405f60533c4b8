"""Device time a step spends on the two uses of a tied embedding and the
loss between them: the operations of the ``embed`` vertex (the gather and,
backward, the scatter-add of its cotangent into the table), of the ``head``
vertex (the table transposed for the products) and of the loss (the
``loss.*`` scopes: the one block loop with its three products a block, the
sum of both uses' gradients arrives as an ``add_any`` under a layer's
marker). Union of the intervals on the first chip over the steps in the
traced slice; nothing where the program has neither vertex."""

import re

LAYER = "tied head"
UNIT = "ms"
MOVES = "train_items_per_s"

_VERTEX = re.compile(r"EmbeddingSequenceLayer:embed(?!\w)"
                     r"|TokenOutputLayer:head(?!\w)")
_MINE = re.compile(r"(?<![\w.])loss\.|" + _VERTEX.pattern)


def read(ctx):
    from harness import hlo_ops, layer_scopes

    view = hlo_ops.program_view(ctx)
    if not view or not _VERTEX.search(view.get("hlo_text") or ""):
        return None
    return layer_scopes.ms_per_step_where(
        ctx, lambda op: bool(_MINE.search(op))) or None

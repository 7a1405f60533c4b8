"""Device time a step spends in operations of the multi-token prediction
module: everything under a vertex named ``mtp1_*`` (the shift of the
embedding, the stack, ``mtp.combine``'s two norms and product, the
module's own latent attention and routed experts, its norm), forward,
rematerialised forward and backward. The module's share of the one loss
loop is the loss's and not counted here. Union of the intervals on the
first chip over the traced slice's steps; nothing where the program has no
such vertex."""

import re

LAYER = "multi-token prediction"
UNIT = "ms"
MOVES = "train_items_per_s"

_MODULE = re.compile(r"[A-Za-z_]\w*:mtp\d+_")


def read(ctx):
    from harness import layer_scopes

    return layer_scopes.ms_per_step_where(
        ctx, lambda op: bool(_MODULE.search(op))) or None

"""Device time a step spends in operations that came from a
``MultiHeadLatentAttention`` layer in the cell whose every block is one,
with a low-rank query and a decoupled rotation (the trunk's layers and the
prediction module's block together; both low-rank projections, their
norms, the rotations, the tile pairs and their backward, forward,
rematerialised forward and backward): what ``mla.device_ms_per_step``
reads, by that reader's own code, under a name of its own. (The ``mla.*``
entry of the manifest lists the Kimi cell, whose one latent layer is not
rotated, and a PR that adds a cell may not edit an entry.)"""

LAYER = "latent attention"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    return ctx["cell"].layer_reader("mla.device_ms_per_step")(ctx)

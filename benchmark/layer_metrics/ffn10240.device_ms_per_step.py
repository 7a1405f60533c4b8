"""Device time a step spends in operations that came from a
``GatedFeedForward`` layer of a model whose every block has the dense
SwiGLU at a width of 10,240 (three products, forward, rematerialised
forward and backward; every such layer together): by
``ffn8192.device_ms_per_step``'s code under a name of its own (that entry
lists the cell it is reported in); nothing where the program has no such
layer."""

LAYER = "dense feed-forward"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    return ctx["cell"].layer_reader("ffn8192.device_ms_per_step")(ctx)

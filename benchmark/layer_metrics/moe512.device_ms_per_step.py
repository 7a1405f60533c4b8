"""Device time a step spends in operations that came from a ``RoutedExperts`` layer
in the cell of the 512-expert softmax router: what ``moe.device_ms_per_step``
reads, by that reader's own code, under a name of its own. (The ``moe.*``
entries of the manifest list the cells they are reported in, and a PR that
adds a cell may not edit an entry: PERF.md section 7.)"""

LAYER = "routed experts"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    return ctx["cell"].layer_reader("moe.device_ms_per_step")(ctx)

"""Device time a step spends in operations that came from a ``RoutedExperts`` layer
in the cell of the 32-expert sigmoid router of 1792-wide experts without a
shared one: what ``moe.device_ms_per_step`` reads, by that reader's own code, under a name
of its own, as ``moe64.device_ms_per_step`` does. (The ``moe.*`` entries of the manifest
list the cells they are reported in, and a PR that adds a cell may not edit
an entry: PERF.md section 7; ROADMAP Queue 2 item 1a queues the fold.)"""

LAYER = "routed experts"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    return ctx["cell"].layer_reader("moe.device_ms_per_step")(ctx)

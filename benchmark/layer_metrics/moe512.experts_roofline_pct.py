"""Roofline share of the grouped expert products (the ``moe.experts`` scope)
in the cell of the 512-expert softmax router: what ``moe.experts_roofline_pct``
reads, by that reader's own code, under a name of its own. (The ``moe.*``
entries of the manifest list the cells they are reported in, and a PR that
adds a cell may not edit an entry: PERF.md section 7.)"""

LAYER = "routed experts"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    return ctx["cell"].layer_reader("moe.experts_roofline_pct")(ctx)

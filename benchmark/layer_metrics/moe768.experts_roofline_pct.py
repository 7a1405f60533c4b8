"""Roofline share of the grouped expert products (the ``moe.experts`` scope)
in the cell of the 256-expert sigmoid router over experts of 768 with a
shared one (the trunk's five routed layers and the prediction module's):
what ``moe.experts_roofline_pct`` reads, by that reader's own code, under a name of
its own, as ``moe512.experts_roofline_pct`` and ``moe64.experts_roofline_pct`` do. (The ``moe.*``
entries of the manifest list the cells they are reported in, and a PR that
adds a cell may not edit an entry: PERF.md section 7; ROADMAP Queue 2 item
1a queues the fold.)"""

LAYER = "routed experts"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    return ctx["cell"].layer_reader("moe.experts_roofline_pct")(ctx)

"""Roofline share of the grouped expert products (the ``moe.experts`` scope)
in the cell of the 32-expert sigmoid router of 1792-wide experts without a
shared one: what ``moe.experts_roofline_pct`` reads, by that reader's own code, under a name
of its own, as ``moe64.experts_roofline_pct`` does. (The ``moe.*`` entries of the manifest
list the cells they are reported in, and a PR that adds a cell may not edit
an entry: PERF.md section 7; ROADMAP Queue 2 item 1a queues the fold.)"""

LAYER = "routed experts"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    return ctx["cell"].layer_reader("moe.experts_roofline_pct")(ctx)

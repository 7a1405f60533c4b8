"""The busiest held expert's pairs over the mean of the held experts'
in the cell of the 256-expert sigmoid router over experts of 768 with a
shared one (the trunk's five routed layers and the prediction module's):
what ``moe.expert_load_max_over_mean`` reads, by that reader's own code, under a name of
its own, as ``moe512.expert_load_max_over_mean`` and ``moe64.expert_load_max_over_mean`` do. (The ``moe.*``
entries of the manifest list the cells they are reported in, and a PR that
adds a cell may not edit an entry: PERF.md section 7; ROADMAP Queue 2 item
1a queues the fold.)"""

LAYER = "routed experts"
UNIT = "x"
MOVES = "train_items_per_s"


def read(ctx):
    return ctx["cell"].layer_reader("moe.expert_load_max_over_mean")(ctx)

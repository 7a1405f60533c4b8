"""How unevenly the router loaded the experts held here over the window:
the busiest held expert's (token, expert) pairs over the mean of the held
experts', in the routed layer where that is largest. From the layers' own
counters (device arrays in the layer ``state``, read once after the
window). 1.0 is an even load; the grouped products' time follows the
busiest expert only through its extra row tiles."""

LAYER = "routed experts"
UNIT = "x"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    if not view or not view.get("moe"):
        return None
    worst = None
    for counts in view["moe"].values():
        tokens = counts["expert_tokens"]
        mean = sum(tokens) / max(len(tokens), 1)
        if mean > 0:
            worst = max(worst or 0.0, max(tokens) / mean)
    return worst

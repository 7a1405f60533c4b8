"""Device time per step of the operations that move data between chips
(all-reduce and its kin, by HLO name), on the chip that spent most on
them. Nothing to read on one chip."""

LAYER = "across chips"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import trace

    if ctx["chips"] < 2:
        return None
    steps = trace.steps(ctx["trace"])
    secs = trace.collective_seconds(ctx["trace"])
    if not steps or secs <= 0:
        return None
    return 1000.0 * secs / steps

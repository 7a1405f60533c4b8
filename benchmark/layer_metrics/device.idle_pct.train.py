"""1 - (union of the intervals in which an operation ran on the device) /
(traced slice), averaged over the chips, in a training cell."""

LAYER = "device"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import trace

    busy_s, window_s = trace.busy_seconds(ctx["trace"])
    if window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)

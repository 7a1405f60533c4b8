"""Model FLOP/s utilisation of the traced run: items per second (window
net of the time the profiler's start and stop held the host) times the
training FLOPs an item needs (``harness.flops`` over the reference's layer
list, 3 x forward) over chips times the published bf16 peak."""

LAYER = "fit loops"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import flops

    raw, cell = ctx["raw"], ctx["cell"]
    per_item = flops.train_flops_per_item(cell.reference.layers(cell.config))
    rate = raw["items"] / (raw["elapsed_s"] - raw["profiler_s"])
    return 100.0 * rate * per_item / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])

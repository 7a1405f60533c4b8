"""FLOPs the steps inside the traced slice needed, per chip, over (the
chip's busy time in the slice times the published bf16 peak): the
utilisation net of idle time. Steps (executions of the step program) and
busy time both come from the device trace. Says whether the step's kernels
are bound by compute or not."""

LAYER = "kernels"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import flops, trace

    raw, cell, tr = ctx["raw"], ctx["cell"], ctx["trace"]
    steps = trace.steps(tr)
    busy_s, _ = trace.busy_seconds(tr)
    if not steps or busy_s <= 0 or not raw.get("steps"):
        return None
    per_item = flops.train_flops_per_item(cell.reference.layers(cell.config))
    items_per_step_per_chip = raw["items"] / raw["steps"] / ctx["chips"]
    return 100.0 * per_item * items_per_step_per_chip * steps / (
        busy_s * ctx["peaks"]["bf16_flops_per_s"])

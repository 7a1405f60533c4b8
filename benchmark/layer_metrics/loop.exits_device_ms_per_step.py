"""Device time a step spends on the exits of the loop: the gates
(``loop.exit_gate``), the head and the cross-entropy of every pass in
blocks (``loop.exit_head``: four passes over the whole vocabulary) and the
exit-weighted mixing with its entropy term (``loss.exit_weighted``),
forward and backward. The three scopes do not overlap; each is the union of
its operations' intervals on the first chip over the steps in the traced
slice (``harness/hlo_ops.py``), and the reading is their sum."""

LAYER = "exit-weighted loss"
UNIT = "ms"
MOVES = "train_items_per_s"

SCOPES = ("loop.exit_gate", "loop.exit_head", "loss.exit_weighted")


def read(ctx):
    from harness import hlo_ops

    parts = [hlo_ops.ms_per_step_under(ctx, scope) for scope in SCOPES]
    if any(p is None for p in parts) or not sum(parts):
        return None
    return sum(parts)

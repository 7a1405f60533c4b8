"""What a save costs the thread that asked for it: the mean duration of the
whole ``checkpoint.save`` spans of the training thread in the traced slice
(the drain of the queued steps, the device-to-host copy, the hand-over to
the writer and whatever else ``CheckpointManager.save`` does before it
returns). ``None`` on a slice that holds no whole save.
SOURCE: program_span (``harness.checkpoint_spans``)."""

LAYER = "checkpoint"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import checkpoint_spans

    return checkpoint_spans.of(ctx).stall_ms()

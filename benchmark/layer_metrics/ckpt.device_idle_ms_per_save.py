"""What a save costs the device: the first chip's idle gaps that overlap a
whole ``checkpoint.save`` span of the training thread, each counted WHOLE,
from the device's last operation to its next (the refill after the copy is
the save's doing), per save in the traced slice. An earlier line splits it
into the part before the span, under it and after it, and gives apart the
idle time under an open ``checkpoint_writer.write`` and no save.
SOURCE: program_span + device_trace (``harness.checkpoint_spans``)."""

LAYER = "checkpoint"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import checkpoint_spans

    return checkpoint_spans.of(ctx).device_idle_ms()

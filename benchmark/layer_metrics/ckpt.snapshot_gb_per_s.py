"""The rate of the device-to-host copy: ``bytes`` of the whole saves'
``checkpoint.snapshot`` spans over their SELF time, which is the copy
without the wait for the queued steps (that is the child
``checkpoint.drain``).
SOURCE: program_span (``harness.checkpoint_spans``)."""

LAYER = "checkpoint"
UNIT = "GB/s"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import checkpoint_spans

    return checkpoint_spans.of(ctx).snapshot_gb_per_s()

"""What the writer thread does with a snapshot: the mean duration of the
whole ``checkpoint_writer.write`` spans in the traced slice (serialize,
hash, put with its fsync and rename, journal: an earlier line gives the
four). Has to stay under the period between two saves, or the queue fills
and the step loop waits in ``checkpoint.enqueue``.
SOURCE: program_span (``harness.checkpoint_spans``)."""

LAYER = "checkpoint"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import checkpoint_spans

    return checkpoint_spans.of(ctx).write_ms()

"""How long a save waited to hand its snapshot to the writer: the
``checkpoint.enqueue`` spans (``queue.put`` on a queue of ``queue_depth``)
of the whole saves in the traced slice, per save. Next to nothing while
the writer keeps up; the writer's lag once it is ``queue_depth`` behind.
SOURCE: program_span (``harness.checkpoint_spans``)."""

LAYER = "checkpoint"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import checkpoint_spans

    return checkpoint_spans.of(ctx).enqueue_wait_ms()

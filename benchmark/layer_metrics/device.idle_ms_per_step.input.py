"""Device idle time per step that lies under the fit loop's input path: the
gaps between device operations (first chip, traced slice) whose innermost
covering span of the program is ``train.data_wait`` (``next()`` of the
stream the loop iterates) or ``prefetch.place`` under it, over the steps
the device ran in the slice.
SOURCE: program_span + device_trace (``harness.program_spans``)."""

LAYER = "device"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import program_spans

    return program_spans.idle_ms_per_step(ctx, program_spans.INPUT,
                                          "prefetch.")

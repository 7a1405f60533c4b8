"""Host time to issue one batch's staging where it happens: the duration
of the program's ``prefetch.place`` span (``DevicePrefetchIterator._place``:
the ``device_put`` / ``shard_batch`` of batch N+1, issued inside the fit
loop's wait for batch N), mean over the batches in the traced slice.
SOURCE: program_span (``harness.program_spans``)."""

LAYER = "fit loops"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import program_spans

    program = program_spans.of(ctx)
    places = program.named("prefetch.place") if program else []
    if not places:
        return None
    return 1000.0 * sum(p.seconds for p in places) / len(places)

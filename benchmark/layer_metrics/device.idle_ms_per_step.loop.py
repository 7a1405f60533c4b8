"""Device idle time per step that lies under the fit loop's own work on a
batch: the gaps between device operations (first chip, traced slice) whose
innermost covering span of the program is ``train.step_host`` or one of its
children (``train.stage`` / ``dispatch`` / ``post`` / ``listeners``,
``checkpoint.*``), over the steps the device ran in the slice.
SOURCE: program_span + device_trace (``harness.program_spans``)."""

LAYER = "device"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import program_spans

    return program_spans.idle_ms_per_step(ctx, program_spans.LOOP,
                                          "checkpoint.")

"""Host work per step outside the jitted call: the duration of the
program's ``train.step_host`` span (the fit loop's own work on the batch
it was handed) minus its ``train.dispatch`` children, mean over the steps
in the traced slice. SOURCE: program_span (``harness.program_spans``)."""

LAYER = "fit loops"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import program_spans

    program = program_spans.of(ctx)
    hosts = program.named("train.step_host") if program else []
    if not hosts:
        return None
    return 1000.0 * sum(h.seconds - h.child_seconds("train.dispatch")
                        for h in hosts) / len(hosts)

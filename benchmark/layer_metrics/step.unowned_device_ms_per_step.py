"""Device time a step spends in operations that NOBODY owns: the tracing's
own coverage, which every PR that adds a layer or a kernel keeps near zero.
An operation is ``unowned`` when neither its instruction's ``op_name``, nor
the instructions of the computations it calls, nor (where it only moves a
value: a copy, a prefetch) what made the value name an owner by the
program's rule (``deeplearning4j_tpu/obs/owners.py`` ``owner_of``), and
``mixed`` when they name several (``harness/owners.py``); what stays on the
chip is the compiler's prefetches of the program's arguments and of a
loop's carried values;
what a ``while`` or a ``conditional`` holds is counted with its own
operations, not with the loop. Union of the operations' own intervals on
the first chip over the steps in the traced slice;
``python benchmark/harness/owners.py .bench_trace/<cell>`` lists them by
instruction and source line. None where the driver kept no HLO text (the
ResNet50 cells) or the program names no owners. SOURCE: device_trace."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import owners

    return owners.ms_per_step(ctx, owners.UNOWNED, owners.MIXED)

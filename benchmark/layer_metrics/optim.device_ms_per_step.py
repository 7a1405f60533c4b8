"""Device time a step spends in the optimizer's operations: those whose
owner, by the program's own rule (``deeplearning4j_tpu/obs/owners.py``
``owner_of``; the scope ``optim.update`` that ``Network._apply_updates``
opens in every step program), is ``optim``, together with the fusions that
XLA built across the optimizer's leaves and gave no ``op_name``
(``harness/owners.py`` follows them into the computation they call). Union
of the operations' own intervals on the first chip over the steps in the
traced slice. None where the driver kept no HLO text (the ResNet50 cells)
or the program names no owners. SOURCE: device_trace."""

LAYER = "fit loops"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import owners

    return owners.ms_per_step(ctx, "optim")

"""Device time a step spends on the two uses of a tied embedding and the
loss between them, in a cell whose chip holds 25,008 rows of the vocabulary
(the gather, the scatter-add of its cotangent, the table transposed for the
head and the ``loss.*`` scopes' one block loop): by
``tiedhead.loss_device_ms_per_step``'s code under a name of its own (that
entry lists the cell it is reported in); nothing where the program has
neither vertex."""

LAYER = "tied head"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    return ctx["cell"].layer_reader("tiedhead.loss_device_ms_per_step")(ctx)

"""Device time a step spends in the selective scan of the ``Mamba1Mixer``
layers (their ``mamba1.scan`` scope: the loop over chunks, a chunk's steps
in order with their 5,120 x 16 exponentials and multiply-adds, the D term;
forward, rematerialised forward and backward, every such layer together):
union of the intervals on the first chip over the steps in the traced slice
(a ``while`` and its body's operations counted once). Nothing where the
program has no such scope."""

LAYER = "state-space mixer"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    return hlo_ops.ms_per_step_under(ctx, "mamba1.scan") or None

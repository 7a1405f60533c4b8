"""Device time a step spends in the chunked state-space scan of the
``Mamba2Mixer`` layers (their ``ssm.scan`` scope: a chunk's C B^T, its
masked decays, the mixing and the two state products, the D term; forward,
rematerialised forward and backward, every such layer together): union of
the intervals on the first chip over the steps in the traced slice (a
``while`` and its body's operations counted once). Nothing where the
program has no such scope."""

LAYER = "state-space mixer"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    return hlo_ops.ms_per_step_under(ctx, "ssm.scan") or None

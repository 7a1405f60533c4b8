"""Roofline share of the chunked delta-rule scan (the ``kda.scan`` scope of
every KDA layer): the least time the chip could take for the operations
and bytes of the FORM THE PROGRAM COMPUTES (``kda_scan_cost`` of the
configuration's reference module: chunks of 64, diagonal blocks of 8
channel by channel, forward substitution over blocks of 8; bytes are what a
kernel that kept a group's terms on the chip would move, q, k, v in
bfloat16, g and the output in float32, NOT the program's own float32
intermediates between XLA's fusions: those are the gap the share shows),
forward, rematerialised forward and backward, over the measured device time
of the operations under the scope. A reading over 100% is a wrong count,
not a result."""

LAYER = "linear attention"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    ref, cfg = ctx["cell"].reference, ctx["cell"].config
    if not view or not hasattr(ref, "kda_scan_cost"):
        return None
    layers = sum(b["attn"] == "kda" for b in ref.blocks(cfg))
    one = ref.kda_scan_cost(cfg, view["tokens_per_step"])
    passes = hlo_ops.training_passes(cfg) * layers
    return hlo_ops.roofline_pct(ctx, "kda.scan", {
        "flops": one["flops"] * passes, "bytes": one["bytes"] * passes})

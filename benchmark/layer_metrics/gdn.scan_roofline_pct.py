"""Roofline share of the chunked delta-rule scan of the Gated DeltaNet
layers (their ``gdn.scan`` scope): the least time the chip could take for
the operations and bytes of the FORM THE PROGRAM COMPUTES
(``gdn_scan_cost`` of the configuration's reference module: the
per-channel chunked scan with the head's one decay spread over its
channels and q, k repeated to the value heads; bytes are what the kernels
move), forward, rematerialised forward and backward, over the measured
device time of the operations under the scope. A reading over 100% is a
wrong count, not a result."""

LAYER = "linear attention"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    ref, cfg = ctx["cell"].reference, ctx["cell"].config
    if not view or not hasattr(ref, "gdn_scan_cost"):
        return None
    layers = sum(b["attn"] == "gdn" for b in ref.blocks(cfg))
    one = ref.gdn_scan_cost(cfg, view["tokens_per_step"])
    passes = hlo_ops.training_passes(cfg) * layers
    return hlo_ops.roofline_pct(ctx, "gdn.scan", {
        "flops": one["flops"] * passes, "bytes": one["bytes"] * passes})

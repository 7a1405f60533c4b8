"""Roofline share of what lies between a short-convolution layer's two
products (the ``sconv.gate_conv`` scope of every ``GatedShortConv`` layer:
the input gate, the depthwise taps, the output gate): the least time the
chip could take for ``gate_conv_cost`` of the configuration's reference
module (B, C, u read and the gated result written forward; those and the
result's cotangent read and three cotangents written backward), the forward
twice where the layers are rematerialised plus the backward, for every
layer that the reference's ``blocks`` list with ``"attn": "conv"``, over
the measured device time under the scope. The bound is bytes. The count is
the algorithm's, whether XLA's fusions or a kernel run it."""

LAYER = "short convolution"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    cell = ctx["cell"]
    ref, cfg = cell.reference, cell.config
    if not view or not hasattr(ref, "gate_conv_cost"):
        return None
    layers = sum(1 for b in ref.blocks(cfg) if b.get("attn") == "conv")
    tokens = view["tokens_per_step"]
    forwards = 2.0 if cfg.get("program", {}).get("remat") else 1.0
    fwd = ref.gate_conv_cost(cfg, tokens)
    bwd = ref.gate_conv_cost(cfg, tokens, backward=True)
    cost = {key: layers * (forwards * fwd[key] + bwd[key])
            for key in ("flops", "bytes")}
    return hlo_ops.roofline_pct(ctx, "sconv.gate_conv", cost)

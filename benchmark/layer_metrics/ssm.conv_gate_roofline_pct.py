"""Roofline share of what lies between a ``Mamba2Mixer`` layer's two wide
products beside the scan (its ``ssm.conv`` scope: the taps, the bias, the
SiLU and the softplus of dt; and its ``ssm.gate_norm`` scope: the gate and
the norm over the inner width): the least time the chip could take for
``conv_gate_cost`` of the configuration's reference module, the forward
twice where the layers are rematerialised plus the backward, for every
layer that the reference's ``blocks`` list with ``"attn": "ssm"``, over the
measured device time under the two scopes. The bound is bytes."""

LAYER = "state-space mixer"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops, layer_scopes

    view = hlo_ops.program_view(ctx)
    cell = ctx["cell"]
    ref, cfg = cell.reference, cell.config
    if not view or not hasattr(ref, "conv_gate_cost"):
        return None
    layers = sum(1 for b in ref.blocks(cfg) if b.get("attn") == "ssm")
    tokens = view["tokens_per_step"]
    forwards = 2.0 if cfg.get("program", {}).get("remat") else 1.0
    fwd = ref.conv_gate_cost(cfg, tokens)
    bwd = ref.conv_gate_cost(cfg, tokens, backward=True)
    cost = {key: layers * (forwards * fwd[key] + bwd[key])
            for key in ("flops", "bytes")}
    return layer_scopes.roofline_pct_where(
        ctx, lambda op: "ssm.conv" in op or "ssm.gate_norm" in op, cost)

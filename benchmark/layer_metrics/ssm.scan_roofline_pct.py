"""Roofline share of the chunked state-space scan of the ``Mamba2Mixer``
layers (their ``ssm.scan`` scope): the least time the chip could take for
``ssd_scan_cost`` of the configuration's reference module (the ALGORITHM's
least at the published chunk: the positions the mask keeps in a chunk's
products, the state products; x, B, C, dt read and y written), the forward
twice where the layers are rematerialised plus the backward, for every
layer that the reference's ``blocks`` list with ``"attn": "ssm"``, over the
measured device time under the scope. The count is the same whether XLA's
fusions or a kernel run the scan. A reading over 100% is a wrong count,
not a result."""

LAYER = "state-space mixer"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    cell = ctx["cell"]
    ref, cfg = cell.reference, cell.config
    if not view or not hasattr(ref, "ssd_scan_cost"):
        return None
    layers = sum(1 for b in ref.blocks(cfg) if b.get("attn") == "ssm")
    sequences = cell.traffic["sequences_per_step"]
    tokens = view["tokens_per_step"] // sequences
    forwards = 2.0 if cfg.get("program", {}).get("remat") else 1.0
    fwd = ref.ssd_scan_cost(cfg, tokens)
    bwd = ref.ssd_scan_cost(cfg, tokens, backward=True)
    cost = {key: layers * sequences * (forwards * fwd[key] + bwd[key])
            for key in ("flops", "bytes")}
    return hlo_ops.roofline_pct(ctx, "ssm.scan", cost)

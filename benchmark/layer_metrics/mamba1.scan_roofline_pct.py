"""Roofline share of the selective scan of the ``Mamba1Mixer`` layers
(their ``mamba1.scan`` scope): the least time the chip could take for
``selective_scan_cost`` of the configuration's reference module (the
ALGORITHM's least whatever runs it: xc, dt, B and C read and y written once
a pass, the state never leaving the chip), the forward twice where the
layers are rematerialised plus the backward (the chunk's steps that the
scan's own rematerialisation runs a third time are the program's cost: in
the time, not in the count), for every layer that the reference's
``blocks`` list with ``"attn": "mamba1"``, over the measured device time
under the scope. The bound is BYTES: the scan's operations are vector
operations and ``harness/peaks.py`` has the matrix unit's peak alone, so a
scan bound by the vector units reads well under 100 here. A reading over
100% is a wrong count, not a result."""

LAYER = "state-space mixer"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    cell = ctx["cell"]
    ref, cfg = cell.reference, cell.config
    if not view or not hasattr(ref, "selective_scan_cost"):
        return None
    layers = sum(1 for b in ref.blocks(cfg) if b.get("attn") == "mamba1")
    sequences = cell.traffic["sequences_per_step"]
    tokens = view["tokens_per_step"] // sequences
    forwards = 2.0 if cfg.get("program", {}).get("remat") else 1.0
    fwd = ref.selective_scan_cost(cfg, tokens)
    bwd = ref.selective_scan_cost(cfg, tokens, backward=True)
    cost = {key: layers * sequences * (forwards * fwd[key] + bwd[key])
            for key in ("flops", "bytes")}
    return hlo_ops.roofline_pct(ctx, "mamba1.scan", cost)

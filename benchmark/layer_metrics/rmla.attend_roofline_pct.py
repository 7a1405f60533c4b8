"""Roofline share of the latent attention's tile pairs (the ``mla.attend``
scope of every ``MultiHeadLatentAttention`` layer, the prediction module's
among them): ``mla_attend_cost(cfg, tokens)`` of the configuration's
reference module a layer (the two products over the (query, key) positions
the causal mask keeps, t + 1 keys a query, every head's 192 / 128 widths),
the forward (twice where the layer is rematerialised) plus a backward of
2.5 forwards (five products a position against two), over the measured
device time under the scope. MXU bound at 8k tokens. A reading over 100%
is a wrong count, not a result."""

LAYER = "latent attention"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    cell = ctx["cell"]
    ref, cfg = cell.reference, cell.config
    if not view or not hasattr(ref, "mla_attend_cost"):
        return None
    layers = sum(b["attn"] == "mla" for b in ref.blocks(cfg))
    sequences = cell.traffic["sequences_per_step"]
    one = ref.mla_attend_cost(cfg, view["tokens_per_step"] // sequences)
    # training_passes takes the backward at two forwards; here it is 2.5
    passes = (hlo_ops.training_passes(cfg) + 0.5) * layers * sequences
    return hlo_ops.roofline_pct(ctx, "mla.attend", {
        "flops": one["flops"] * passes, "bytes": one["bytes"] * passes})

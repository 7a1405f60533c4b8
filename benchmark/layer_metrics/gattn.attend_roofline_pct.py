"""Roofline share of the gated attention's tile pairs (the ``gattn.attend``
scope of every ``GatedAttention`` layer): the least time the chip could
take for the operations and bytes of the FORM THE PROGRAM COMPUTES
(``gattn_attend_cost`` of the configuration's reference module: the causal
triangle's tile pairs for every query head, k and v repeated over their
group and so read once a query head), over the measured device time of the
operations under the scope. The forward makes two products a pair and the
backward five (the scores again, dv, dp, dk, dq), so a training step is
the forward (twice where the layer is rematerialised) plus 2.5 forwards.
The traffic has one sequence a step. A reading over 100% is a wrong count,
not a result."""

LAYER = "gated attention"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    cell = ctx["cell"]
    ref, cfg = cell.reference, cell.config
    if not view or not hasattr(ref, "gattn_attend_cost"):
        return None
    layers = sum(b["attn"] == "gattn" for b in ref.blocks(cfg))
    sequences = cell.traffic["sequences_per_step"]
    one = ref.gattn_attend_cost(cfg, view["tokens_per_step"] // sequences)
    # training_passes takes the backward at two forwards; here it is 2.5
    passes = (hlo_ops.training_passes(cfg) + 0.5) * layers * sequences
    return hlo_ops.roofline_pct(ctx, "gattn.attend", {
        "flops": one["flops"] * passes, "bytes": one["bytes"] * passes})

"""Roofline share of the looped block's attention tile pairs (the
``rattn.attend`` scope of every ``RotaryAttention`` layer): the least time
the chip could take for the operations and bytes of the FORM THE PROGRAM
COMPUTES (``rattn_attend_cost`` of the configuration's reference module:
the causal triangle's tile pairs for every query head, one pass), times
the layers, the passes of the loop (``total_ut_steps``: each is work the
algorithm needs) and a training step's forwards, over the measured device
time of the operations under the scope. The forward makes two products a
pair and the backward five (the scores again, dv, dp, dk, dq), so a
training step is the forward (twice where the layer is rematerialised)
plus 2.5 forwards, as ``gattn.attend_roofline_pct`` counts it. The traffic
has one sequence a step. A reading over 100% is a wrong count, not a
result."""

LAYER = "looped block"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    cell = ctx["cell"]
    ref, cfg = cell.reference, cell.config
    if not view or not hasattr(ref, "rattn_attend_cost"):
        return None
    layers = sum(b["attn"] == "rattn" for b in ref.blocks(cfg))
    sequences = cell.traffic["sequences_per_step"]
    one = ref.rattn_attend_cost(cfg, view["tokens_per_step"] // sequences)
    # training_passes takes the backward at two forwards; here it is 2.5
    passes = ((hlo_ops.training_passes(cfg) + 0.5) * layers
              * cfg["total_ut_steps"] * sequences)
    return hlo_ops.roofline_pct(ctx, "rattn.attend", {
        "flops": one["flops"] * passes, "bytes": one["bytes"] * passes})

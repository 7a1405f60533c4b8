"""Roofline share of the differential attention's tile pairs (the
``dattn.attend`` scope of every ``DifferentialAttention`` layer: window,
whole and cross together): ``attend_cost(cfg, tokens, window)`` of the
reference module a layer (the positions its mask keeps, a 64-wide and a
128-wide product a position and query head for each of the 40; q read and
the output written once a query head, k and v once a key/value head), the
forward twice where rematerialised plus a backward of 2.5 forwards (4.5 a
step), over the measured device time under the scope. What the program
spends there on repeating k over 2 and V over 4 heads, on the layout copies
and on lanes half filled by 64-wide heads is in the time and not in the
cost. MXU bound."""

LAYER = "differential attention"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops, layer_scopes

    view = hlo_ops.program_view(ctx)
    cell = ctx["cell"]
    ref, cfg = cell.reference, cell.config
    if not view or not hasattr(ref, "selective_scan_cost"):
        return None
    blks = [b for b in ref.blocks(cfg)
            if b.get("attn") in ("swa", "full", "cross")]
    sequences = cell.traffic["sequences_per_step"]
    tokens = view["tokens_per_step"] // sequences
    # training_passes takes the backward at two forwards; here it is 2.5
    passes = (hlo_ops.training_passes(cfg) + 0.5) * sequences
    cost = {"flops": 0.0, "bytes": 0.0}
    for b in blks:
        one = ref.attend_cost(cfg, tokens, b["window"])
        for key in cost:
            cost[key] += one[key] * passes
    return layer_scopes.roofline_pct_where(
        ctx, layer_scopes.under("DifferentialAttention",
                                [b["vertex"] for b in blks], "dattn.attend"),
        cost)

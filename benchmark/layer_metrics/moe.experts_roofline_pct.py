"""Roofline share of the grouped expert products (the ``moe.experts`` scope
of every routed layer): the least time the chip could take for the
operations and bytes of the pairs the step REALLY routed to held experts
(the layers' own counters over the TRACED SLICE's steps, read where the
profiler started and stopped: the load drifts inside a window, and the
time is the slice's; ``moe_experts_cost`` of the
configuration's reference module: each used expert's three matrices read
once a pass), forward, rematerialised forward and backward, over the
measured device time of the operations under the scope. At 256 tokens an
expert the bound is bytes: the weights."""

LAYER = "routed experts"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    ref, cfg = ctx["cell"].reference, ctx["cell"].config
    took = (view or {}).get("moe_slice")
    if not took or not took["steps"] \
            or not hasattr(ref, "moe_experts_cost"):
        return None
    steps = took["steps"]
    passes = hlo_ops.training_passes(cfg)
    flops = nbytes = 0.0
    for counts in took["layers"].values():
        used = sum(1 for n in counts["expert_tokens"] if n)
        one = ref.moe_experts_cost(cfg, counts["pairs_held"] / steps, used)
        flops += one["flops"] * passes
        nbytes += one["bytes"] * passes
    return hlo_ops.roofline_pct(ctx, "moe.experts",
                                {"flops": flops, "bytes": nbytes})

"""Device time a step spends under the scopes ``mla.q_lora`` (the query's
two low-rank products and the norm between them) and ``mla.rope`` (the
rotation of every head's rotary widths and of the one shared key) of the
``MultiHeadLatentAttention`` layers, forward, rematerialised forward and
backward: what this configuration adds to the latent layer the Kimi cell
runs. Union of the intervals on the first chip over the traced slice's
steps; nothing where the program has neither scope."""

LAYER = "latent attention"
UNIT = "ms"
MOVES = "train_items_per_s"


def read(ctx):
    from harness import layer_scopes

    return layer_scopes.ms_per_step_where(
        ctx, lambda op: "MultiHeadLatentAttention:" in op
        and ("mla.q_lora" in op or "mla.rope" in op)) or None

"""Share of the routed layers' steps that took the slow tier: a routed
layer whose held pairs pass its first window runs EVERY window
(``nn/conf/experts.py``: ``lax.cond(n_held <= window, first_window,
every_window)``), which lengthens the step by tens of milliseconds a layer,
so two runs of one program differ by it. The layers count such steps in
their ``state`` (``steps_every_window``) and ``obs.watch_moe`` publishes
their sum as ``moe_every_window_steps_total``; this reads it from the
program's registry in the run's own process after the window, over routed
layers x ``train_steps_total``: every step the process trained, set-up's 8
(first steps and warm-up) included. 0 is the fast tier on every step. None
where the model has no routed layer or the program no such counter.
SOURCE: program_counter."""

LAYER = "routed experts"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    from deeplearning4j_tpu.obs import get_registry
    from harness import hlo_ops

    view = hlo_ops.program_view(ctx)
    layers = len((view or {}).get("moe") or ())
    scraped = get_registry().as_dict()
    took = scraped.get("moe_every_window_steps_total")
    steps = scraped.get("train_steps_total", {}).get("value", 0.0)
    if not layers or took is None or not steps:
        return None
    return 100.0 * took["value"] / (layers * steps)

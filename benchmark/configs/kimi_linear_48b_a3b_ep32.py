"""The ``kimi_linear_48b_a3b_ep32`` configuration built from the program's
zoo builder (``models.KimiLinear``), and the views of the built network
that the correctness check needs, keyed like the reference's leaves
(``<vertex>/<param>``)."""

from __future__ import annotations

import dataclasses


def public_config(cfg: dict) -> dict:
    """The keys of the public ``config.json`` as the zoo builder reads
    them: the published counts back in the place of this chip's share."""
    return {**cfg, **cfg["published"]}


def build(cfg: dict, params: dict):
    """``ComputationGraph`` of this chip's share in the configuration's
    compute type, started from the seeded ``params`` (``init(params=)``:
    handed over, not copied, and nothing drawn to be replaced; ``params``
    is emptied)."""
    from deeplearning4j_tpu.models import KimiLinear
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.optimize.updaters import Adam

    upd = cfg["updater"]
    if upd["name"] != "adam":
        raise ValueError(f"this builder knows Adam, not {upd['name']!r}")
    prog = cfg["program"]
    zoo = KimiLinear(public_config(cfg), layers=cfg["num_hidden_layers"],
                     experts_held=cfg["num_experts"],
                     expert_offset=cfg["expert_offset"],
                     vocab_rows=cfg["vocab_size"],
                     kda_low_rank=cfg["kda_low_rank_width"],
                     sequence_length=cfg["sequence_length"],
                     remat=prog["remat"],
                     attention_block=prog["attention_block"],
                     loss_block=prog["loss_block"],
                     updater=Adam(learning_rate=upd["learning_rate"],
                                  beta1=upd["beta1"], beta2=upd["beta2"],
                                  epsilon=upd["epsilon"]))
    conf = dataclasses.replace(zoo.conf(), dtype=cfg["compute_dtype"])
    nested = {}
    for name in list(params):
        vertex, key = name.split("/")
        nested.setdefault(vertex, {})[key] = params.pop(name)
    return ComputationGraph(conf).init(params=nested)


def params_flat(net) -> dict:
    return {f"{v}/{k}": a for v, leaves in net.params.items()
            for k, a in leaves.items()}


def first_moment_flat(net) -> dict:
    """Adam's first moment, leaf by leaf; after exactly one step it is
    (1 - beta1) times the gradient the optimiser got."""
    import optax

    if net.iteration != 1:
        raise ValueError(f"needs the state after one step, not "
                         f"{net.iteration}")
    out = {}
    for v, leaves in net.params.items():
        if not leaves:
            continue
        mu = optax.tree_utils.tree_get(net.opt_state[v], "mu")
        for k in leaves:
            out[f"{v}/{k}"] = mu[k]
    return out


def moe_counters(net) -> dict:
    """The routed layers' load counters out of the network's state, as
    host numbers: {vertex: {"expert_tokens": [...], "pairs_held": n,
    "pairs_dropped": n}}. One fetch; not for the step path."""
    import numpy as np

    out = {}
    for v, st in net.state.items():
        if "expert_tokens" in st:
            out[v] = {"expert_tokens": np.asarray(st["expert_tokens"])
                      .astype(int).tolist(),
                      "pairs_held": int(st["pairs_held"]),
                      "pairs_dropped": int(st["pairs_dropped"])}
    return out

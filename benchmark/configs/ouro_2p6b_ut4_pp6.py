"""The ``ouro_2p6b_ut4_pp6`` configuration built from the program's zoo
builder (``models.Ouro``), and the views of the built network that the
correctness check needs, keyed like the reference's leaves: the path of a
leaf in ``net.params`` joined by ``/`` (``loop/l3_attn/attn/Wq``: the
looped block's leaves lie nested under its vertex, once)."""

from __future__ import annotations

import dataclasses

# at import, so that a program without the builder (this PR's parent) fails
# when the cell is resolved, before any device is touched
from deeplearning4j_tpu.models import Ouro


def public_config(cfg: dict) -> dict:
    """The keys of the public ``config.json`` as the zoo builder reads
    them: the published depth back in the place of this stage's."""
    return {**cfg, **cfg["published"]}


def flat(tree: dict, prefix: str = "") -> dict:
    """A nested dict of leaves keyed by their paths joined by ``/``."""
    out = {}
    for key, node in tree.items():
        if isinstance(node, dict):
            out.update(flat(node, f"{prefix}{key}/"))
        else:
            out[prefix + key] = node
    return out


def build(cfg: dict, params: dict):
    """``ComputationGraph`` of this stage in the configuration's compute
    type, started from the seeded ``params`` (``init(params=)``: handed
    over, not copied, and nothing drawn to be replaced; ``params`` is
    emptied)."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.optimize.updaters import Adam

    upd = cfg["updater"]
    if upd["name"] != "adam":
        raise ValueError(f"this builder knows Adam, not {upd['name']!r}")
    prog = cfg["program"]
    zoo = Ouro(public_config(cfg), layers=cfg["num_hidden_layers"],
               vocab_rows=cfg["vocab_size"],
               sequence_length=cfg["sequence_length"], remat=prog["remat"],
               attention_block=prog["attention_block"],
               loss_block=prog["loss_block"],
               entropy_weight=cfg["entropy_weight"],
               updater=Adam(learning_rate=upd["learning_rate"],
                            beta1=upd["beta1"], beta2=upd["beta2"],
                            epsilon=upd["epsilon"]))
    conf = dataclasses.replace(zoo.conf(), dtype=cfg["compute_dtype"])
    nested = {}
    for name in list(params):
        *path, leaf = name.split("/")
        node = nested
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = params.pop(name)
    return ComputationGraph(conf).init(params=nested)


def params_flat(net) -> dict:
    return flat(net.params)


def first_moment_flat(net) -> dict:
    """Adam's first moment, leaf by leaf; after exactly one step it is
    (1 - beta1) times the gradient the optimiser got: for the looped
    block's leaves, the sum over the passes."""
    import optax

    if net.iteration != 1:
        raise ValueError(f"needs the state after one step, not "
                         f"{net.iteration}")
    return flat({v: optax.tree_utils.tree_get(net.opt_state[v], "mu")
                  for v, leaves in net.params.items() if leaves})


def moe_counters(net) -> dict:
    """No routed layer: the driver's ``failed`` rests on a finite loss."""
    return {}

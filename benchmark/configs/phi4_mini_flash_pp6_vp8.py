"""The ``phi4_mini_flash_pp6_vp8`` configuration built from the program's
zoo builder (``models.Phi4Flash``). The views of the built network that the
correctness check needs (``params_flat``, ``first_moment_flat``,
``moe_counters``, keyed like the reference's leaves, ``<vertex>/<param>``)
are those of any ``ComputationGraph``: the Kimi configuration's, taken from
its file (this model has no routed layer, so ``moe_counters`` is empty).
The tied head owns no leaf, so both views hold the ONE ``embed/W``, as the
reference does."""

from __future__ import annotations

import dataclasses
import os

# at import, so that a program without the builder (this PR's parent) fails
# when the cell is resolved, before any device is touched
from deeplearning4j_tpu.models import Phi4Flash
from harness import loader

_views = loader.import_file(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "kimi_linear_48b_a3b_ep32.py"), "config")
public_config = _views.public_config
params_flat = _views.params_flat
first_moment_flat = _views.first_moment_flat
moe_counters = _views.moe_counters


def build(cfg: dict, params: dict):
    """``ComputationGraph`` of this chip's share in the configuration's
    compute type, started from the seeded ``params`` (``init(params=)``:
    handed over, not copied, and nothing drawn to be replaced; ``params``
    is emptied)."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.optimize.updaters import Adam

    upd = cfg["updater"]
    if upd["name"] != "adam":
        raise ValueError(f"this builder knows Adam, not {upd['name']!r}")
    prog = cfg["program"]
    zoo = Phi4Flash(public_config(cfg), layer_indices=cfg["layer_indices"],
                    vocab_rows=cfg["vocab_size"],
                    sequence_length=cfg["sequence_length"],
                    remat=prog["remat"],
                    attention_block=prog["attention_block"],
                    loss_block=prog["loss_block"],
                    scan_chunk=prog["scan_chunk"],
                    updater=Adam(learning_rate=upd["learning_rate"],
                                 beta1=upd["beta1"], beta2=upd["beta2"],
                                 epsilon=upd["epsilon"]))
    conf = dataclasses.replace(zoo.conf(), dtype=cfg["compute_dtype"])
    nested = {}
    for name in list(params):
        vertex, key = name.split("/")
        nested.setdefault(vertex, {})[key] = params.pop(name)
    return ComputationGraph(conf).init(params=nested)

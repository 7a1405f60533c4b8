"""The ``resnet50_imagenet_bf16`` configuration built from the program's
public builders, and the three views of the built network that the
correctness check needs, keyed like the reference's leaves
(``<vertex>/<param>``)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def build(cfg: dict, params: dict):
    """``ComputationGraph`` of the zoo's ResNet50 in the configuration's
    compute type, holding copies of the seeded ``params``."""
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.optimize.updaters import Adam

    upd = cfg["updater"]
    if upd["name"] != "adam":
        raise ValueError(f"this builder knows Adam, not {upd['name']!r}")
    zoo = ResNet50(num_classes=cfg["num_classes"],
                   input_shape=tuple(cfg["input_shape"]),
                   updater=Adam(learning_rate=upd["learning_rate"],
                                beta1=upd["beta1"], beta2=upd["beta2"],
                                epsilon=upd["epsilon"]))
    conf = dataclasses.replace(zoo.conf(), dtype=cfg["compute_dtype"])
    net = ComputationGraph(conf).init()
    install(net, params)
    return net


def install(net, params: dict) -> None:
    """Replace the network's own initial weights by copies of ``params``
    (copies: the train step donates its arguments)."""
    have = {f"{v}/{k}": a.shape for v, leaves in net.params.items()
            for k, a in leaves.items()}
    want = {k: tuple(a.shape) for k, a in params.items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise ValueError(f"seeded weights do not fit the network: {odd}")
    fresh = {v: dict(leaves) for v, leaves in net.params.items()}
    for name, a in params.items():
        vertex, key = name.split("/")
        fresh[vertex][key] = jnp.array(a, copy=True)
    net.params = fresh


def params_flat(net) -> dict:
    return {f"{v}/{k}": a for v, leaves in net.params.items()
            for k, a in leaves.items()}


def first_gradient_flat(net, cfg: dict) -> dict:
    """The gradient as the optimiser got it in the first step, worked out
    from its state after exactly one step: Adam's first moment is
    (1 - beta1) * g then."""
    import optax

    if net.iteration != 1:
        raise ValueError(f"needs the state after one step, not "
                         f"{net.iteration}")
    scale = 1.0 / (1.0 - cfg["updater"]["beta1"])
    out = {}
    for v, leaves in net.params.items():
        if not leaves:
            continue
        mu = optax.tree_utils.tree_get(net.opt_state[v], "mu")
        for k in leaves:
            out[f"{v}/{k}"] = mu[k] * scale
    return out

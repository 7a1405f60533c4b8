"""The ``charrnn_textgen_lstm`` configuration built from the program's
public builders, and the views of the built network that the correctness
check needs, keyed like the reference's leaves (``<layer index>/<param>``)."""

from __future__ import annotations

import jax.numpy as jnp


def build(cfg: dict, params: dict):
    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.optimize.updaters import RmsProp

    upd = cfg["updater"]
    if upd["name"] != "rmsprop" or cfg["lstm_layers"] != 2:
        raise ValueError("this builder knows the zoo's two-layer RmsProp "
                         "model only")
    if cfg["compute_dtype"] != "float32":
        raise ValueError("the zoo model computes in float32")
    net = TextGenerationLSTM(
        total_unique_characters=cfg["vocab_size"], units=cfg["units"],
        tbptt_length=cfg["tbptt_length"],
        updater=RmsProp(learning_rate=upd["learning_rate"],
                        rms_decay=upd["rms_decay"],
                        epsilon=upd["epsilon"])).init()
    install(net, params)
    return net


def install(net, params: dict) -> None:
    have = {f"{i}/{k}": tuple(a.shape) for i, leaves in enumerate(net.params)
            for k, a in leaves.items()}
    want = {k: tuple(a.shape) for k, a in params.items()}
    if have != want:
        odd = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise ValueError(f"seeded weights do not fit the network: {odd}")
    fresh = [dict(leaves) for leaves in net.params]
    for name, a in params.items():
        i, key = name.split("/")
        fresh[int(i)][key] = jnp.array(a, copy=True)
    net.params = fresh


def params_flat(net) -> dict:
    return {f"{i}/{k}": a for i, leaves in enumerate(net.params)
            for k, a in leaves.items()}


def first_gradient_flat(net, cfg: dict) -> dict:
    """The gradient as RmsProp has it after the first fused dispatch:
    sqrt(nu), leaf by leaf (a decayed mean of squares over that dispatch's
    windows; the dispatch does not give out its single gradients)."""
    import optax

    out = {}
    for i, leaves in enumerate(net.params):
        if not leaves:
            continue
        nu = optax.tree_utils.tree_get(net.opt_state[i], "nu")
        for k in leaves:
            out[f"{i}/{k}"] = jnp.sqrt(nu[k])
    return out

"""Operations a model's algorithm needs, from its shapes alone. One
multiply-accumulate is 2 FLOPs. Only convolutions and matrix products
count: elementwise work, normalisation and pooling are left out, as is
recomputation, so a utilisation built on these is a model FLOP/s
utilisation. Training is forward + input gradient + weight gradient =
3 x forward (the first layer's unused input gradient is counted too, the
usual convention).

A configuration's reference module lists its layers as dicts (``layers``)
and these functions add them up."""

from __future__ import annotations

from typing import Iterable


def conv2d_flops(h_out: int, w_out: int, kh: int, kw: int, cin: int,
                 cout: int) -> float:
    return 2.0 * h_out * w_out * kh * kw * cin * cout


def dense_flops(n_in: int, n_out: int, positions: int = 1) -> float:
    return 2.0 * n_in * n_out * positions


def lstm_flops(n_in: int, units: int, steps: int = 1) -> float:
    """Input and recurrent projections of the four gates per time step;
    peepholes and gate nonlinearities are elementwise and not counted."""
    return 2.0 * (n_in + units) * 4 * units * steps


def layer_flops(layer: dict) -> float:
    kind = layer["kind"]
    if kind == "conv2d":
        return conv2d_flops(layer["h_out"], layer["w_out"], layer["kh"],
                            layer["kw"], layer["cin"], layer["cout"])
    if kind == "dense":
        return dense_flops(layer["n_in"], layer["n_out"],
                           layer.get("positions", 1))
    if kind == "lstm":
        return lstm_flops(layer["n_in"], layer["units"],
                          layer.get("steps", 1))
    raise KeyError(f"no FLOP count for layer kind {kind!r}")


def forward_flops_per_item(layers: Iterable[dict]) -> float:
    return float(sum(layer_flops(layer) for layer in layers))


def train_flops_per_item(layers: Iterable[dict]) -> float:
    return 3.0 * forward_flops_per_item(layers)

"""Input types for shape inference.

Parity surface: reference ``nn/conf/inputs/InputType.java`` — the declarative
shape-inference system used by ``MultiLayerConfiguration``/
``ComputationGraphConfiguration`` to wire n_in automatically and to insert
input preprocessors between layer families.

TPU-first convention: convolutional activations are **NHWC** (batch, height,
width, channels) — the layout XLA:TPU tiles best — instead of DL4J's NCHW;
recurrent activations are (batch, time, size) instead of DL4J's (batch, size,
time).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class InputType:
    kind: str  # "ff" | "rnn" | "cnn" | "cnn_flat" | "cnn1d"
    size: int = 0  # ff/rnn feature size; cnn1d channels
    height: int = 0
    width: int = 0
    channels: int = 0
    timeseries_length: Optional[int] = None
    # a ``LoopVertex``'s stacked output (or a ``StackStatesVertex``'s): this
    # many passes on a new leading axis, in front of the batch; the other
    # fields describe one pass
    passes: Optional[int] = None

    # ---- factories (InputType.feedForward etc. in the reference) ----
    @staticmethod
    def feed_forward(size: int) -> "InputType":
        return InputType("ff", size=size)

    @staticmethod
    def recurrent(size: int, timeseries_length: Optional[int] = None) -> "InputType":
        return InputType("rnn", size=size, timeseries_length=timeseries_length)

    @staticmethod
    def convolutional(height: int, width: int, channels: int) -> "InputType":
        return InputType("cnn", height=height, width=width, channels=channels)

    @staticmethod
    def convolutional_flat(height: int, width: int, channels: int) -> "InputType":
        return InputType("cnn_flat", height=height, width=width, channels=channels)

    @staticmethod
    def recurrent1d(channels: int, length: Optional[int] = None) -> "InputType":
        return InputType("cnn1d", size=channels, timeseries_length=length)

    # ---- helpers ----
    def flat_size(self) -> int:
        if self.kind == "ff":
            return self.size
        if self.kind in ("cnn", "cnn_flat"):
            return self.height * self.width * self.channels
        if self.kind == "rnn":
            return self.size
        if self.kind == "cnn1d":
            return self.size
        raise ValueError(self.kind)

    def example_shape(self, batch: int = 1) -> Tuple[int, ...]:
        """Concrete array shape for one batch of this input type."""
        if self.passes:
            one = dataclasses.replace(self, passes=None)
            return (self.passes,) + one.example_shape(batch)
        if self.kind in ("ff", "cnn_flat"):
            return (batch, self.flat_size())
        if self.kind == "rnn":
            t = self.timeseries_length or 1
            return (batch, t, self.size)
        if self.kind == "cnn":
            return (batch, self.height, self.width, self.channels)
        if self.kind == "cnn1d":
            t = self.timeseries_length or 1
            return (batch, t, self.size)
        raise ValueError(self.kind)

    def to_dict(self):
        return {k: v for k, v in dataclasses.asdict(self).items() if v not in (None,)}

    @staticmethod
    def from_dict(d):
        return InputType(**d)

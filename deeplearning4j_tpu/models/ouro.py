"""Ouro (looped language models) as a ComputationGraph, from the keys of
its public ``config.json`` (``model_type`` ``ouro``; e.g.
ByteDance/Ouro-2.6B; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741).

Not in the reference zoo. A decoder whose whole stack of layers is run
``total_ut_steps`` times over ONE set of weights (``LoopVertex``). With
``x_0`` the embedding, pass r = 1..R is

    h = x_{r-1};  for every layer:  h = h + N2(Attn(N1(h))),
                                    h = h + N4(MLP(N3(h)));   x_r = N_f(h)

(sandwich norms: four ``RMSNorm`` a layer with a plain weight; ``Attn`` is
``RotaryAttention`` over the whole head width, ``MLP`` a SwiGLU
``GatedFeedForward``; the final norm closes every pass and the next pass
starts from its output). Every pass is read by the head (tied to the
embedding where ``tie_word_embeddings``) and by an exit gate, ``lambda_r = sigmoid(x_r w_g + b_g)``, and the training loss is
the expectation of the passes' cross-entropies under the gates' exit
distribution less ``entropy_weight`` times its entropy
(``ExitWeightedTokenOutputLayer``). Input: (batch, time) integer ids;
labels: the next ids, as integers. ``config.json`` carries
``total_ut_steps`` and ``early_exit_threshold`` only: the sandwich norms,
the gate's place after the norm and the objective are the paper's and the
public model code's. Not built: the later training stage that fits the gate
alone against detached losses, and leaving early at inference
(``early_exit_threshold``): ``output`` is the last pass's.

What one chip holds of a larger deployment is given as arguments, not in
the config: ``layers`` (how many leading layers to keep: a pipeline
stage's block, looped on its own) and ``vocab_rows``. Vertex names:
``embed``, ``loop`` (body: ``l<i>_attn`` and ``l<i>_ffn``, i from 0, each a
one-pass ``LoopVertex`` of ``pre``, ``attn`` | ``ffn``, ``post``, ``add``
that is rematerialised as a unit, so that a pass keeps two arrays a layer;
then ``final_norm``), ``head``."""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.models.zoo import ZooModel
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward
from deeplearning4j_tpu.nn.conf.graph import (ElementWiseVertex, GraphBuilder,
                                              LoopVertex)
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (
    EmbeddingSequenceLayer, ExitWeightedTokenOutputLayer)
from deeplearning4j_tpu.optimize.updaters import Adam


class Ouro(ZooModel):
    def __init__(self, config: dict, layers: Optional[int] = None,
                 vocab_rows: Optional[int] = None,
                 sequence_length: Optional[int] = None,
                 remat: Optional[str] = "full",
                 attention_block: int = 512, loss_block: int = 1024,
                 entropy_weight: float = 0.05, seed: int = 12345,
                 updater=None):
        vocab = vocab_rows or config["vocab_size"]
        super().__init__(vocab, seed)
        if config.get("rope_scaling") or config.get("use_sliding_window"):
            raise NotImplementedError(
                "scaled rotation and a sliding window are not built")
        self.config = config
        self.layers = layers or config["num_hidden_layers"]
        self.sequence_length = sequence_length
        self.remat = remat
        self.attention_block = attention_block
        self.loss_block = loss_block
        self.entropy_weight = entropy_weight
        self.updater = updater or Adam(learning_rate=1e-3)

    def _sandwich(self, parent, name: str, inner) -> LoopVertex:
        """``x + post(inner(pre(x)))`` as one vertex, rematerialised whole."""
        c = self.config
        b = GraphBuilder(parent)
        b.add_inputs("x")
        b.add_layer("pre", RMSNorm(eps=c["rms_norm_eps"]), "x")
        b.add_layer(name, inner, "pre")
        b.add_layer("post", RMSNorm(eps=c["rms_norm_eps"]), name)
        b.add_vertex("add", ElementWiseVertex(op="add"), "x", "post")
        b.set_outputs("add")
        b.set_input_types(InputType.recurrent(c["hidden_size"],
                                              self.sequence_length))
        return LoopVertex(body=b.build(), steps=1, stacked=False,
                          remat=self.remat)

    def conf(self):
        from deeplearning4j_tpu.nn.conf.network import Builder as NNBuilder
        c = self.config
        d = c["hidden_size"]
        hidden = InputType.recurrent(d, self.sequence_length)
        parent = NNBuilder()
        parent.seed(self.seed).updater(self.updater)

        block = GraphBuilder(parent)
        block.add_inputs("h")
        x = "h"
        for i in range(self.layers):
            block.add_layer(f"l{i}_attn", self._sandwich(
                parent, "attn", RotaryAttention(
                    n_heads=c["num_attention_heads"],
                    n_kv_heads=c["num_key_value_heads"],
                    head_dim=c["head_dim"], rope_theta=float(c["rope_theta"]),
                    block=self.attention_block)), x)
            block.add_layer(f"l{i}_ffn", self._sandwich(
                parent, "ffn", GatedFeedForward(
                    ff_size=c["intermediate_size"])), f"l{i}_attn")
            x = f"l{i}_ffn"
        block.add_layer("final_norm", RMSNorm(eps=c["rms_norm_eps"],
                                              remat=self.remat), x)
        block.set_outputs("final_norm")
        block.set_input_types(hidden)

        g = GraphBuilder(parent)
        g.add_inputs("ids")
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_in=self.num_classes, n_out=d, weight_init="normal"), "ids")
        g.add_layer("loop", LoopVertex(body=block.build(),
                                       steps=c["total_ut_steps"]), "embed")
        g.add_layer("head", ExitWeightedTokenOutputLayer(
            n_out=self.num_classes, time_block=self.loss_block,
            entropy_weight=self.entropy_weight,
            weight_init="xavier_fan_in",
            tied_to="embed" if c.get("tie_word_embeddings") else ""), "loop")
        g.set_outputs("head")
        g.set_input_types(InputType.recurrent(self.num_classes,
                                              self.sequence_length))
        return g.build()

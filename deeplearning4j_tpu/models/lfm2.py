"""LFM2-MoE as a ComputationGraph, from the keys of its public
``config.json`` (``model_type`` ``lfm2_moe``; e.g. LiquidAI/LFM2-8B-A1B).

Not in the reference zoo. A decoder of pre-norm blocks,
``h = x + Op(norm(x))``, ``y = h + FFN(norm(h))``, a final norm and a head
tied to the embedding; every norm is a plain ``RMSNorm`` at ``norm_eps``.
With i the layer's index from 0, ``layer_types[i]`` picks the token
mixer's CLASS: ``"conv"`` is ``GatedShortConv`` (two gates around a
depthwise causal convolution of ``conv_L_cache`` taps, no activation),
``"full_attention"`` is ``RotaryAttention`` over grouped heads of
``hidden_size / num_attention_heads`` widths with per-head q/k norms, all
widths rotated half-split at ``rope_theta``. ``FFN`` is a dense SwiGLU of
``intermediate_size`` in the first ``num_dense_layers`` layers and routed
experts after them: a sigmoid router with a selection bias
(``use_expert_bias``), the top ``num_experts_per_tok`` renormalised
(``norm_topk_prob``) and scaled by ``routed_scaling_factor``, SwiGLU experts
of ``moe_intermediate_size``, no shared expert. Input: (batch, time) integer
ids; labels: the next ids, as integers (``TokenOutputLayer``).

What the public keys do not say is a value, so that a reader who knows
otherwise changes it and no code: the key ``tie_word_embeddings`` (tied
where the config has none: the published parameter count is the tied one)
and the argument ``renorm_eps`` (what the public code adds to the chosen
scores' sum before dividing by it).

What one chip holds of a larger deployment is given as arguments, not in
the config: ``layers`` (how many leading layers to keep), ``experts_held``
and ``expert_offset`` (this chip's experts; the router keeps its published
width), ``vocab_rows`` (this chip's slice of the vocabulary). Vertex names:
``embed``, ``l<i>_op_norm``, ``l<i>_conv`` or ``l<i>_attn``, ``l<i>_op_add``,
``l<i>_ffn_norm``, ``l<i>_ffn``, ``l<i>_ffn_add`` (i from 0, the published
index), ``final_norm``, ``head``."""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.models.zoo import ZooModel
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward, RoutedExperts
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex, GraphBuilder
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.nn.conf.short_conv import GatedShortConv
from deeplearning4j_tpu.optimize.updaters import Adam


class Lfm2Moe(ZooModel):
    def __init__(self, config: dict, layers: Optional[int] = None,
                 experts_held: Optional[int] = None, expert_offset: int = 0,
                 vocab_rows: Optional[int] = None,
                 sequence_length: Optional[int] = None,
                 remat: Optional[str] = "full",
                 attention_block: int = 512, loss_block: int = 1024,
                 renorm_eps: float = 1e-6,
                 seed: int = 12345, updater=None):
        vocab = vocab_rows or config["vocab_size"]
        super().__init__(vocab, seed)
        c = config
        if c.get("conv_bias"):
            raise NotImplementedError("biases on the short convolution")
        if not c["norm_topk_prob"]:
            raise NotImplementedError("only the renormalised top-k")
        if not c["use_expert_bias"]:
            raise NotImplementedError("a router without its selection bias")
        odd = sorted(set(c["layer_types"]) - {"conv", "full_attention"})
        if odd:
            raise NotImplementedError(f"layer types {odd}")
        self.config = config
        self.layers = layers or c["num_hidden_layers"]
        self.experts_held = experts_held or c["num_experts"]
        self.expert_offset = expert_offset
        self.sequence_length = sequence_length
        self.remat = remat
        self.attention_block = attention_block
        self.loss_block = loss_block
        self.renorm_eps = renorm_eps
        self.updater = updater or Adam(learning_rate=1e-3)

    def _operator(self, index: int):
        """(vertex suffix, the token mixer) of layer ``index``."""
        c = self.config
        if c["layer_types"][index] == "conv":
            return "_conv", GatedShortConv(taps=c["conv_L_cache"],
                                           remat=self.remat)
        heads = c["num_attention_heads"]
        return "_attn", RotaryAttention(
            n_heads=heads, n_kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // heads,
            rope_theta=float(c["rope_theta"]), qk_norm=True,
            eps=c["norm_eps"], block=self.attention_block, remat=self.remat)

    def _feed_forward(self, index: int):
        c = self.config
        if index < c["num_dense_layers"]:
            return GatedFeedForward(ff_size=c["intermediate_size"],
                                    remat=self.remat)
        # no layer-level remat: the layer checkpoints its own windows of
        # sorted slots (see models/kimi_linear.py)
        return RoutedExperts(
            n_experts=c["num_experts"], experts_held=self.experts_held,
            expert_offset=self.expert_offset, top_k=c["num_experts_per_tok"],
            expert_size=c["moe_intermediate_size"], shared_size=0,
            router_activation="sigmoid",
            scaling=float(c["routed_scaling_factor"]),
            renorm_eps=self.renorm_eps)

    def conf(self):
        from deeplearning4j_tpu.nn.conf.network import Builder as NNBuilder
        c = self.config
        d = c["hidden_size"]

        def norm():
            return RMSNorm(eps=c["norm_eps"])

        parent = NNBuilder()
        parent.seed(self.seed).updater(self.updater)
        g = GraphBuilder(parent)
        g.add_inputs("ids")
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_in=self.num_classes, n_out=d, weight_init="normal"), "ids")
        x = "embed"
        for i in range(self.layers):
            n = f"l{i}"
            suffix, operator = self._operator(i)
            g.add_layer(n + "_op_norm", norm(), x)
            g.add_layer(n + suffix, operator, n + "_op_norm")
            g.add_vertex(n + "_op_add", ElementWiseVertex(op="add"), x,
                         n + suffix)
            g.add_layer(n + "_ffn_norm", norm(), n + "_op_add")
            g.add_layer(n + "_ffn", self._feed_forward(i), n + "_ffn_norm")
            g.add_vertex(n + "_ffn_add", ElementWiseVertex(op="add"),
                         n + "_op_add", n + "_ffn")
            x = n + "_ffn_add"
        g.add_layer("final_norm", norm(), x)
        g.add_layer("head", TokenOutputLayer(
            n_out=self.num_classes, time_block=self.loss_block,
            weight_init="xavier_fan_in",
            tied_to="embed" if c.get("tie_word_embeddings", True) else ""),
            "final_norm")
        g.set_outputs("head")
        g.set_input_types(InputType.recurrent(self.num_classes,
                                              self.sequence_length))
        return g.build()

"""Qwen3-Next as a ComputationGraph, from the keys of its public
``config.json`` (``model_type`` ``qwen3_next``; e.g.
Qwen/Qwen3-Next-80B-A3B-Instruct).

Not in the reference zoo. A decoder of pre-norm blocks,
``h = x + Mixer(norm(x))``, ``y = h + FFN(norm(h))``, a final norm and an
untied head; every norm scales by ``1 + w`` (``RMSNorm(zero_centered=
True)``). With i the layer's index from 0, ``Mixer`` is gated
grouped-query attention with a partial rotary embedding where
``(i + 1) % full_attention_interval == 0`` and Gated DeltaNet otherwise;
``FFN`` is routed experts (softmax router, top-k renormalised) plus one
shared expert behind a sigmoid gate in every layer that
``decoder_sparse_step`` and ``mlp_only_layers`` make sparse, and a dense
SwiGLU of ``intermediate_size`` in the others. Input: (batch, time)
integer ids; labels: the next ids, as integers (``TokenOutputLayer``).
The checkpoint's multi-token-prediction block is not in ``config.json``
and is not built.

What one chip holds of a larger deployment is given as arguments, not in
the config: ``layers`` (how many leading layers to keep), ``experts_held``
and ``expert_offset`` (this chip's experts; the router keeps its published
width), ``vocab_rows`` (this chip's slice of the vocabulary). Vertex names:
``embed``, ``l<i>_attn_norm``, ``l<i>_attn``, ``l<i>_attn_add``,
``l<i>_ffn_norm``, ``l<i>_ffn``, ``l<i>_ffn_add`` (i from 0, the published
index), ``final_norm``, ``head``."""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.models.zoo import ZooModel
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import GatedAttention
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward, RoutedExperts
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex, GraphBuilder
from deeplearning4j_tpu.nn.conf.linear_attention import GatedDeltaNet
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.optimize.updaters import Adam


class Qwen3Next(ZooModel):
    def __init__(self, config: dict, layers: Optional[int] = None,
                 experts_held: Optional[int] = None, expert_offset: int = 0,
                 vocab_rows: Optional[int] = None,
                 sequence_length: Optional[int] = None,
                 remat: Optional[str] = "full",
                 attention_block: int = 512, loss_block: int = 1024,
                 seed: int = 12345, updater=None):
        vocab = vocab_rows or config["vocab_size"]
        super().__init__(vocab, seed)
        self.config = config
        self.layers = layers or config["num_hidden_layers"]
        self.experts_held = experts_held or config["num_experts"]
        self.expert_offset = expert_offset
        self.sequence_length = sequence_length
        self.remat = remat
        self.attention_block = attention_block
        self.loss_block = loss_block
        self.updater = updater or Adam(learning_rate=1e-3)

    def _mixer(self, index: int):
        c = self.config
        if (index + 1) % c["full_attention_interval"] == 0:
            if c.get("rope_scaling") or c.get("use_sliding_window"):
                raise NotImplementedError(
                    "scaled rotation and a sliding window are not built")
            return GatedAttention(
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                rotary_dim=int(c["head_dim"] * c["partial_rotary_factor"]),
                rope_theta=float(c["rope_theta"]), block=self.attention_block,
                eps=c["rms_norm_eps"], remat=self.remat)
        if c["linear_key_head_dim"] != c["linear_value_head_dim"]:
            raise NotImplementedError(
                "Gated DeltaNet with key and value heads of different widths")
        return GatedDeltaNet(
            n_key_heads=c["linear_num_key_heads"],
            n_value_heads=c["linear_num_value_heads"],
            head_dim=c["linear_key_head_dim"],
            conv_size=c["linear_conv_kernel_dim"], eps=c["rms_norm_eps"],
            remat=self.remat)

    def _feed_forward(self, index: int):
        c = self.config
        sparse = (index not in c["mlp_only_layers"] and c["num_experts"] > 0
                  and (index + 1) % c["decoder_sparse_step"] == 0)
        if not sparse:
            return GatedFeedForward(ff_size=c["intermediate_size"],
                                    remat=self.remat)
        if not c["norm_topk_prob"]:
            raise NotImplementedError("only the renormalised top-k")
        # no layer-level remat: the layer checkpoints its own windows of
        # sorted slots (see models/kimi_linear.py)
        return RoutedExperts(
            n_experts=c["num_experts"], experts_held=self.experts_held,
            expert_offset=self.expert_offset, top_k=c["num_experts_per_tok"],
            expert_size=c["moe_intermediate_size"],
            shared_size=c["shared_expert_intermediate_size"],
            router_activation="softmax", shared_gate=True)

    def conf(self):
        from deeplearning4j_tpu.nn.conf.network import Builder as NNBuilder
        c = self.config
        d = c["hidden_size"]

        def norm():
            return RMSNorm(eps=c["rms_norm_eps"], zero_centered=True)

        parent = NNBuilder()
        parent.seed(self.seed).updater(self.updater)
        g = GraphBuilder(parent)
        g.add_inputs("ids")
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_in=self.num_classes, n_out=d, weight_init="normal"), "ids")
        x = "embed"
        for i in range(self.layers):
            n = f"l{i}"
            g.add_layer(n + "_attn_norm", norm(), x)
            g.add_layer(n + "_attn", self._mixer(i), n + "_attn_norm")
            g.add_vertex(n + "_attn_add", ElementWiseVertex(op="add"), x,
                         n + "_attn")
            g.add_layer(n + "_ffn_norm", norm(), n + "_attn_add")
            g.add_layer(n + "_ffn", self._feed_forward(i), n + "_ffn_norm")
            g.add_vertex(n + "_ffn_add", ElementWiseVertex(op="add"),
                         n + "_attn_add", n + "_ffn")
            x = n + "_ffn_add"
        g.add_layer("final_norm", norm(), x)
        g.add_layer("head", TokenOutputLayer(
            n_out=self.num_classes, time_block=self.loss_block,
            weight_init="xavier_fan_in"), "final_norm")
        g.set_outputs("head")
        g.set_input_types(InputType.recurrent(self.num_classes,
                                              self.sequence_length))
        return g.build()

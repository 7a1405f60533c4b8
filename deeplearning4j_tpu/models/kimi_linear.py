"""Kimi Linear as a ComputationGraph, from the keys of its public
``config.json`` (``model_type`` ``kimi_linear``; Kimi Linear,
arXiv:2510.26692; e.g. moonshotai/Kimi-Linear-48B-A3B-Instruct).

Not in the reference zoo. A decoder of pre-norm blocks,
``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, a final RMSNorm
and an untied head. ``Attn`` is Kimi Delta Attention in the layers that
``linear_attn_config.kda_layers`` names and latent attention in its
``full_attn_layers`` (without rotation under ``mla_use_nope``, else turned
in the interleaved pairing at ``rope_theta``; with a low-rank query where
``q_lora_rank`` is set); ``FFN`` is a dense SwiGLU in the
first ``first_k_dense_replace`` layers and routed experts plus the shared
experts in the rest. Input: (batch, time) integer ids; labels: the next
ids, as integers (``TokenOutputLayer``).

What one chip holds of a larger deployment is given as arguments, not in
the config: ``layers`` (how many leading layers to keep), ``experts_held``
and ``expert_offset`` (this chip's experts; the router keeps its published
width), ``vocab_rows`` (this chip's slice of the vocabulary). Vertex names:
``embed``, ``l<i>_attn_norm``, ``l<i>_attn``, ``l<i>_attn_add``,
``l<i>_ffn_norm``, ``l<i>_ffn``, ``l<i>_ffn_add`` (i from 1, the published
index), ``final_norm``, ``head``."""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.models.zoo import ZooModel
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import MultiHeadLatentAttention
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward, RoutedExperts
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex, GraphBuilder
from deeplearning4j_tpu.nn.conf.linear_attention import KimiDeltaAttention
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.optimize.updaters import Adam


class KimiLinear(ZooModel):
    def __init__(self, config: dict, layers: Optional[int] = None,
                 experts_held: Optional[int] = None, expert_offset: int = 0,
                 vocab_rows: Optional[int] = None,
                 kda_low_rank: Optional[int] = None,
                 sequence_length: Optional[int] = None, remat: Optional[str] = "full",
                 attention_block: int = 512, loss_block: int = 1024,
                 seed: int = 12345, updater=None):
        vocab = vocab_rows or config["vocab_size"]
        super().__init__(vocab, seed)
        self.config = config
        self.layers = layers or config["num_hidden_layers"]
        self.experts_held = experts_held or config["num_experts"]
        self.expert_offset = expert_offset
        self.kda_low_rank = kda_low_rank or 0      # 0: the head size
        self.sequence_length = sequence_length
        self.remat = remat
        self.attention_block = attention_block
        self.loss_block = loss_block
        self.updater = updater or Adam(learning_rate=1e-3)

    def _attention(self, index: int):
        c = self.config
        la = c["linear_attn_config"]
        if index in la["kda_layers"]:
            return KimiDeltaAttention(
                n_heads=la["num_heads"], head_dim=la["head_dim"],
                conv_size=la["short_conv_kernel_size"],
                low_rank=self.kda_low_rank, eps=c["rms_norm_eps"],
                remat=self.remat)
        if index in la["full_attn_layers"]:
            rotated = not c.get("mla_use_nope", False)
            if rotated and (c.get("rope_scaling")
                            or not c.get("rope_interleave", True)):
                raise NotImplementedError(
                    "latent attention turns adjacent widths at the plain "
                    "frequencies: no scaled rotation, no half-split pairing")
            return MultiHeadLatentAttention(
                n_heads=c["num_attention_heads"],
                nope_dim=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
                v_dim=c["v_head_dim"], kv_rank=c["kv_lora_rank"],
                q_rank=c.get("q_lora_rank") or 0,
                rope_theta=float(c["rope_theta"]) if rotated else 0.0,
                block=self.attention_block, eps=c["rms_norm_eps"],
                remat=self.remat)
        raise ValueError(f"layer {index} is in neither list of "
                         "linear_attn_config")

    def _feed_forward(self, index: int):
        c = self.config
        routed = (index > c["first_k_dense_replace"]
                  and (index - 1) % c["moe_layer_freq"] == 0)
        if not routed:
            return GatedFeedForward(ff_size=c["intermediate_size"],
                                    remat=self.remat)
        if c["moe_router_activation_func"] != "sigmoid" \
                or not c["moe_renormalize"] or c["num_expert_group"] != 1:
            raise NotImplementedError(
                "only the sigmoid router, renormalised, in one group")
        return RoutedExperts(
            n_experts=c["num_experts"], experts_held=self.experts_held,
            expert_offset=self.expert_offset,
            top_k=c["num_experts_per_token"],
            expert_size=c["moe_intermediate_size"],
            shared_size=c["moe_intermediate_size"] * c["num_shared_experts"],
            scaling=c["routed_scaling_factor"])
        # no layer-level remat: the layer checkpoints its own windows of
        # sorted slots, and what else it keeps (router scores, the shared
        # expert's hidden rows) is small beside a forward pass saved

    def conf(self):
        from deeplearning4j_tpu.nn.conf.network import Builder as NNBuilder
        c = self.config
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        parent = NNBuilder()
        parent.seed(self.seed).updater(self.updater)
        g = GraphBuilder(parent)
        g.add_inputs("ids")
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_in=self.num_classes, n_out=d, weight_init="normal"), "ids")
        x = "embed"
        for i in range(1, self.layers + 1):
            n = f"l{i}"
            g.add_layer(n + "_attn_norm", RMSNorm(eps=eps), x)
            g.add_layer(n + "_attn", self._attention(i), n + "_attn_norm")
            g.add_vertex(n + "_attn_add", ElementWiseVertex(op="add"), x,
                         n + "_attn")
            g.add_layer(n + "_ffn_norm", RMSNorm(eps=eps), n + "_attn_add")
            g.add_layer(n + "_ffn", self._feed_forward(i), n + "_ffn_norm")
            g.add_vertex(n + "_ffn_add", ElementWiseVertex(op="add"),
                         n + "_attn_add", n + "_ffn")
            x = n + "_ffn_add"
        g.add_layer("final_norm", RMSNorm(eps=eps), x)
        g.add_layer("head", TokenOutputLayer(
            n_out=self.num_classes, time_block=self.loss_block,
            weight_init="xavier_fan_in"), "final_norm")
        g.set_outputs("head")
        g.set_input_types(InputType.recurrent(self.num_classes,
                                              self.sequence_length))
        return g.build()

"""JoyAI-LLM-Flash as a ComputationGraph, from the keys of its public
``config.json`` (``model_type`` ``joyai_llm_flash``; e.g.
jdopensource/JoyAI-LLM-Flash). The keys are the DeepSeek-V3 family's,
whose public description is arXiv:2412.19437 (section 2.1: latent attention
and the auxiliary-loss-free router; section 2.2: multi-token prediction).

Not in the reference zoo. A decoder of pre-norm blocks,
``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, a final RMSNorm
and a head (tied to the embedding where ``tie_word_embeddings``). ``Attn``
is latent attention in every layer, with a low-rank query (``q_lora_rank``) and a decoupled rotation of the
``qk_rope_head_dim`` widths in the interleaved pairing
(``rope_interleave``) at ``rope_theta``; ``FFN`` is a dense SwiGLU in the
first ``first_k_dense_replace`` layers and, in the rest, ``n_routed_experts``
sigmoid-scored experts (top ``num_experts_per_tok`` of score + bias, the
chosen scores renormalised and times ``routed_scaling_factor``) plus
``n_shared_experts`` shared ones. After the last layer come
``num_nextn_predict_layers`` multi-token prediction modules (one is
built): the last layer's output BEFORE the final norm and the embedding of
the token one step on, each normalised, side by side through ``eh_proj``,
one more block of the routed kind, a norm of its own, and the model's one
head, scored against the labels one step further on
(``MultiTokenOutputLayer``: the trunk's loss + ``mtp_weight`` x the
module's; ``config.json`` holds no weight, 0.3 is the report's first-stage
value). Input: (batch, time) integer ids; labels: the next ids, as
integers.

What one chip holds of a larger deployment is given as arguments, not in
the config: ``layers`` (how many leading layers to keep), ``experts_held``
and ``expert_offset`` (this chip's experts; the router keeps its published
width), ``vocab_rows`` (this chip's slice of the vocabulary). Vertex names:
``embed``, ``l<i>_attn_norm``, ``l<i>_attn``, ``l<i>_attn_add``,
``l<i>_ffn_norm``, ``l<i>_ffn``, ``l<i>_ffn_add`` (i from 1, the published
index), ``final_norm``; the module's ``mtp1_shift``, ``mtp1_in``,
``mtp1_combine``, its block ``mtp1_attn_norm`` .. ``mtp1_ffn_add`` and
``mtp1_norm``; ``states`` (the trunk's and the module's), ``head``."""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.models.zoo import ZooModel
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import MultiHeadLatentAttention
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward, RoutedExperts
from deeplearning4j_tpu.nn.conf.graph import (ElementWiseVertex, GraphBuilder,
                                              StackStatesVertex,
                                              TimeShiftVertex)
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  MultiTokenCombine,
                                                  MultiTokenOutputLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.optimize.updaters import Adam


class JoyAIFlash(ZooModel):
    def __init__(self, config: dict, layers: Optional[int] = None,
                 experts_held: Optional[int] = None, expert_offset: int = 0,
                 vocab_rows: Optional[int] = None,
                 sequence_length: Optional[int] = None,
                 remat: Optional[str] = "full",
                 attention_block: int = 512, loss_block: int = 1024,
                 mtp_weight: float = 0.3, seed: int = 12345, updater=None):
        vocab = vocab_rows or config["vocab_size"]
        super().__init__(vocab, seed)
        c = config
        if c.get("attention_bias"):
            raise NotImplementedError("biases on the attention projections")
        if c.get("rope_scaling"):
            raise NotImplementedError("a scaled rotation on latent attention")
        if not c.get("rope_interleave"):
            raise NotImplementedError(
                "latent attention turns adjacent widths (rope_interleave); "
                "the half-split pairing is not built for it")
        if c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc" \
                or not c["norm_topk_prob"] or c["n_group"] != 1 \
                or c["topk_group"] != 1:
            raise NotImplementedError(
                "only the sigmoid router with a selection bias (noaux_tc), "
                "renormalised, in one group")
        if c["num_nextn_predict_layers"] not in (0, 1):
            raise NotImplementedError("more than one prediction module")
        self.config = config
        self.layers = layers or c["num_hidden_layers"]
        self.experts_held = experts_held or c["n_routed_experts"]
        self.expert_offset = expert_offset
        self.sequence_length = sequence_length
        self.remat = remat
        self.attention_block = attention_block
        self.loss_block = loss_block
        self.mtp_weight = mtp_weight
        self.updater = updater or Adam(learning_rate=1e-3)

    def _attention(self):
        c = self.config
        return MultiHeadLatentAttention(
            n_heads=c["num_attention_heads"], nope_dim=c["qk_nope_head_dim"],
            rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
            kv_rank=c["kv_lora_rank"], q_rank=c["q_lora_rank"] or 0,
            rope_theta=float(c["rope_theta"]), block=self.attention_block,
            eps=c["rms_norm_eps"], remat=self.remat)

    def _routed(self, index: int) -> bool:
        c = self.config
        return (index > c["first_k_dense_replace"]
                and (index - 1) % c["moe_layer_freq"] == 0)

    def _feed_forward(self, routed: bool):
        c = self.config
        if not routed:
            return GatedFeedForward(ff_size=c["intermediate_size"],
                                    remat=self.remat)
        # no layer-level remat: the layer checkpoints its own windows of
        # sorted slots (see models/kimi_linear.py)
        return RoutedExperts(
            n_experts=c["n_routed_experts"], experts_held=self.experts_held,
            expert_offset=self.expert_offset, top_k=c["num_experts_per_tok"],
            expert_size=c["moe_intermediate_size"],
            shared_size=c["moe_intermediate_size"] * c["n_shared_experts"],
            scaling=c["routed_scaling_factor"])

    def _block(self, g, n: str, x: str, routed: bool) -> str:
        """One pre-norm block's vertices under the prefix ``n``, reading
        vertex ``x``; returns the vertex that holds its output."""
        eps = self.config["rms_norm_eps"]
        g.add_layer(n + "_attn_norm", RMSNorm(eps=eps), x)
        g.add_layer(n + "_attn", self._attention(), n + "_attn_norm")
        g.add_vertex(n + "_attn_add", ElementWiseVertex(op="add"), x,
                     n + "_attn")
        g.add_layer(n + "_ffn_norm", RMSNorm(eps=eps), n + "_attn_add")
        g.add_layer(n + "_ffn", self._feed_forward(routed), n + "_ffn_norm")
        g.add_vertex(n + "_ffn_add", ElementWiseVertex(op="add"),
                     n + "_attn_add", n + "_ffn")
        return n + "_ffn_add"

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
            x = self._block(g, f"l{i}", x, self._routed(i))
        g.add_layer("final_norm", RMSNorm(eps=eps), x)
        head = dict(n_out=self.num_classes, time_block=self.loss_block,
                    weight_init="xavier_fan_in",
                    tied_to="embed" if c.get("tie_word_embeddings") else "")
        if c["num_nextn_predict_layers"]:
            # the next token's embedding from the ONE table; the module's
            # block is of the routed kind whatever the depth kept
            g.add_vertex("mtp1_shift", TimeShiftVertex(steps=1), "embed")
            g.add_vertex("mtp1_in", StackStatesVertex(), x, "mtp1_shift")
            g.add_layer("mtp1_combine", MultiTokenCombine(
                eps=eps, remat=self.remat), "mtp1_in")
            m = self._block(g, "mtp1", "mtp1_combine", True)
            g.add_layer("mtp1_norm", RMSNorm(eps=eps), m)
            g.add_vertex("states", StackStatesVertex(), "final_norm",
                         "mtp1_norm")
            g.add_layer("head", MultiTokenOutputLayer(
                module_weight=self.mtp_weight, **head), "states")
        else:
            g.add_layer("head", TokenOutputLayer(**head), "final_norm")
        g.set_outputs("head")
        g.set_input_types(InputType.recurrent(self.num_classes,
                                              self.sequence_length))
        return g.build()

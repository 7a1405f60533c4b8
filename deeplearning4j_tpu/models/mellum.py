"""Mellum 2 as a ComputationGraph, from the keys of its public
``config.json`` (``model_type`` ``mellum``; e.g.
JetBrains/Mellum2-12B-A2.5B-Instruct).

Not in the reference zoo. A decoder of pre-norm blocks,
``h = x + Attn(norm(x))``, ``y = h + FFN(norm(h))``, a final norm and an
untied head; every norm is a plain ``RMSNorm``. With i the layer's index
from 0, ``Attn`` is ``RotaryAttention`` over grouped heads with per-head q/k
norms, and ``layer_types[i]`` picks its mask and its rotation:
``"sliding_attention"`` sees the last ``sliding_window`` keys (where
``use_sliding_window``), ``"full_attention"`` every key at or before the
query, each turning by ``rope_parameters[layer_types[i]]`` (a plain
rotation, or a YaRN-scaled one). ``mlp_layer_types[i]`` picks ``FFN``:
``"sparse"`` is routed experts (softmax router, top-k renormalised, no
shared expert), anything else a dense SwiGLU of ``intermediate_size``.
Input: (batch, time) integer ids; labels: the next ids, as integers
(``TokenOutputLayer``). ``config.json`` has no key for the q/k norms or
the router's activation: its key set is the Qwen3-MoE line's, whose public
code has both (``qk_norm`` / ``router_activation`` are arguments here, so
that a reader who knows otherwise changes a field and no code). No
auxiliary router loss and no multi-token-prediction head are built:
``config.json`` describes neither.

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
from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward, RoutedExperts
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex, GraphBuilder
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.optimize.updaters import Adam


class Mellum2(ZooModel):
    def __init__(self, config: dict, layers: Optional[int] = None,
                 experts_held: Optional[int] = None, expert_offset: int = 0,
                 vocab_rows: Optional[int] = None,
                 sequence_length: Optional[int] = None,
                 remat: Optional[str] = "full",
                 attention_block: int = 512, loss_block: int = 1024,
                 qk_norm: bool = True, router_activation: str = "softmax",
                 seed: int = 12345, updater=None):
        vocab = vocab_rows or config["vocab_size"]
        super().__init__(vocab, seed)
        if config.get("tie_word_embeddings"):
            raise NotImplementedError("a head tied to the embedding")
        if config.get("attention_bias"):
            raise NotImplementedError("biases on the attention projections")
        self.config = config
        self.layers = layers or config["num_hidden_layers"]
        self.experts_held = experts_held or config["num_experts"]
        self.expert_offset = expert_offset
        self.sequence_length = sequence_length
        self.remat = remat
        self.attention_block = attention_block
        self.loss_block = loss_block
        self.qk_norm = qk_norm
        self.router_activation = router_activation
        self.updater = updater or Adam(learning_rate=1e-3)

    def _attention(self, index: int):
        c = self.config
        kind = c["layer_types"][index]
        if kind not in ("sliding_attention", "full_attention"):
            raise NotImplementedError(f"layer type {kind!r}")
        rope = c["rope_parameters"][kind]
        sliding = kind == "sliding_attention" and c.get("use_sliding_window")
        return RotaryAttention(
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            rope_theta=float(rope["rope_theta"]),
            rope_scaling=(None if rope.get("rope_type", "default") == "default"
                          else dict(rope)),
            window=int(c["sliding_window"]) if sliding else 0,
            qk_norm=self.qk_norm, eps=c["rms_norm_eps"],
            block=self.attention_block, remat=self.remat)

    def _feed_forward(self, index: int):
        c = self.config
        if c["mlp_layer_types"][index] != "sparse":
            return GatedFeedForward(ff_size=c["intermediate_size"],
                                    remat=self.remat)
        if not c["norm_topk_prob"]:
            raise NotImplementedError("only the renormalised top-k")
        # no layer-level remat: the layer checkpoints its own windows of
        # sorted slots (see models/kimi_linear.py)
        return RoutedExperts(
            n_experts=c["num_experts"], experts_held=self.experts_held,
            expert_offset=self.expert_offset, top_k=c["num_experts_per_tok"],
            expert_size=c["moe_intermediate_size"], shared_size=0,
            router_activation=self.router_activation)

    def conf(self):
        from deeplearning4j_tpu.nn.conf.network import Builder as NNBuilder
        c = self.config
        d = c["hidden_size"]

        def norm():
            return RMSNorm(eps=c["rms_norm_eps"])

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
            g.add_layer(n + "_attn", self._attention(i), n + "_attn_norm")
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

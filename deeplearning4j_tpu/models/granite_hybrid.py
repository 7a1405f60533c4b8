"""Granite 4.0-H as a ComputationGraph, from the keys of its public
``config.json`` (``model_type`` ``granitemoehybrid``; e.g.
ibm-granite/granite-4.0-h-micro).

Not in the reference zoo. A decoder of pre-norm blocks whose branches are
SCALED before they are added, with m_e = ``embedding_multiplier``, m_r =
``residual_multiplier``, m_a = ``attention_multiplier``, m_l =
``logits_scaling`` and every norm a plain ``RMSNorm`` at ``rms_norm_eps``:

    x_0 = m_e E[ids]
    h = x + m_r Mix_i(norm(x));   y = h + m_r FFN(norm(h))
    logits = (norm(y_last) / m_l) E^T         (the head is E itself)

``layer_types[i]`` picks the token mixer's CLASS: ``"mamba"`` is
``Mamba2Mixer`` (``mamba_n_heads`` heads of ``mamba_d_head`` over
``mamba_n_groups`` shared B and C of ``mamba_d_state``, a convolution of
``mamba_d_conv`` taps with ``mamba_conv_bias``, chunks of
``mamba_chunk_size``), ``"attention"`` is ``RotaryAttention`` over grouped
heads of ``hidden_size / num_attention_heads`` widths WITHOUT a rotation
(``position_embedding_type`` ``"nope"``) whose softmax is scaled by m_a and
not by 1 / sqrt(d). ``FFN`` is the dense SwiGLU of
``shared_intermediate_size`` in every layer (``num_local_experts`` 0). The
four multipliers are ``ScaleVertex``es; the logits' 1 / m_l sits on the
final norm's output, which the tied head multiplies: the same numbers, and
the blocked loss stays one loop. Input: (batch, time) integer ids; labels:
the next ids, as integers (``TokenOutputLayer``).

It raises on what it does not build: routed experts
(``num_local_experts`` > 0), a ``position_embedding_type`` other than
``"nope"``, ``mamba_proj_bias`` or ``attention_bias`` true, a
``layer_types`` entry it does not know.

What one chip holds of a larger deployment is given as arguments, not in
the config: ``layers`` (how many leading layers to keep), ``vocab_rows``
(this chip's slice of the vocabulary), and ``projections_kept`` (how many
of the kept Mamba layers, from the first, hold ``u W_in`` across their
rematerialisation; None: all). Vertex names: ``embed``, ``embed_scale``,
``l<i>_mix_norm``, ``l<i>_ssm`` or ``l<i>_attn``, ``l<i>_mix_scale``,
``l<i>_mix_add``, ``l<i>_ffn_norm``, ``l<i>_ffn``, ``l<i>_ffn_scale``,
``l<i>_ffn_add`` (i from 0, the published index), ``final_norm``,
``logit_scale``, ``head``."""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.models.zoo import ZooModel
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward
from deeplearning4j_tpu.nn.conf.graph import (ElementWiseVertex, GraphBuilder,
                                              ScaleVertex)
from deeplearning4j_tpu.nn.conf.normalization import RMSNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.nn.conf.state_space import Mamba2Mixer
from deeplearning4j_tpu.optimize.updaters import Adam


class GraniteHybrid(ZooModel):
    def __init__(self, config: dict, layers: Optional[int] = None,
                 vocab_rows: Optional[int] = None,
                 sequence_length: Optional[int] = None,
                 remat: Optional[str] = "full",
                 attention_block: int = 512, loss_block: int = 1024,
                 tied: Optional[bool] = None,
                 projections_kept: Optional[int] = None,
                 seed: int = 12345, updater=None):
        vocab = vocab_rows or config["vocab_size"]
        super().__init__(vocab, seed)
        c = config
        if c.get("num_local_experts", 0):
            raise NotImplementedError("routed experts beside the shared "
                                      "feed-forward")
        if c.get("position_embedding_type", "nope") != "nope":
            raise NotImplementedError(
                f"position_embedding_type {c['position_embedding_type']!r}")
        if c.get("mamba_proj_bias") or c.get("attention_bias"):
            raise NotImplementedError("biases on the projections")
        odd = sorted(set(c["layer_types"]) - {"mamba", "attention"})
        if odd:
            raise NotImplementedError(f"layer types {odd}")
        self.config = config
        self.layers = layers or c["num_hidden_layers"]
        self.sequence_length = sequence_length
        self.remat = remat
        self.attention_block = attention_block
        self.loss_block = loss_block
        self.tied = (c.get("tie_word_embeddings", True) if tied is None
                     else tied)
        self.projections_kept = projections_kept
        self.updater = updater or Adam(learning_rate=1e-3)

    def _mixer(self, index: int, mamba_before: int):
        """(vertex suffix, the token mixer) of layer ``index``, the
        ``mamba_before``-th Mamba layer where it is one."""
        c = self.config
        if c["layer_types"][index] == "mamba":
            keep = (self.projections_kept is None
                    or mamba_before < self.projections_kept)
            return "_ssm", Mamba2Mixer(
                n_heads=c["mamba_n_heads"], head_dim=c["mamba_d_head"],
                state_size=c["mamba_d_state"], n_groups=c["mamba_n_groups"],
                conv_size=c["mamba_d_conv"], conv_bias=c["mamba_conv_bias"],
                chunk=c["mamba_chunk_size"], eps=c["rms_norm_eps"],
                keep_projection=keep, remat=self.remat)
        heads = c["num_attention_heads"]
        return "_attn", RotaryAttention(
            n_heads=heads, n_kv_heads=c["num_key_value_heads"],
            head_dim=c["hidden_size"] // heads, position_embedding="nope",
            softmax_scale=float(c["attention_multiplier"]),
            block=self.attention_block, remat=self.remat)

    def conf(self):
        from deeplearning4j_tpu.nn.conf.network import Builder as NNBuilder
        c = self.config
        d = c["hidden_size"]
        if c["mamba_n_heads"] * c["mamba_d_head"] != c["mamba_expand"] * d:
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")

        def norm():
            return RMSNorm(eps=c["rms_norm_eps"])

        def scaled(name, by, source):
            g.add_vertex(name, ScaleVertex(scale=float(by)), source)
            return name

        parent = NNBuilder()
        parent.seed(self.seed).updater(self.updater)
        g = GraphBuilder(parent)
        g.add_inputs("ids")
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_in=self.num_classes, n_out=d, weight_init="normal"), "ids")
        x = scaled("embed_scale", c["embedding_multiplier"], "embed")
        mamba_before = 0
        for i in range(self.layers):
            n = f"l{i}"
            suffix, mixer = self._mixer(i, mamba_before)
            mamba_before += suffix == "_ssm"
            g.add_layer(n + "_mix_norm", norm(), x)
            g.add_layer(n + suffix, mixer, n + "_mix_norm")
            scaled(n + "_mix_scale", c["residual_multiplier"], n + suffix)
            g.add_vertex(n + "_mix_add", ElementWiseVertex(op="add"), x,
                         n + "_mix_scale")
            g.add_layer(n + "_ffn_norm", norm(), n + "_mix_add")
            g.add_layer(n + "_ffn", GatedFeedForward(
                ff_size=c["shared_intermediate_size"], remat=self.remat),
                n + "_ffn_norm")
            scaled(n + "_ffn_scale", c["residual_multiplier"], n + "_ffn")
            g.add_vertex(n + "_ffn_add", ElementWiseVertex(op="add"),
                         n + "_mix_add", n + "_ffn_scale")
            x = n + "_ffn_add"
        g.add_layer("final_norm", norm(), x)
        scaled("logit_scale", 1.0 / c["logits_scaling"], "final_norm")
        g.add_layer("head", TokenOutputLayer(
            n_out=self.num_classes, time_block=self.loss_block,
            weight_init="xavier_fan_in",
            tied_to="embed" if self.tied else ""), "logit_scale")
        g.set_outputs("head")
        g.set_input_types(InputType.recurrent(self.num_classes,
                                              self.sequence_length))
        return g.build()

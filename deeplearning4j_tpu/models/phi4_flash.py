"""Phi-4-mini-flash-reasoning as a ComputationGraph, from the keys of its
public ``config.json`` (``model_type`` ``phi4flash``; the architecture is
SambaY, "Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with
Long Generation", arXiv:2507.06607, with differential attention,
arXiv:2410.05258, and the Mamba-1 mixer, arXiv:2312.00752).

Not in the reference zoo. A decoder of pre-norm blocks with NO position
term anywhere, every norm a ``LayerNorm`` with weight and bias at
``layer_norm_eps``, the head the embedding's own matrix:

    x_0 = E[ids]
    h = x + Mix_i(LN(x));   y = h + MLP_i(LN'(h))
    logits = LN_f(y_last) E^T

``MLP`` is the dense SwiGLU of ``intermediate_size`` in every layer. The
token mixer is one of five kinds (``layer_types``, derived from
``mb_per_layer``, ``num_hidden_layers`` and ``sliding_window`` where the
config does not give them, ``derive_layer_types``); with L =
``num_hidden_layers``, the self-decoder is layers < L/2, layers L/2 and
L/2 + 1 fill the cross-decoder's memory and the rest read it:

    ``"mamba"``         ``Mamba1Mixer``  (even layers <= L/2; the LAST of
                        them, ``"mamba_memory"``, hands its scan output
                        before the gate on as ``<vertex>.scan``)
    ``"swa"``           ``DifferentialAttention`` under ``sliding_window``
                        (odd layers < L/2)
    ``"full_shared"``   ``DifferentialAttention``, causal, that hands its
                        keys and values on as ``<vertex>.kv`` (layer L/2+1)
    ``"gmu"``           ``GatedMemoryUnit`` over the memory (even layers
                        > L/2 + 1)
    ``"cross"``         ``DifferentialAttention`` with ``W_q``, ``W_o``
                        alone, over the shared keys and values, causal (odd
                        layers > L/2 + 1)

Every attention has ``num_attention_heads`` query and
``num_key_value_heads`` key/value heads of ``hidden_size /
num_attention_heads``, paired as ``DifferentialAttention`` says, with
``lambda_init`` from the layer's PUBLISHED index. Mamba-1's sizes are not
in the config: ``mamba_d_state`` (16), ``mamba_d_conv`` (4),
``mamba_expand`` (2) and ``mamba_dt_rank`` (``"auto"``: ceil(width / 16))
are read from it where a caller put them there, else the family's
convention. Input: (batch, time) integer ids; labels: the next ids, as
integers (``TokenOutputLayer``).

What one chip holds of a larger deployment is given as arguments, not in
the config: ``layer_indices`` (the published indices of the layers kept,
in order; None: all) and ``vocab_rows`` (this chip's slice of the
vocabulary). A reader whose maker is not among the layers kept is an
error. Vertex names: ``embed``, ``l<i>_ln1``, ``l<i>_ssm`` | ``l<i>_attn`` |
``l<i>_gmu``, ``l<i>_mix_add``, ``l<i>_ln2``, ``l<i>_ffn``, ``l<i>_ffn_add``
(i the published index), ``final_norm``, ``head``."""

from __future__ import annotations

from typing import List, Optional, Sequence

from deeplearning4j_tpu.models.zoo import ZooModel
from deeplearning4j_tpu.nn.conf import InputType
from deeplearning4j_tpu.nn.conf.attention import DifferentialAttention
from deeplearning4j_tpu.nn.conf.experts import GatedFeedForward
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex, GraphBuilder
from deeplearning4j_tpu.nn.conf.normalization import LayerNorm
from deeplearning4j_tpu.nn.conf.recurrent import (EmbeddingSequenceLayer,
                                                  TokenOutputLayer)
from deeplearning4j_tpu.nn.conf.state_space import (GatedMemoryUnit,
                                                    Mamba1Mixer)
from deeplearning4j_tpu.optimize.updaters import Adam

KINDS = ("mamba", "swa", "mamba_memory", "full_shared", "gmu", "cross")


def derive_layer_types(config: dict) -> List[str]:
    """The kind of every published layer from ``mb_per_layer`` (a Mamba
    layer every that many), ``num_hidden_layers`` and ``sliding_window``."""
    every, count = config["mb_per_layer"], config["num_hidden_layers"]
    if every != 2 or count % 2 or count < 4:
        raise NotImplementedError(
            f"mb_per_layer {every} over {count} layers: the pattern built is "
            "a Mamba layer and an attention layer by turns")
    if not config.get("sliding_window"):
        raise NotImplementedError("a self-decoder without a window")
    half = count // 2
    out = []
    for i in range(count):
        if i % 2 == 0:
            out.append("mamba" if i < half else
                       "mamba_memory" if i == half else "gmu")
        else:
            out.append("swa" if i < half else
                       "full_shared" if i == half + 1 else "cross")
    return out


class Phi4Flash(ZooModel):
    def __init__(self, config: dict,
                 layer_indices: Optional[Sequence[int]] = None,
                 vocab_rows: Optional[int] = None,
                 sequence_length: Optional[int] = None,
                 remat: Optional[str] = "full",
                 attention_block: int = 512, loss_block: int = 1024,
                 scan_chunk: int = 64,
                 seed: int = 12345, updater=None):
        vocab = vocab_rows or config["vocab_size"]
        super().__init__(vocab, seed)
        c = config
        self.layer_types = list(c.get("layer_types")
                                or derive_layer_types(c))
        if len(self.layer_types) != c["num_hidden_layers"]:
            raise ValueError("layer_types names another number of layers "
                             "than num_hidden_layers")
        odd = sorted(set(self.layer_types) - set(KINDS))
        if odd:
            raise NotImplementedError(f"layer types {odd}")
        if c.get("mlp_bias") or c.get("lm_head_bias"):
            raise NotImplementedError("a bias on the MLP or the head")
        if not c.get("tie_word_embeddings", True):
            raise NotImplementedError("an untied head")
        if c["hidden_size"] % c["num_attention_heads"]:
            raise ValueError("hidden_size is no multiple of "
                             "num_attention_heads")
        self.config = config
        self.layer_indices = list(range(c["num_hidden_layers"])
                                  if layer_indices is None
                                  else layer_indices)
        self.sequence_length = sequence_length
        self.remat = remat
        self.attention_block = attention_block
        self.loss_block = loss_block
        self.scan_chunk = scan_chunk
        self.updater = updater or Adam(learning_rate=1e-3)

    def _maker(self, kind: str, reader: int) -> int:
        """The published index of the kept layer of ``kind`` that layer
        ``reader`` reads."""
        found = [i for i in self.layer_indices
                 if self.layer_types[i] == kind and i < reader]
        if not found:
            raise ValueError(f"layer {reader} reads a {kind!r} layer and "
                             f"none is kept before it")
        return found[-1]

    def _mixer(self, index: int):
        """(vertex suffix, the token mixer, what it reads beside its own
        input) of published layer ``index``."""
        c = self.config
        kind = self.layer_types[index]
        d = c["hidden_size"]
        inner = c.get("mamba_expand", 2) * d
        if kind in ("mamba", "mamba_memory"):
            rank = c.get("mamba_dt_rank", "auto")
            return "_ssm", Mamba1Mixer(
                expand=c.get("mamba_expand", 2),
                state_size=c.get("mamba_d_state", 16),
                conv_size=c.get("mamba_d_conv", 4),
                dt_rank=0 if rank == "auto" else int(rank),
                chunk=self.scan_chunk, share_scan=kind == "mamba_memory",
                remat=self.remat), ()
        if kind == "gmu":
            memory = f"l{self._maker('mamba_memory', index)}_ssm.scan"
            return "_gmu", GatedMemoryUnit(memory_size=inner,
                                           remat=self.remat), (memory,)
        heads = c["num_attention_heads"]
        attention = dict(
            n_heads=heads, n_kv_heads=c["num_key_value_heads"],
            head_dim=d // heads, layer_index=index,
            eps=c["layer_norm_eps"], block=self.attention_block,
            remat=self.remat)
        if kind == "cross":
            maker = f"l{self._maker('full_shared', index)}_attn"
            return "_attn", DifferentialAttention(
                kv_from=maker, **attention), (maker + ".kv",)
        return "_attn", DifferentialAttention(
            window=c["sliding_window"] if kind == "swa" else 0,
            share_kv=kind == "full_shared", **attention), ()

    def conf(self):
        from deeplearning4j_tpu.nn.conf.network import Builder as NNBuilder
        c = self.config
        d = c["hidden_size"]

        def norm():
            return LayerNorm(eps=c["layer_norm_eps"])

        parent = NNBuilder()
        parent.seed(self.seed).updater(self.updater)
        g = GraphBuilder(parent)
        g.add_inputs("ids")
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_in=self.num_classes, n_out=d, weight_init="normal"), "ids")
        x = "embed"
        for i in self.layer_indices:
            n = f"l{i}"
            suffix, mixer, reads = self._mixer(i)
            g.add_layer(n + "_ln1", norm(), x)
            g.add_layer(n + suffix, mixer, n + "_ln1", *reads)
            g.add_vertex(n + "_mix_add", ElementWiseVertex(op="add"), x,
                         n + suffix)
            g.add_layer(n + "_ln2", norm(), n + "_mix_add")
            g.add_layer(n + "_ffn", GatedFeedForward(
                ff_size=c["intermediate_size"], remat=self.remat),
                n + "_ln2")
            g.add_vertex(n + "_ffn_add", ElementWiseVertex(op="add"),
                         n + "_mix_add", n + "_ffn")
            x = n + "_ffn_add"
        g.add_layer("final_norm", norm(), x)
        g.add_layer("head", TokenOutputLayer(
            n_out=self.num_classes, time_block=self.loss_block,
            weight_init="xavier_fan_in", tied_to="embed"), "final_norm")
        g.set_outputs("head")
        g.set_input_types(InputType.recurrent(self.num_classes,
                                              self.sequence_length))
        return g.build()

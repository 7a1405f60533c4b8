"""Fusion / memory-traffic optimization pass.

tools/PROFILE_r5.md pins ResNet50 bf16 training at ~0.33 MFU with the convs
AT their bandwidth floor: the ~16 ms non-conv remainder is ≈4.7 full
activation-set HBM crossings caused by BN-train stats/normalize/residual
traffic and BN *backward* re-reading activation-sized saves. This module
attacks exactly that traffic, three ways:

- ``fuse(conf)`` / ``fuse_network(net)`` — a stack/graph rewriter that
  pattern-matches Conv→BatchNorm→Activation(→residual-add) in
  MultiLayerConfiguration stacks and ComputationGraph DAGs and replaces
  each match with a :class:`~deeplearning4j_tpu.nn.conf.convolutional.
  FusedConvBNActivation` block whose ``jax.custom_vjp`` BN backward
  recomputes x-hat from the saved conv output plus O(C) mean/inv-std —
  eliminating the activation-sized save/re-read pairs (the In-Place
  Activated BatchNorm recipe, Bulò et al. CVPR 2018). SeparableConv2D and
  Conv1D chain heads match too (FusedSeparableConvBNActivation /
  FusedConv1DBNActivation share the same custom VJP).

- ``fold_bn(net)`` — serving-time constant folding: BN's inference-mode
  scale/shift folds into the preceding conv's weights/bias, so inference
  graphs (ParallelInference(fold_bn=True), transfer-learning exports,
  ``ZooModel.init(fold_bn=True)``) contain no BN at all; exact within fp
  tolerance.

- ``remat_policy(name, keeps)`` + the per-layer ``remat=`` config knob —
  lowers a layer's apply through ``jax.checkpoint`` with a selectable
  policy (gradient checkpointing, Chen et al. 2016), trading recompute
  FLOPs for saved-activation HBM. ``"full"`` means "recompute everything
  but what the layer TYPE names as dearer to recompute than to keep"
  (``Layer.remat_keeps``: ``jax.ad_checkpoint.checkpoint_name``s: the
  delta-rule scan's output and chunk states, perf/pallas/kda.py, and the
  delta-rule layers' wide projections' outputs, nn/conf/linear_attention.py;
  the latent attention's output and log-sum-exp, perf/pallas/attention.py,
  and its q, k, v as the attention reads them, nn/conf/attention.py);
  a layer that names nothing recomputes everything, as before. The saving
  policies keep what they keep and the names too; ``"nothing_saveable"``
  keeps nothing, names included: the way back for a run short of memory.

Observability: ``training_activation_bytes(conf)`` measures the actual
forward→backward residual set from the jaxpr of ``jax.vjp`` of the REAL
loss (no device allocation — abstract tracing only); it feeds the
training-activation-bytes line of ``conf.memory_report()`` and the HBM
planner. Fused-block trace hits count into
CompileWatch (``fusion.fused_block``), surfaced by
``ParallelInference.stats()``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.convolutional import (
    Convolution1DLayer, ConvolutionLayer, FusedConv1DBNActivation,
    FusedConvBNActivation, FusedSeparableConvBNActivation,
    SeparableConvolution2D,
)
from deeplearning4j_tpu.nn.conf.graph import (
    ComputationGraphConfiguration, ElementWiseVertex,
)
from deeplearning4j_tpu.nn.conf.layers import ActivationLayer
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.normalization import BatchNormalization

__all__ = [
    "REMAT_POLICIES", "remat_policy", "kept_names", "fuse", "fuse_network",
    "fold_bn", "training_activation_bytes",
]


# ----------------------------------------------------------------- remat
# name -> attribute on jax.checkpoint_policies (None = save nothing, i.e.
# jax.checkpoint's default full-recompute behavior)
REMAT_POLICIES = {
    "full": None,
    "nothing_saveable": "nothing_saveable",
    "dots_saveable": "dots_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    "everything_saveable": "everything_saveable",
}


@functools.lru_cache(maxsize=None)
def remat_policy(name: str, keeps: Tuple[str, ...] = ()):
    """Resolve a ``remat=`` knob value to a jax.checkpoint policy callable
    (or None for full recompute). ``keeps`` are the ``checkpoint_name``s
    the layer holds across rematerialisation (``kept_names``): joined to
    the named policy, so ``"full"`` saves them alone. One policy object a
    (name, keeps): layers that share a policy share their lowered
    functions. Raises ValueError on unknown names — the same check
    analysis/validation.py runs ahead of any trace."""
    try:
        attr = REMAT_POLICIES[str(name)]
    except KeyError:
        raise ValueError(
            f"Unknown remat policy '{name}' "
            f"(known: {sorted(REMAT_POLICIES)})") from None
    policies = jax.checkpoint_policies
    base = None if attr is None else getattr(policies, attr)
    if not keeps:
        return base
    named = policies.save_only_these_names(*keeps)
    return named if base is None else policies.save_from_both_policies(
        base, named)


def kept_names(layer) -> Tuple[str, ...]:
    """The ``checkpoint_name``s that ``layer``'s rematerialisation keeps:
    what its type declares (``remat_keeps``) under every ``remat`` but None
    (nothing is rematerialised) and ``"nothing_saveable"`` (which keeps
    nothing, by its name)."""
    if getattr(layer, "remat", None) in (None, "nothing_saveable"):
        return ()
    return tuple(getattr(layer, "remat_keeps", ()))


# ----------------------------------------------------------------- helpers
def _updaters_compatible(conv, bn) -> bool:
    """Fused params share ONE update chain (updater + gradient
    normalization): the BN may only carry the same per-layer overrides as
    the conv, or none — otherwise fusing would silently change how
    gamma/beta update (e.g. drop the BN's gradient clipping)."""
    bu = getattr(bn, "updater", None)
    if bu is not None and bu != getattr(conv, "updater", None):
        return False
    bgn = getattr(bn, "gradient_normalization", None)
    if bgn is not None:
        if bgn != getattr(conv, "gradient_normalization", None):
            return False
        if (getattr(bn, "gradient_normalization_threshold", 1.0)
                != getattr(conv, "gradient_normalization_threshold", 1.0)):
            return False
    return True


def _conv_matchable(conv) -> bool:
    return (isinstance(conv, ConvolutionLayer)
            and conv.activation == "identity")


# chain heads the rewriter matches ahead of a BatchNormalization. The fused
# block classes subclass BaseLayer directly, so isinstance checks on the
# plain conv classes cannot re-match an already-fused block.
_FUSABLE_HEADS = (ConvolutionLayer, SeparableConvolution2D,
                  Convolution1DLayer)


def _head_matchable(layer) -> bool:
    return (isinstance(layer, _FUSABLE_HEADS)
            and layer.activation == "identity")


def _bn_matchable(conv, bn) -> bool:
    return (isinstance(bn, BatchNormalization)
            and not bn.lock_gamma_beta
            and not bn.dropout
            and bn.remat is None
            and _updaters_compatible(conv, bn))


def _act_matchable(act) -> bool:
    return (isinstance(act, ActivationLayer)
            and act.activation_param is None
            and not act.dropout
            and act.remat is None)


def _make_fused(conv: ConvolutionLayer, bn: BatchNormalization,
                activation: str, residual: bool = False,
                name: Optional[str] = None) -> FusedConvBNActivation:
    return FusedConvBNActivation(
        name=name if name is not None else conv.name,
        dropout=conv.dropout,
        remat=conv.remat,
        activation=activation,
        weight_init=conv.weight_init,
        dist=conv.dist,
        bias_init=conv.bias_init,
        l1=conv.l1, l2=conv.l2,
        l1_bias=conv.l1_bias, l2_bias=conv.l2_bias,
        updater=conv.updater,
        gradient_normalization=conv.gradient_normalization,
        gradient_normalization_threshold=conv.gradient_normalization_threshold,
        constraints=conv.constraints,
        weight_noise=conv.weight_noise,
        n_in=conv.n_in, n_out=conv.n_out,
        kernel_size=conv.kernel_size, stride=conv.stride,
        padding=conv.padding, convolution_mode=conv.convolution_mode,
        dilation=conv.dilation, has_bias=conv.has_bias,
        decay=bn.decay, eps=bn.eps, gamma=bn.gamma, beta=bn.beta,
        residual=residual)


def _common_fused_kwargs(conv, bn, activation: str,
                         name: Optional[str]) -> dict:
    return dict(
        name=name if name is not None else conv.name,
        dropout=conv.dropout, remat=conv.remat, activation=activation,
        weight_init=conv.weight_init, dist=conv.dist,
        bias_init=conv.bias_init,
        l1=conv.l1, l2=conv.l2, l1_bias=conv.l1_bias, l2_bias=conv.l2_bias,
        updater=conv.updater,
        gradient_normalization=conv.gradient_normalization,
        gradient_normalization_threshold=conv.gradient_normalization_threshold,
        constraints=conv.constraints, weight_noise=conv.weight_noise,
        n_in=conv.n_in, n_out=conv.n_out, kernel_size=conv.kernel_size,
        stride=conv.stride, padding=conv.padding,
        convolution_mode=conv.convolution_mode, has_bias=conv.has_bias,
        decay=bn.decay, eps=bn.eps, gamma=bn.gamma, beta=bn.beta)


def _make_fused_head(conv, bn, activation: str, residual: bool = False,
                     name: Optional[str] = None):
    """Fused block for any matchable chain head (2-D conv, separable conv,
    1-D conv). Residual adds only exist on the 2-D path."""
    if isinstance(conv, ConvolutionLayer):
        return _make_fused(conv, bn, activation, residual=residual, name=name)
    assert not residual, "residual fusion is 2-D-conv only"
    kw = _common_fused_kwargs(conv, bn, activation, name)
    if isinstance(conv, SeparableConvolution2D):
        return FusedSeparableConvBNActivation(
            depth_multiplier=conv.depth_multiplier, **kw)
    if isinstance(conv, Convolution1DLayer):
        return FusedConv1DBNActivation(dilation=conv.dilation, **kw)
    raise TypeError(f"unfusable chain head {type(conv).__name__}")


# -------------------------------------------------------------- MLN rewrite
def _fuse_multilayer(conf: MultiLayerConfiguration):
    """Returns (fused conf, mapping). mapping entries: ("copy", i) or
    ("fuse", conv_i, bn_i, act_i_or_None) in new-layer order."""
    pres = dict(conf.input_preprocessors or {})
    layers = list(conf.layers)
    new_layers: List = []
    new_pres: Dict[int, object] = {}
    mapping: List[tuple] = []
    i = 0
    while i < len(layers):
        l = layers[i]
        fused = None
        span = 1
        if (_head_matchable(l) and i + 1 < len(layers)
                and (i + 1) not in pres and _bn_matchable(l, layers[i + 1])):
            bn = layers[i + 1]
            act, span = "identity", 2
            act_i = None
            if (i + 2 < len(layers) and (i + 2) not in pres
                    and _act_matchable(layers[i + 2])):
                act, span, act_i = layers[i + 2].activation, 3, i + 2
            fused = _make_fused_head(l, bn, act)
        if i in pres:
            new_pres[len(new_layers)] = pres[i]
        if fused is not None:
            mapping.append(("fuse", i, i + 1, act_i))
            new_layers.append(fused)
            i += span
        else:
            mapping.append(("copy", i))
            new_layers.append(l)
            i += 1
    new_conf = dataclasses.replace(conf, layers=tuple(new_layers),
                                   input_preprocessors=new_pres or None)
    return new_conf, mapping


# ------------------------------------------------------------ graph rewrite
def _fuse_graph(conf: ComputationGraphConfiguration):
    """Returns (fused conf, mapping). mapping: new vertex name ->
    {"conv": name, "bn": name} for fused vertices. Matched chains must have
    fan-out 1 at every interior edge and touch no network output; the
    surviving vertex keeps the LAST matched vertex's name so downstream
    references stay valid."""
    vertices = dict(conf.vertices)
    outputs = set(conf.network_outputs)
    mapping: Dict[str, dict] = {}
    changed = True
    while changed:
        changed = False
        consumers: Dict[str, List[str]] = {}
        for n, (_, ins) in vertices.items():
            for inp in ins:
                consumers.setdefault(inp, []).append(n)
        for cname in list(vertices):
            cobj, cins = vertices[cname]
            if not _head_matchable(cobj):
                continue
            if cname in outputs or len(consumers.get(cname, ())) != 1:
                continue
            bname = consumers[cname][0]
            bobj, bins = vertices[bname]
            if bins != (cname,) or not _bn_matchable(cobj, bobj):
                continue
            if bname in outputs or len(consumers.get(bname, ())) != 1:
                continue
            nxt = consumers[bname][0]
            nobj, nins = vertices[nxt]
            add_name = act_name = res_input = None
            act = "identity"
            if _act_matchable(nobj) and nins == (bname,):
                act_name, act = nxt, nobj.activation
            elif (isinstance(cobj, ConvolutionLayer)  # residual: 2-D only
                  and isinstance(nobj, ElementWiseVertex)
                  and nobj.op.lower() == "add" and len(nins) == 2
                  and nxt not in outputs
                  and len(consumers.get(nxt, ())) == 1):
                anxt = consumers[nxt][0]
                aobj, ains = vertices[anxt]
                if _act_matchable(aobj) and ains == (nxt,):
                    add_name, act_name, act = nxt, anxt, aobj.activation
                    res_input = nins[0] if nins[1] == bname else nins[1]
            new_name = act_name if act_name is not None else bname
            fused = _make_fused_head(cobj, bobj, act,
                                     residual=res_input is not None,
                                     name=cobj.name or cname)
            inputs = (cins[0],) + ((res_input,) if res_input else ())
            vertices[new_name] = (fused, inputs)
            for dead in (cname, bname, add_name):
                if dead is not None and dead != new_name:
                    vertices.pop(dead)
            mapping[new_name] = {"conv": cname, "bn": bname}
            changed = True
            break  # consumer map is stale; rebuild and rescan
    new_conf = dataclasses.replace(conf, vertices=vertices)
    return new_conf, mapping


def fuse(conf):
    """Conv→BN→Act(→residual-add) fusion rewrite of a configuration.

    Accepts a MultiLayerConfiguration or ComputationGraphConfiguration and
    returns a new configuration of the same class with every matched chain
    replaced by a FusedConvBNActivation block (see nn/conf/convolutional).
    Unmatched layers/vertices are untouched; a conf with no matches returns
    structurally equal. Opt out simply by not calling it — fusion is never
    applied implicitly."""
    if isinstance(conf, MultiLayerConfiguration):
        return _fuse_multilayer(conf)[0]
    if isinstance(conf, ComputationGraphConfiguration):
        return _fuse_graph(conf)[0]
    raise TypeError(f"fuse() expects a network configuration, got "
                    f"{type(conf).__name__}")


def _copy_tree(tree):
    return jax.tree_util.tree_map(jnp.array, tree)


def fuse_network(net):
    """Fuse an (optionally initialized/trained) network: rewrites the conf
    AND maps the existing conv/BN parameters and running stats onto the
    fused layout, so the fused network computes the same function. Updater
    state is re-initialized (the fused block owns one update chain where
    conv+BN owned two)."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if isinstance(net, MultiLayerNetwork):
        new_conf, mapping = _fuse_multilayer(net.conf)
        out = MultiLayerNetwork(new_conf)
        if net.params is not None:
            params, state = [], []
            for entry in mapping:
                if entry[0] == "copy":
                    params.append(_copy_tree(net.params[entry[1]]))
                    state.append(_copy_tree(net.state[entry[1]]))
                else:
                    _, ci, bi, _ = entry
                    # the fused param layout is the head conv's keys
                    # (W / W_dw+W_pw[, b]) plus the BN's gamma/beta
                    p = {k: jnp.array(v) for k, v in net.params[ci].items()}
                    p["gamma"] = jnp.array(net.params[bi]["gamma"])
                    p["beta"] = jnp.array(net.params[bi]["beta"])
                    params.append(p)
                    state.append(_copy_tree(net.state[bi]))
            out.params, out.state = params, state
            out.opt_state = [tx.init(p) for tx, p in zip(out._txs, params)]
            out._rng = net._rng
        return out
    if isinstance(net, ComputationGraph):
        new_conf, mapping = _fuse_graph(net.conf)
        out = ComputationGraph(new_conf)
        if net.params is not None:
            params, state = {}, {}
            for name in out.order:
                src = mapping.get(name)
                if src is None:
                    params[name] = _copy_tree(net.params[name])
                    state[name] = _copy_tree(net.state[name])
                else:
                    p = {k: jnp.array(v)
                         for k, v in net.params[src["conv"]].items()}
                    p["gamma"] = jnp.array(net.params[src["bn"]]["gamma"])
                    p["beta"] = jnp.array(net.params[src["bn"]]["beta"])
                    params[name] = p
                    state[name] = _copy_tree(net.state[src["bn"]])
            out.params, out.state = params, state
            out.opt_state = {n: out._txs[n].init(params[n])
                             for n in out._layer_names}
            out._rng = net._rng
        return out
    raise TypeError(f"fuse_network() expects a network, got "
                    f"{type(net).__name__}")


# ---------------------------------------------------------------- fold_bn
def _bn_scale_shift(bn, bn_params, bn_state):
    """Inference-mode per-channel (scale, shift) of a BatchNormalization (or
    FusedConvBNActivation) from its running stats, in f32."""
    mean = jnp.asarray(bn_state["mean"], jnp.float32)
    var = jnp.asarray(bn_state["var"], jnp.float32)
    if getattr(bn, "lock_gamma_beta", False):
        gamma = jnp.full_like(mean, bn.gamma)
        beta = jnp.full_like(mean, bn.beta)
    else:
        gamma = jnp.asarray(bn_params["gamma"], jnp.float32)
        beta = jnp.asarray(bn_params["beta"], jnp.float32)
    inv = jax.lax.rsqrt(var + jnp.float32(bn.eps))
    scale = gamma * inv
    shift = beta - mean * scale
    return scale, shift


def _fold_conv_params(conv_params, has_bias, scale, shift):
    w = jnp.asarray(conv_params["W"], jnp.float32)
    b = (jnp.asarray(conv_params["b"], jnp.float32) if has_bias
         else jnp.zeros((w.shape[-1],), jnp.float32))
    return {"W": w * scale, "b": b * scale + shift}


def _fold_head_params(layer, params, scale, shift):
    """Fold a per-channel (scale, shift) into the head conv's parameters:
    into W's output-channel axis for 2-D/1-D convolutions, into the
    pointwise W_pw for separable convolutions (the depthwise stage is
    untouched — BN sits after the pointwise mix)."""
    if isinstance(layer, (SeparableConvolution2D,
                          FusedSeparableConvBNActivation)):
        w_pw = jnp.asarray(params["W_pw"], jnp.float32)
        b = (jnp.asarray(params["b"], jnp.float32) if layer.has_bias
             else jnp.zeros((w_pw.shape[-1],), jnp.float32))
        return {"W_dw": jnp.asarray(params["W_dw"], jnp.float32),
                "W_pw": w_pw * scale, "b": b * scale + shift}
    return _fold_conv_params(params, layer.has_bias, scale, shift)


def fold_bn(net):
    """Serving-time BN folding: every Conv(activation=identity)→BatchNorm
    pair — and every non-residual FusedConvBNActivation block — collapses
    into a single ConvolutionLayer whose weights/bias absorb the BN's
    inference-mode scale/shift (W' = W·γ/√(σ²+ε); b' = β + (b−μ)·γ/√(σ²+ε)).

    Returns a NEW network of the same class whose inference output matches
    the BN-inference output within fp tolerance and whose graph contains no
    foldable BN. Separable (fold into the pointwise W_pw) and 1-D conv
    heads fold too, as do all fused blocks: residual
    FusedConvBNActivation vertices expand back into the BN-free
    conv → add → activation triple (the activation keeps the vertex name).
    BN not directly behind an identity-activation conv is left in place.
    Train-mode semantics are NOT preserved (batch stats no longer exist) —
    fold for inference/export only. Updater state is reset."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if net.params is None:
        net.init()
    if isinstance(net, MultiLayerNetwork):
        return _fold_bn_multilayer(net)
    if isinstance(net, ComputationGraph):
        return _fold_bn_graph(net)
    raise TypeError(f"fold_bn() expects a network, got {type(net).__name__}")


def _unfuse_to_conv(fl: FusedConvBNActivation) -> ConvolutionLayer:
    return ConvolutionLayer(
        name=fl.name, dropout=fl.dropout, remat=fl.remat,
        activation=fl.activation, weight_init=fl.weight_init, dist=fl.dist,
        bias_init=fl.bias_init, l1=fl.l1, l2=fl.l2, l1_bias=fl.l1_bias,
        l2_bias=fl.l2_bias, updater=fl.updater,
        gradient_normalization=fl.gradient_normalization,
        gradient_normalization_threshold=fl.gradient_normalization_threshold,
        constraints=fl.constraints, weight_noise=fl.weight_noise,
        n_in=fl.n_in, n_out=fl.n_out, kernel_size=fl.kernel_size,
        stride=fl.stride, padding=fl.padding,
        convolution_mode=fl.convolution_mode, dilation=fl.dilation,
        has_bias=True)


# fused blocks fold_bn can collapse back into their BN-free head conv
_FOLDABLE_FUSED = (FusedConvBNActivation, FusedSeparableConvBNActivation,
                   FusedConv1DBNActivation)


def _unfuse_head(fl):
    """The BN-free conv the folded fused block collapses into (bias always
    materialized — it absorbs the BN shift)."""
    if isinstance(fl, FusedConvBNActivation):
        return _unfuse_to_conv(fl)
    common = dict(
        name=fl.name, dropout=fl.dropout, remat=fl.remat,
        activation=fl.activation, weight_init=fl.weight_init, dist=fl.dist,
        bias_init=fl.bias_init, l1=fl.l1, l2=fl.l2, l1_bias=fl.l1_bias,
        l2_bias=fl.l2_bias, updater=fl.updater,
        gradient_normalization=fl.gradient_normalization,
        gradient_normalization_threshold=fl.gradient_normalization_threshold,
        constraints=fl.constraints, weight_noise=fl.weight_noise,
        n_in=fl.n_in, n_out=fl.n_out, kernel_size=fl.kernel_size,
        stride=fl.stride, padding=fl.padding,
        convolution_mode=fl.convolution_mode, has_bias=True)
    if isinstance(fl, FusedSeparableConvBNActivation):
        return SeparableConvolution2D(depth_multiplier=fl.depth_multiplier,
                                      **common)
    if isinstance(fl, FusedConv1DBNActivation):
        return Convolution1DLayer(dilation=fl.dilation, **common)
    raise TypeError(f"not a fused block: {type(fl).__name__}")


def _fold_bn_multilayer(net):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    pres = dict(net.conf.input_preprocessors or {})
    layers = list(net.conf.layers)
    new_layers: List = []
    new_pres: Dict[int, object] = {}
    new_params: List[dict] = []
    new_state: List[dict] = []
    i = 0
    while i < len(layers):
        l = layers[i]
        if i in pres:
            new_pres[len(new_layers)] = pres[i]
        if (_head_matchable(l) and i + 1 < len(layers)
                and isinstance(layers[i + 1], BatchNormalization)
                and (i + 1) not in pres):
            bn = layers[i + 1]
            scale, shift = _bn_scale_shift(bn, net.params[i + 1],
                                           net.state[i + 1])
            new_layers.append(dataclasses.replace(l, has_bias=True))
            new_params.append(_fold_head_params(l, net.params[i], scale,
                                                shift))
            new_state.append({})
            i += 2
        elif (isinstance(l, _FOLDABLE_FUSED)
              and not getattr(l, "residual", False)):
            scale, shift = _bn_scale_shift(l, net.params[i], net.state[i])
            new_layers.append(_unfuse_head(l))
            new_params.append(_fold_head_params(l, net.params[i], scale,
                                                shift))
            new_state.append({})
            i += 1
        else:
            new_layers.append(l)
            new_params.append(_copy_tree(net.params[i]))
            new_state.append(_copy_tree(net.state[i]))
            i += 1
    conf = dataclasses.replace(net.conf, layers=tuple(new_layers),
                               input_preprocessors=new_pres or None)
    out = MultiLayerNetwork(conf)
    out.params, out.state = new_params, new_state
    out.opt_state = [tx.init(p) for tx, p in zip(out._txs, new_params)]
    out._rng = net._rng
    return out


def _fold_bn_graph(net):
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    vertices = dict(net.conf.vertices)
    outputs = list(net.conf.network_outputs)
    params = {n: _copy_tree(net.params[n]) for n in net.params}
    state = {n: _copy_tree(net.state[n]) for n in net.state}

    # standalone fused blocks first; non-residual ones fold in place (no
    # topology change), residual ones expand back into the BN-free
    # conv → add → activation triple — the PR 4 leftover: a fold_bn'd
    # ResNet50 serving graph now contains NO fused block at all
    for name in list(vertices):
        obj, ins = vertices[name]
        if isinstance(obj, _FOLDABLE_FUSED) \
                and not getattr(obj, "residual", False):
            scale, shift = _bn_scale_shift(obj, params[name], state[name])
            vertices[name] = (_unfuse_head(obj), ins)
            params[name] = _fold_head_params(obj, params[name], scale,
                                             shift)
            state[name] = {}
        elif isinstance(obj, FusedConvBNActivation) and obj.residual:
            scale, shift = _bn_scale_shift(obj, params[name], state[name])
            conv = dataclasses.replace(_unfuse_to_conv(obj),
                                       activation="identity")
            conv_name, add_name = f"{name}.fold_conv", f"{name}.fold_add"
            while conv_name in vertices:
                conv_name += "_"
            while add_name in vertices:
                add_name += "_"
            # the ActivationLayer keeps the fused vertex's NAME, so every
            # downstream reference (and the network outputs) keep resolving
            vertices[conv_name] = (conv, (ins[0],))
            vertices[add_name] = (ElementWiseVertex(op="add"),
                                  (conv_name, ins[1]))
            vertices[name] = (ActivationLayer(activation=obj.activation),
                              (add_name,))
            params[conv_name] = _fold_head_params(obj, params[name], scale,
                                                  shift)
            state[conv_name] = {}
            params[add_name], state[add_name] = {}, {}
            params[name], state[name] = {}, {}

    changed = True
    while changed:
        changed = False
        consumers: Dict[str, List[str]] = {}
        for n, (_, ins) in vertices.items():
            for inp in ins:
                consumers.setdefault(inp, []).append(n)
        for cname in list(vertices):
            cobj, cins = vertices[cname]
            if not _head_matchable(cobj):
                continue
            if cname in outputs or len(consumers.get(cname, ())) != 1:
                continue
            bname = consumers[cname][0]
            bobj, bins = vertices[bname]
            if not isinstance(bobj, BatchNormalization) or bins != (cname,):
                continue
            if bname in outputs:
                continue
            scale, shift = _bn_scale_shift(bobj, params[bname], state[bname])
            # the folded conv takes the BN's name so every downstream
            # reference keeps resolving
            vertices[bname] = (dataclasses.replace(cobj, has_bias=True),
                               cins)
            params[bname] = _fold_head_params(cobj, params[cname], scale,
                                              shift)
            state[bname] = {}
            vertices.pop(cname)
            params.pop(cname)
            state.pop(cname)
            changed = True
            break
    conf = dataclasses.replace(net.conf, vertices=vertices)
    out = ComputationGraph(conf)
    out.params = {n: params[n] for n in out.order}
    out.state = {n: state[n] for n in out.order}
    out.opt_state = {n: out._txs[n].init(out.params[n])
                     for n in out._layer_names}
    out._rng = net._rng
    return out


# --------------------------------------------- residual-set measurement
def _residual_bytes_of(run, *arg_structs) -> int:
    """Bytes of the tensors autodiff saves between forward and backward.

    ``run`` must call ``jax.vjp`` of a **jitted** scalar-valued forward:
    partial evaluation then stages the forward as the first ``jit``
    equation of the jaxpr, whose outputs are exactly (primal, *residuals) —
    so the residual set is read off the jaxpr without allocating a byte."""
    jaxpr = jax.make_jaxpr(run)(*arg_structs)
    fwd = next(e for e in jaxpr.eqns if e.primitive.name == "jit")
    total = 0
    for v in fwd.outvars[1:]:  # outvars[0] is the scalar loss
        aval = v.aval
        try:
            total += int(np.prod(aval.shape)) * aval.dtype.itemsize
        except (AttributeError, TypeError):
            pass  # extended dtypes (PRNG keys) etc: not activation traffic
    return total


def _labels_struct(out_layer, out_type, minibatch: int):
    n_out = getattr(out_layer, "n_out", 0) or out_type.flat_size()
    if out_type.kind in ("rnn", "cnn1d"):
        t = out_type.timeseries_length or 16
        return jax.ShapeDtypeStruct((minibatch, t, n_out), jnp.float32)
    return jax.ShapeDtypeStruct((minibatch, n_out), jnp.float32)


def training_activation_bytes(conf, minibatch: int = 32,
                              augmentation=None) -> int:
    """Measured training-activation bytes for a configuration: the size of
    the residual set the REAL train-mode loss forward hands its backward,
    derived from the jaxpr (``jax.make_jaxpr`` over abstract inputs — zero
    device allocation). Fusion and ``remat=`` knobs change this number the
    same way they change the compiled step's HBM traffic, which makes it
    the number the HBM planner budgets and the training-activation-bytes
    line of ``conf.memory_report()``.
    ``augmentation`` (datasets/augment.ImageAugmentation) measures the step
    WITH on-device augmentation in the graph — augmentation changes the
    residual set, so the HBM planner passes it through."""
    from deeplearning4j_tpu.analysis.validation import (
        _abstract_init, _input_struct, _is_index_layer,
    )
    key = jax.random.key(0)
    if isinstance(conf, MultiLayerConfiguration):
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        if conf.input_type is None:
            raise ValueError("training_activation_bytes needs an input_type")
        net = MultiLayerNetwork(conf)
        net.augmentation = augmentation
        types = conf.layer_input_types()
        params, state = [], []
        for layer, it in zip(net.layers, types):
            p, s = _abstract_init(layer, it, key)
            params.append(p)
            state.append(s)
        x = _input_struct(conf.input_type, minibatch,
                          _is_index_layer(net.layers[0]))
        y = _labels_struct(net.layers[-1],
                           net.layers[-1].output_type(types[-1]), minibatch)

        def run(p, s, xx, yy):
            fwd = jax.jit(
                lambda pp: net._loss_fn(pp, s, xx, yy, key, None, None)[0])
            loss, vjp = jax.vjp(fwd, p)
            return vjp(jnp.float32(1.0))

        return _residual_bytes_of(run, params, state, x, y)

    if isinstance(conf, ComputationGraphConfiguration):
        from deeplearning4j_tpu.nn.conf.layers import Layer
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        net = ComputationGraph(conf)
        net.augmentation = augmentation
        params, state = {}, {}
        for name in net.order:
            obj, _ = net.vertices[name]
            if isinstance(obj, Layer):
                p, s = _abstract_init(obj, net.vertex_input_types[name][0],
                                      key)
            else:
                p, s = {}, {}
            params[name] = p
            state[name] = s
        inputs = []
        for ni, it in zip(conf.network_inputs, conf.input_types):
            cons = [conf.vertices[n][0] for n, (_, ins) in
                    conf.vertices.items() if ni in ins]
            idx = any(isinstance(c, Layer) and _is_index_layer(c)
                      for c in cons)
            inputs.append(_input_struct(it, minibatch, idx))
        out_types = conf.vertex_output_types()
        labels = [_labels_struct(conf.vertices[o][0], out_types[o], minibatch)
                  for o in conf.network_outputs]

        def run(p, s, xs, ys):
            fwd = jax.jit(
                lambda pp: net._loss_fn(pp, s, xs, ys, key, None, None)[0])
            loss, vjp = jax.vjp(fwd, p)
            return vjp(jnp.float32(1.0))

        return _residual_bytes_of(run, params, state, inputs, labels)

    raise TypeError(f"training_activation_bytes() expects a configuration, "
                    f"got {type(conf).__name__}")

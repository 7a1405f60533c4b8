"""Gated feed-forward layers: a dense SwiGLU and a routed mixture of experts
that computes ITS SHARE of an expert-parallel deployment.

Not in the 0.9.x reference line. ``RoutedExperts`` routes over ALL
``n_experts`` published experts: scores ``s = act(W_r x)`` in float32 with
``act`` the layer's ``router_activation`` (``"sigmoid"``, the DeepSeek-V3 /
Kimi family's router, or ``"softmax"`` over the experts, the Qwen3-Next
family's), top-``top_k`` of ``s + bias``, weights
``scaling * s_i / sum_topk s_j``, and

    y = sum over chosen experts HELD HERE of w_i E_i(x) + c(x) E_shared(x),
    E(x) = W_down(SiLU(W_gate x) * W_up x)

with ``c(x) = sigmoid(w_sg . x)`` where the layer has a ``shared_gate`` and
1 where it has none.

The layer is told which experts it holds (``experts_held`` of them from
``expert_offset``). What the absent experts would add is left out: on one
chip the layer runs without the exchange, and the partial sum goes on. No
(token, expert) pair that falls on a held expert is ever dropped, at any
imbalance: the pairs are sorted by expert and the three products run as
GROUPED matrix products over the rows each expert really got
(``grouped_matmul``: the Pallas ``megablox`` kernel on TPU, whose grid
follows the load, and ``lax.ragged_dot`` elsewhere). The sorted slots are
computed a window at a time (four times the even share of the held
experts, twice where the share is a quarter or more, at most the worst
case of tokens x top_k slots: ``_window_slots``): one window when
the held pairs fit it, all of them under a ``lax.cond`` when they do not,
so that work, traffic and memory follow the load and a skewed batch is
slower, never wrong.

The dispatch around the products: a window's tokens' rows are GATHERED
(``xf[token]``, ``window`` rows), and the results go back to their tokens
one of two ways. The scatter form adds the window's rows to a float32 (n, d)
array (``zeros.at[token].add``; autodiff makes the gather's transpose a
second scatter-add). The gather form goes through the sort's inverse
permutation ``rank`` (the sorted slot of every (token, choice) pair): a
token's result is the float32 sum over its ``top_k`` pairs of the rows found
through ``rank`` (``_combine``), and so is its row of ``xf``'s cotangent
(``_take_rows``): gathers of tokens x top_k rows, no (n, d) scatter in
either direction, the same products with the same weights. A window's rows
are gathered from a block of columns at a time where they are too many
bytes for the chip's fast memory (``GATHER_OPERAND_BYTES``): a gathered row
then costs a fifth. The layer takes the gather form where tokens x top_k <=
``DISPATCH_GATHER_RATIO`` x ``window``, i.e. where it holds a large share of
the experts (a quarter: ratio 2; all of them: ratio 1), and keeps the
scatter form at a 16th or a 32nd (ratios 4 and 8), where the constant's
readings have it no slower: ``_gathers``, from the shapes alone, counted at
trace time (``bump_active``) as ``moe.dispatch_gather`` or
``moe.dispatch_scatter`` once a layer.

The selection bias and the load counters live in the layer's
non-trained ``state``: ``bias`` (frozen; its load-driven update is not part
of any published config), ``expert_tokens`` (pairs each held expert got,
summed over steps), ``pairs_held``, ``pairs_dropped`` (must stay 0) and
``steps_every_window`` (steps whose held pairs passed the first window, so
that every window ran: the one branch whose choice changes the step's
length). They are device arrays updated inside the step;
``obs.registry.watch_moe`` reads them at scrape time only.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BaseLayer, dropout_input, register_layer,
)
from deeplearning4j_tpu.nn.initializers import init_weights
from deeplearning4j_tpu.perf.compile_watch import bump_active

GMM_ROW_TILE = 128
# a call of ``grouped_matmul`` with this many rows a group or more (the
# window's rows, which are two to four even shares of an expert) takes
# ``_gmm_tiling_dense``'s tiles; at 640 and 1,024 rows a group they are 9%
# and 4% slower than ``_gmm_tiling``'s (PERF.md section 6, PR 37, call 8)
GMM_DENSE_ROWS = 2048
GMM_DENSE_ROW_TILE = 512


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


@register_layer
@dataclasses.dataclass(frozen=True)
class GatedFeedForward(BaseLayer):
    """SwiGLU feed-forward over the feature axis:
    ``W_down(SiLU(W_gate x) * W_up x)``, no biases. ``n_out`` (the model
    width) is inferred from the input when 0; ``ff_size`` is the inner
    width."""

    n_in: Optional[int] = None
    n_out: int = 0
    ff_size: int = 0
    weight_init: str = "xavier_fan_in"

    def regularizable(self):
        return ("Wgate", "Wup", "Wdown")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.flat_size()

    def output_type(self, it: InputType) -> InputType:
        if it.kind == "rnn":
            return InputType.recurrent(self._width(it), it.timeseries_length)
        return InputType.feed_forward(self._width(it))

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.flat_size()
        ff = self.ff_size or 4 * d
        ks = jax.random.split(rng, 3)
        return {
            "Wgate": init_weights(ks[0], (d, ff), d, ff, self.weight_init,
                                  self.dist, dtype),
            "Wup": init_weights(ks[1], (d, ff), d, ff, self.weight_init,
                                self.dist, dtype),
            "Wdown": init_weights(ks[2], (ff, self._width(it)), ff,
                                  self._width(it), self.weight_init,
                                  self.dist, dtype),
        }, {}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        return _swiglu(x, params["Wgate"], params["Wup"],
                       params["Wdown"]), state


# ----------------------------------------------------------- grouped products
def _fit(x: int, most: int) -> int:
    """The largest multiple of 128 that divides ``x`` and is at most
    ``most``; 128 where none does."""
    return max((t for t in range(128, min(x, most) + 1, 128) if x % t == 0),
               default=128)


def _gmm_tiling(m: int, k: int, n: int):
    """Tiles for the megablox kernel: the whole contraction in one tile
    where it is an expert's width or the model's (so that an expert's
    matrix passes through VMEM once a row tile), 128 rows."""
    return GMM_ROW_TILE, _fit(k, 1152), _fit(n, 512)


def _gmm_tiling_dense(m: int, k: int, n: int):
    """Tiles where a group has thousands of rows, so that the MXU and not
    the weights' bytes bounds the product: 512 rows, and a width whole up to
    its cap, else its largest 128-multiple divisor under the cap (1792 =
    14 x 128 runs in tiles of 896; 896 = 7 x 128 has no divisor between 128
    and itself, and tiles of 128 x 1152 x 128 ran a product of 33,000 rows
    at a fifth of the MXU's pace: PERF.md section 6, PRs 37 and 44). The
    caps, 1152 of the contraction and 896 of the output, are what a v5e's
    16 MB of VMEM takes at 512 rows of bfloat16. megablox hands ONE rule to
    all three kernels of a product, and the backward ``tgmm`` holds the
    most: a float32 ``tk x tn`` accumulator beside its doubled output and
    the two doubled operand tiles, 8 tk tn + 2048 (tk + tn) bytes = 12.4 MB
    at the caps; the compiler refuses 2304 x 896 (23.1 MB) and 2048 x 896
    (20.7 MB) (ROADMAP Queue 1 item 3(e): one rule for both sides from
    the budget)."""
    return (GMM_DENSE_ROW_TILE, k if k <= 1152 else _fit(k, 1152),
            n if n <= 896 else _fit(n, 896))


def grouped_matmul(rows, weights, group_sizes):
    """``rows[start_g : start_g + group_sizes[g]] @ weights[g]`` for every
    group g, the groups lying one after another from row 0; rows past the
    last group come back as zeros. ``rows`` (m, k), ``weights`` (groups, k,
    n), ``group_sizes`` (groups,) int32. Differentiable in ``rows`` and
    ``weights``.

    Two paths, because ``lax.ragged_dot`` alone does not do on the TPU: it
    leaves the rows past the last group unwritten there (NaN on the v5e;
    zeros on the CPU), and at the benchmark's load its backward pass is
    slower than the kernel's (PERF.md section 6, PR 26: the three products
    of a window of 8192 rows x 2304 with 2,048 rows in 8 groups, forward
    and backward, 2.07 ms against 1.68; 3.47 against 2.90 at 4,400 rows;
    only a full window is faster, 3.56 against 3.97)."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        # one more (empty-weight) group for the rows past the last: the
        # kernel then zeroes what it did not write
        sizes = jnp.concatenate(
            [group_sizes, (rows.shape[0] - jnp.sum(group_sizes))[None]])
        m, groups = rows.shape[0], weights.shape[0]
        dense = (m >= GMM_DENSE_ROWS * groups
                 and m % GMM_DENSE_ROW_TILE == 0)
        return gmm(rows, weights, sizes.astype(jnp.int32), rows.dtype,
                   _gmm_tiling_dense if dense else _gmm_tiling)
    return lax.ragged_dot(rows, weights, group_sizes.astype(jnp.int32))


def _window_slots(slots: int, held: int, experts: int) -> int:
    """Sorted slots a window, of the ``slots`` (tokens x top_k) a step has,
    for a layer that holds ``held`` of ``experts`` experts: four times the
    even share of the held ones. Where that is every slot (a share of a
    quarter or more) a window of them all would gather, multiply and
    scatter the worst case every step, three quarters of it rows of no
    expert held here: the window is then the larger of TWICE the even share
    and half the slots, which is one window of them all again from a share
    of a half on (most pairs are held: nothing to save)."""
    four = -(-slots * 4 * held // experts)
    if four < slots:
        return four
    return min(slots, max(-(-slots * 2 * held // experts), -(-slots // 2)))


# A step has ``slots`` = tokens x top_k (token, choice) pairs and a window
# ``window`` sorted slots. Bringing the window's rows back to their tokens
# (and the rows' cotangent back to ``xf``) is a scatter-add of ``window`` rows
# or, through the sort's inverse permutation, a gather of ``slots`` rows: the
# gather form where ``slots <= DISPATCH_GATHER_RATIO * window``. Timed on a
# v5e (PERF.md section 6, PR 38), the dispatch alone, forward and backward:
# 17.1 ms as scatter-adds against 13.8 as gathers at slots / window = 2
# (Mellum2's share of a quarter; 13.0 against 9.8 at 1, every expert held),
# 3.15 against 3.40 at 4 (Qwen3-Next's 16th), 2.18 against 2.24 at 8 (Kimi
# Linear's 32nd); with the gathers' operands in fast memory
# (``GATHER_OPERAND_BYTES``) a whole layer, forward and gradient, 42.1
# against 35.7 ms at 2. The constant is the largest ratio read to win.
DISPATCH_GATHER_RATIO = 2.0


def _gathers(slots: int, window: int) -> bool:
    """Whether a routed layer of ``slots`` pairs a step and ``window`` sorted
    slots a window takes the gather form of its dispatch."""
    return slots <= DISPATCH_GATHER_RATIO * window


def _inverse(order):
    """The inverse of the permutation ``order`` (sorted slot -> pair): the
    sorted slot of every pair, ``order[_inverse(order)[p]] == p``. A sort of
    integers: a scatter of them is six times slower on a v5e."""
    return jnp.argsort(order).astype(jnp.int32)


def _in_window(rank, start, window, n_held):
    """For every (token, choice) pair, whether its sorted slot ``rank`` lies
    in the window ``start .. start + window`` AND holds a pair of an expert
    held here (the held pairs sort first: slots under ``n_held``), and its
    row in the window, clipped into it where it does not."""
    inside = (rank >= start) & (rank < jnp.minimum(start + window, n_held))
    return inside, jnp.clip(rank - start, 0, window - 1)


# A gather runs four to five times faster a row where its whole operand is
# at most this large: the v5e then serves it from its fast memory (16,384
# rows of 2304 bfloat16 from 24,576 rows, 108 MiB: 0.24 ms; from 28,672 rows,
# 126 MiB: 0.65 ms, as from 65,536; PERF.md section 6, PR 38). A larger
# operand is gathered a block of columns at a time.
GATHER_OPERAND_BYTES = 108 * 2 ** 20


def _column_blocks(rows: int, d: int, itemsize: int):
    """(start, stop) of the column blocks of a (rows, d) gather operand:
    the whole width where that fits ``GATHER_OPERAND_BYTES``, else equal
    blocks of the most lane tiles of 128 columns that do (at least one)."""
    if rows * d * itemsize <= GATHER_OPERAND_BYTES or d % 128:
        return [(0, d)]
    tiles = d // 128
    fit = max([t for t in range(1, tiles + 1) if tiles % t == 0
               and rows * t * 128 * itemsize <= GATHER_OPERAND_BYTES] or [1])
    return [(c, c + fit * 128) for c in range(0, d, fit * 128)]


def _rows_at(rows, index):
    """``rows[index]`` along the first axis for an ``index`` known to lie
    inside it (a clipped rank, a token of a slot): no bounds to mask."""
    return rows.at[index].get(mode="promise_in_bounds")


def _sum_pairs(rows, at, weight):
    """``sum_j weight[t, j] * float32(rows[at[t, j]])``: a token's ``top_k``
    rows of the window, gathered in ``rows``' type and added in float32.
    ``rows`` (window, d), ``at`` and ``weight`` (n, k). A block of columns
    is cut out only when the block before it is summed (the barrier), so
    that one block at a time asks for the fast memory."""
    parts = []
    for lo, hi in _column_blocks(*rows.shape, rows.dtype.itemsize):
        picked = _rows_at(rows[:, lo:hi], at).astype(jnp.float32)
        total = jnp.sum(picked * weight[..., None], 1)
        rows, total = lax.optimization_barrier((rows, total))
        parts.append(total)
    return jnp.concatenate(parts, -1)


@jax.custom_vjp
def _take_rows(xf, token, rank, start, n_held):
    """``xf[token]``: the window's tokens' rows. Its cotangent reaches ``xf``
    as a GATHER through ``rank`` (``_take_rows_bwd``), where autodiff
    scatter-adds the window's rows."""
    return _rows_at(xf, token)


def _take_rows_fwd(xf, token, rank, start, n_held):
    return _rows_at(xf, token), (rank, start, n_held)


def _take_rows_bwd(kept, d_rows):
    rank, start, n_held = kept
    inside, at = _in_window(rank, start, d_rows.shape[0], n_held)
    dxf = _sum_pairs(d_rows, at, inside.astype(jnp.float32))
    return dxf.astype(d_rows.dtype), None, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _combine(y_rows, w, slots, rank, start, n_held):
    """The window's result rows back at their tokens, float32 (n, d):
    ``y[t] = sum_j w[t, j] * y_rows[rank[t, j] - start]`` over the pairs of
    held experts in the window (``_in_window``), a gather where
    ``zeros.at[token].add`` scatters. ``y_rows`` (window, d), ``w`` and
    ``rank`` (n, k), ``slots`` (window,) the pairs of the window's slots
    (the backward rule's)."""
    inside, at = _in_window(rank, start, y_rows.shape[0], n_held)
    return _sum_pairs(y_rows, at, jnp.where(inside, w, 0.0))


def _combine_fwd(*operands):
    return _combine.fun(*operands), operands


def _combine_bwd(kept, dy):
    """What autodiff gives the scatter form, slot by slot: a row's cotangent
    is its token's ``dy`` times the slot's weight, a weight's the row dot of
    the two, brought to its (token, choice) through ``rank``. ``dy``'s rows
    are gathered a block of columns at a time, as ``_sum_pairs``' are."""
    y_rows, w, slots, rank, start, n_held = kept
    window = y_rows.shape[0]
    valid = start + jnp.arange(window) < n_held
    by_slot = jnp.where(valid, _rows_at(w.reshape(-1), slots), 0.0)
    token = slots // w.shape[1]
    d_by_slot, parts = jnp.zeros((window,), jnp.float32), []
    for lo, hi in _column_blocks(*dy.shape, dy.dtype.itemsize):
        dy_rows = _rows_at(dy[:, lo:hi], token)
        d_by_slot = d_by_slot + jnp.sum(
            dy_rows * y_rows[:, lo:hi].astype(jnp.float32), -1)
        part = (dy_rows * by_slot[:, None]).astype(y_rows.dtype)
        dy, part = lax.optimization_barrier((dy, part))
        parts.append(part)
    inside, at = _in_window(rank, start, window, n_held)
    d_w = jnp.where(inside, _rows_at(jnp.where(valid, d_by_slot, 0.0), at),
                    0.0)
    return (jnp.concatenate(parts, -1), d_w.astype(w.dtype), None, None,
            None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


@register_layer
@dataclasses.dataclass(frozen=True)
class RoutedExperts(BaseLayer):
    """This chip's experts of a routed mixture plus the shared expert (see
    the module docstring). ``n_experts`` is the router's width (all
    published experts), ``experts_held`` how many live here, from
    ``expert_offset``. ``shared_size`` 0 leaves the shared expert out (the
    other shares of a test that counts it once). ``router_activation`` is
    ``"sigmoid"`` or ``"softmax"``; ``shared_gate`` multiplies the shared
    expert by ``sigmoid(w_sg . x)`` (the leaf ``Wsg``); ``renorm_eps`` is
    added to the chosen scores' sum before they are divided by it (0: the
    plain sum)."""

    n_in: Optional[int] = None
    n_out: int = 0
    n_experts: int = 8
    experts_held: int = 8
    expert_offset: int = 0
    top_k: int = 2
    expert_size: int = 0
    shared_size: int = 0
    scaling: float = 1.0
    router_activation: str = "sigmoid"
    shared_gate: bool = False
    weight_init: str = "xavier_fan_in"
    renorm_eps: float = 0.0

    def regularizable(self):
        return ("Wr", "Wgate", "Wup", "Wdown", "Sgate", "Sup", "Sdown",
                "Wsg")

    def _width(self, it: InputType) -> int:
        return self.n_out or self.n_in or it.flat_size()

    def output_type(self, it: InputType) -> InputType:
        if self.expert_offset + self.experts_held > self.n_experts:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.experts_held}"
                f" held, but the router has {self.n_experts}")
        if self.router_activation not in ("sigmoid", "softmax"):
            raise ValueError("router_activation is 'sigmoid' or 'softmax', "
                             f"not {self.router_activation!r}")
        if self.shared_gate and not self.shared_size:
            raise ValueError("a shared_gate needs a shared expert")
        if it.kind == "rnn":
            return InputType.recurrent(self._width(it), it.timeseries_length)
        return InputType.feed_forward(self._width(it))

    def init(self, rng, it: InputType, dtype=jnp.float32):
        d = self.n_in or it.flat_size()
        width = self._width(it)
        ff = self.expert_size or d
        e = self.experts_held
        ks = jax.random.split(rng, 8)

        def w(key, shape):
            return init_weights(key, shape, shape[-2], shape[-1],
                                self.weight_init, self.dist, dtype)

        params = {"Wr": w(ks[0], (d, self.n_experts)),
                  "Wgate": w(ks[1], (e, d, ff)), "Wup": w(ks[2], (e, d, ff)),
                  "Wdown": w(ks[3], (e, ff, width))}
        if self.shared_size:
            params.update({"Sgate": w(ks[4], (d, self.shared_size)),
                           "Sup": w(ks[5], (d, self.shared_size)),
                           "Sdown": w(ks[6], (self.shared_size, width))})
        if self.shared_gate:
            params["Wsg"] = w(ks[7], (d, 1))
        state = {"bias": jnp.zeros((self.n_experts,), jnp.float32),
                 "expert_tokens": jnp.zeros((e,), jnp.int32),
                 "pairs_held": jnp.zeros((), jnp.int32),
                 "pairs_dropped": jnp.zeros((), jnp.int32),
                 "steps_every_window": jnp.zeros((), jnp.int32)}
        return params, state

    def route(self, x, w_r, bias):
        """(weights, expert ids), both (tokens, top_k), over all experts."""
        logits = (x @ w_r).astype(jnp.float32)
        s = (jax.nn.softmax(logits, -1) if self.router_activation == "softmax"
             else jax.nn.sigmoid(logits))
        _, idx = lax.top_k(s + bias, self.top_k)
        chosen = jnp.take_along_axis(s, idx, -1)
        scaled = self.scaling * chosen
        total = jnp.sum(chosen, -1, keepdims=True)
        if self.renorm_eps:
            total = total + self.renorm_eps
        return scaled / total, idx

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = dropout_input(x, self.dropout, train, rng)
        shape = x.shape
        xf = x.reshape(-1, shape[-1])
        n, k, e = xf.shape[0], self.top_k, self.experts_held
        with jax.named_scope("moe.route"):
            w, idx = self.route(xf, params["Wr"], state["bias"])
        with jax.named_scope("moe.dispatch"):
            local = idx - self.expert_offset
            held = (local >= 0) & (local < e)
            key = jnp.where(held, local, e).reshape(-1)       # (n * k,)
            order = jnp.argsort(key, stable=True)     # sorted slot -> pair
            sizes = jnp.sum(jax.nn.one_hot(key, e + 1, dtype=jnp.int32),
                            0)[:e]
            n_held = jnp.sum(sizes)
        # the sorted slots are computed ``window`` at a time: four times
        # the even share of the held experts (held / all of the tokens x
        # top_k slots). The usual load fits the first window, with room
        # for a router that drifts towards the experts it is trained
        # through; a skewed batch takes as many as it needs, up to the
        # worst case (tokens x top_k slots)
        window = -(-_window_slots(n * k, e, self.n_experts)
                   // GMM_ROW_TILE) * GMM_ROW_TILE
        windows = -(-n * k // window)
        rank = None
        if _gathers(n * k, window):
            with jax.named_scope("moe.dispatch"):
                rank = _inverse(order).reshape(n, k)
        bump_active("moe.dispatch_scatter" if rank is None
                    else "moe.dispatch_gather")
        if windows * window > n * k:
            order = jnp.pad(order, (0, windows * window - n * k))
        ends = jnp.cumsum(sizes)

        @jax.checkpoint
        def run(start, xf, experts, order, w, sizes, ends, rank):
            """The sorted slots ``start .. start + window``: their tokens'
            rows gathered, the three grouped products over the part of
            each expert's rows that lies in the window, and the results
            added back to their tokens with the router's weights. Gather
            one way over ``window`` rows; the other way a scatter-add of
            ``window`` rows or, given ``rank`` (``_gathers``), a gather of
            tokens x top_k rows through it (``_combine``), and the same
            for the rows' cotangent on its way to ``xf`` (``_take_rows``)."""
            with jax.named_scope("moe.dispatch"):
                slots = lax.dynamic_slice_in_dim(order, start, window)
                token = slots // k
                rows = (xf[token] if rank is None else
                        _take_rows(xf, token, rank, start, ends[-1]))
                stop = start + window
                here = (jnp.clip(ends, start, stop)
                        - jnp.clip(ends - sizes, start, stop))
            with jax.named_scope("moe.experts"):
                hidden = (jax.nn.silu(grouped_matmul(rows, experts["Wgate"],
                                                     here))
                          * grouped_matmul(rows, experts["Wup"], here))
                y_rows = grouped_matmul(hidden, experts["Wdown"], here)
            with jax.named_scope("moe.dispatch"):
                if rank is None:
                    by_slot = jnp.where(
                        start + jnp.arange(window) < ends[-1],
                        w.reshape(-1)[slots], 0.0)
                    y = jnp.zeros((n, y_rows.shape[-1]), jnp.float32).at[
                        token].add(y_rows.astype(jnp.float32)
                                   * by_slot[:, None])
                else:
                    y = _combine(y_rows, w, slots, rank, start, ends[-1])
            # the rows the grouped products were given: what this window
            # covered of the held pairs
            return y, jnp.sum(here)

        experts = {name: params[name] for name in ("Wgate", "Wup", "Wdown")}
        operands = (xf, experts, order, w, sizes, ends, rank)

        def first_window(*ops):
            return run(0, *ops)

        def every_window(*ops):
            """A skewed batch: every window in turn. A window past the
            held pairs has empty groups (the kernel's grid is then empty)
            and adds zeros; skipping it under a second ``lax.cond`` would
            make the scan keep its operands once a window for the
            backward pass (3.7 GB at the benchmark's sizes)."""
            def step(carry, i):
                y, rows = run(i * window, *ops)
                return (carry[0] + y, carry[1] + rows), None
            carry, _ = lax.scan(step, first_window(*ops),
                                jnp.arange(1, windows))
            return carry

        if windows > 1:
            y, covered = lax.cond(n_held <= window, first_window,
                                  every_window, *operands)
            took_every = (n_held > window).astype(jnp.int32)
        else:
            y, covered = first_window(*operands)
            took_every = 0
        y = y.astype(x.dtype)
        if self.shared_size:
            with jax.named_scope("moe.shared"):
                shared = _swiglu(xf, params["Sgate"], params["Sup"],
                                 params["Sdown"])
                if self.shared_gate:
                    with jax.named_scope("moe.shared_gate"):
                        shared = shared * jax.nn.sigmoid(
                            (xf @ params["Wsg"]).astype(jnp.float32)).astype(
                                shared.dtype)
                y = y + shared
        # ``covered`` counts the rows that the windows which RAN handed to
        # the grouped products; a held pair outside them was dropped
        new_state = {"bias": state["bias"],
                     "expert_tokens": state["expert_tokens"] + sizes,
                     "pairs_held": state["pairs_held"] + n_held,
                     "pairs_dropped": state["pairs_dropped"]
                     + (n_held - covered),
                     "steps_every_window": state["steps_every_window"]
                     + took_every}
        return y.reshape(shape[:-1] + (y.shape[-1],)), new_state


__all__ = ["GatedFeedForward", "RoutedExperts", "grouped_matmul"]

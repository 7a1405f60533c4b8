"""The ``ouro_2p6b_ut4_pp6`` configuration: the program against its plain
reference on the CPU at the file's ``rehearse`` size in float32 (every
pass's state, the exit distribution, the loss, every gradient leaf, three
Adam steps; the reference's step in pieces against its whole loss), the
cell through its driver with the float8 control failing and with two
planted faults (a pass left out of the looped leaves' gradient, half of the
tokens left out of the loss) failing, the scopes in the compiled step, the
hand counts of parameters and FLOPs at the published widths, and each new
per-layer reader on a synthetic trace."""

import contextlib
import dataclasses
import math
import re
import types

import numpy as np
import pytest

import bench_paths
from harness import feed, flops, hlo_ops, loader, peaks, trace

CELL = "ouro_train_8k_ut4"
CONFIG = "ouro_2p6b_ut4_pp6"
# float32 on the CPU, two orders of the same sums through 2 x 3 layers
FORWARD_TOL = 5e-6      # states and probabilities, absolute
LOSS_TOL = 2e-6         # relative
GRAD_TOL = 1e-4         # a leaf's max |difference| over its max |value|
NEW_METRICS = ["loop.attn_device_ms_per_step", "loop.attend_roofline_pct",
               "loop.ffn_device_ms_per_step", "loop.exits_device_ms_per_step"]


@pytest.fixture(scope="module")
def cell():
    return loader.resolve_cell(bench_paths.ROOT, CELL, rehearse=True)


@pytest.fixture(scope="module")
def full():
    return loader.resolve_cell(bench_paths.ROOT, CELL)


@pytest.fixture(scope="module")
def sides(cell):
    """The network and the reference on the same seeded weights and ids,
    with both sides' loss and gradients. T = 200: not a multiple of the
    attention tile (64), of the loss block (64) or of the reference's
    blocks."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        cfg = dict(cell.config, compute_dtype="float32")
        ref = cell.reference
        p0 = ref.init_params(cfg, 7)
        net = cell.build(cfg, dict(p0))
        ids = np.random.default_rng(0).integers(
            0, cfg["vocab_size"], (2, 201)).astype(np.int32)
        x, y = ids[:, :-1], ids[:, 1:]

        def program_loss(params):
            return net._loss_fn(params, net.state, [jnp.asarray(x)],
                                [jnp.asarray(y)], None, None, None)[0]

        loss_p, grads_p = jax.value_and_grad(program_loss)(net.params)
        loss_r, grads_r = jax.value_and_grad(
            lambda p: ref.loss(cfg, p, jnp.asarray(x), jnp.asarray(y)))(p0)
        loss_pieces, grads_pieces = ref.loss_and_grads(
            cfg, p0, jnp.asarray(x), jnp.asarray(y))
        acts = net._forward(net.params, net.state, [jnp.asarray(x)], False,
                            None, None)[0]
        passes_r = ref.passes(cfg, p0, jnp.asarray(x))
        probs_r = jax.nn.softmax(ref.logits(cfg, p0, jnp.asarray(x)), -1)
    return types.SimpleNamespace(
        cfg=cfg, ref=ref, net=net, p0=p0, x=x, y=y,
        loss_p=float(loss_p), loss_r=float(loss_r),
        loss_pieces=float(loss_pieces), grads_p=cell.adapter.flat(grads_p),
        grads_r=grads_r, grads_pieces=grads_pieces,
        passes_p=np.asarray(acts["loop"]),
        passes_r=np.stack([np.asarray(a) for a in passes_r]),
        probs_p=np.asarray(acts["head"]), probs_r=np.asarray(probs_r))


def _reference_module():
    return loader.import_file(
        f"{bench_paths.ROOT}/benchmark/references/{CONFIG}.py", "reference")


def _rehearse_leaves():
    cfg = loader.read_json(f"{bench_paths.ROOT}/benchmark/configs/"
                           f"{CONFIG}.json")
    return list(_reference_module().param_shapes({**cfg, **cfg["rehearse"]}))


def test_every_pass_and_the_loss_follow_the_reference(sides):
    assert sides.passes_p.shape == (3, 2, 200, 64)
    assert np.max(np.abs(sides.passes_p - sides.passes_r)) < FORWARD_TOL
    # the passes differ: the loop is no identity
    assert np.max(np.abs(sides.passes_p[0] - sides.passes_p[2])) > 0.1
    assert np.max(np.abs(sides.probs_p - sides.probs_r)) < FORWARD_TOL
    assert abs(sides.loss_p - sides.loss_r) < LOSS_TOL * abs(sides.loss_r)


@pytest.mark.parametrize("leaf", _rehearse_leaves())
def test_every_gradient_leaf_follows_the_reference(sides, leaf):
    """The looped leaves among them: a pass left out of the sum would read
    a third off."""
    import jax.numpy as jnp
    got, want = sides.grads_p[leaf], sides.grads_r[leaf]
    assert got.shape == want.shape
    scale = max(float(jnp.max(jnp.abs(want))), 1e-12)
    assert float(jnp.max(jnp.abs(got - want))) < GRAD_TOL * scale, leaf
    # the reference's step in pieces is its whole loss differentiated
    pieces = sides.grads_pieces[leaf]
    assert float(jnp.max(jnp.abs(pieces - want))) < GRAD_TOL * scale, leaf


def test_the_reference_s_pieces_give_its_loss(sides):
    assert abs(sides.loss_pieces - sides.loss_r) < LOSS_TOL * abs(sides.loss_r)
    assert set(sides.grads_pieces) == set(sides.p0)


def test_the_exit_distribution_follows_the_reference(sides):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.lossfunctions import exit_distribution
    ref, cfg = sides.ref, sides.cfg
    with jax.default_matmul_precision("highest"):
        want = ref.exits(cfg, sides.p0, jnp.asarray(sides.x))
        head = sides.net.params["head"]
        logits = (jnp.asarray(sides.passes_p) @ head["Wg"])[..., 0] \
            + head["bg"]
        got = jnp.exp(exit_distribution(logits))
    assert want.shape == got.shape == (3, 2, 200)
    assert float(jnp.max(jnp.abs(jnp.sum(want, 0) - 1.0))) < 1e-6
    assert float(jnp.max(jnp.abs(got - want))) < FORWARD_TOL
    assert 0.05 < float(jnp.min(jnp.mean(want, (1, 2))))  # every exit is used


def test_three_adam_steps_follow_the_reference(cell):
    """Set-up's own path at the small size: three steps through
    ``net.fit``, the reference's three after them, leaf by leaf."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import DataSet

    cfg, ref = cell.config, cell.reference
    assert cfg["compute_dtype"] == "float32"
    with jax.default_matmul_precision("highest"):
        net = cell.build(cfg, ref.init_params(cfg, 11))
        rng = np.random.default_rng(1)
        batches = []
        for _ in range(3):
            ids = rng.integers(0, cfg["vocab_size"], (2, 129)).astype(np.int32)
            batches.append((ids[:, :-1], ids[:, 1:]))
        losses = []
        for x, y in batches:
            net.fit(DataSet(x, y))
            losses.append(float(net.score()))
        out = ref.train_steps(cfg, ref.init_params(cfg, 11), batches)
        now = cell.adapter.params_flat(net)
        start = ref.init_params(cfg, 11)
        moved = {k: float(jnp.linalg.norm(now[k] - start[k])) for k in now}
    for got, want in zip(losses, out["losses"]):
        assert abs(got - want) < 1e-5 * abs(want)
    for leaf, want in out["delta_norms"].items():
        assert abs(moved[leaf] - want) <= 2e-3 * max(want, 1e-9), leaf
    assert min(out["delta_norms"].values()) > 0      # every leaf moved


def test_the_cell_runs_through_its_driver_and_the_control_fails(cell, tmp_path):
    """Set-up's first steps through ``net.fit(DevicePrefetchIterator)``,
    the reference after them: ``correct`` in float32 within the cell's
    limits, the float8 control outside one of them."""
    import jax

    quiet = lambda *a: None
    session = cell.driver.setup(cell, jax.devices()[:1], 2_147_483_999, quiet)
    raw = cell.driver.run_window(session, 0.3, None)
    assert raw["steps"] > 0 and raw["compiles_in_window"] == 0
    assert raw["failed"] == 0 and raw["moe_dropped_tokens_total"] == 0
    assert raw["items"] == raw["steps"] * 2 * 128
    assert cell.program_view["moe"] == {}
    # a traced window on the same session: the text is the executable's own
    took = feed.TraceSlice(str(tmp_path), 0.05, 0.05)
    raw = cell.driver.run_window(session, 0.4, took)
    view = cell.program_view
    assert took.done and raw["compiles_for_hlo_text"] == 0
    assert raw["compiles_in_window"] == 0 and raw["failed"] == 0
    assert "RotaryAttention:attn" in view["hlo_text"]
    assert "LoopVertex:loop" in view["hlo_text"]
    counters = session.net.compile_watch.counters()
    assert counters["loop.scanned"] == 1
    assert counters["attention.rotary_blocked"] == 2      # two layers, once
    ok, rows = cell.driver.check(session, quiet)
    assert ok, rows
    ok, rows = cell.driver.control(session, quiet)
    assert not ok, rows


@contextlib.contextmanager
def planted(fault: str):
    """The program with one fault in it, for as long as the block lasts
    (the step is traced inside set-up):

    * ``pass_detached``: the second pass of every loop of more than one
      pass reads the weights behind a ``stop_gradient``, so the looped
      leaves' gradient is the sum over the other passes (the forward pass
      and the first loss are the sound program's);
    * ``half_the_tokens``: the output layer's loss is the mean over the
      first half of the time steps."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.graph import LoopVertex
    from deeplearning4j_tpu.nn.conf.recurrent import (
        ExitWeightedTokenOutputLayer)

    if fault == "pass_detached":
        owner, name = LoopVertex, "apply"
        sound = owner.apply

        def faulty(self, params, state, x, **kw):
            if self.steps == 1:
                return sound(self, params, state, x, **kw)
            one = dataclasses.replace(self, steps=1, stacked=False)
            outs, h = [], x
            for r in range(self.steps):
                p = jax.lax.stop_gradient(params) if r == 1 else params
                h, state = sound(one, p, state, h, **kw)
                outs.append(h)
            return jnp.stack(outs), state
    elif fault == "half_the_tokens":
        owner, name = ExitWeightedTokenOutputLayer, "compute_score"
        sound = owner.compute_score

        def faulty(self, labels, preout, mask=None):
            t = labels.shape[1]
            keep = jnp.broadcast_to(jnp.arange(t) < t // 2, labels.shape[:2])
            return sound(self, labels, preout, keep)
    else:
        raise ValueError(fault)
    setattr(owner, name, faulty)
    try:
        yield
    finally:
        setattr(owner, name, sound)


# what each fault has to trip at the least, of the cell's own limits
FAULTS = {"pass_detached": {"grad_norm.worst_leaf", "grad_norm.median_leaf"},
          "half_the_tokens": {"loss.step1", "grad_norm.worst_leaf",
                              "grad_norm.median_leaf"}}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_driver_s_check(cell, fault):
    """Set-up's first steps through ``net.fit`` with the fault in the
    program, then the driver's own ``check`` against the sound reference:
    not ``correct``, by the numbers the fault is there to move. That holds
    at this size in float32. The readings at the timed size on the chip
    stand beside the limits in the cell's ``limits_why`` and in PERF.md
    section 4: there a detached pass reads nearer the sound runs and is not
    always refused."""
    import jax

    quiet = lambda *a: None
    with planted(fault):
        session = cell.driver.setup(cell, jax.devices()[:1], 2_147_484_123,
                                    quiet)
    ok, rows = cell.driver.check(session, quiet)
    tripped = {row["what"] for row in rows if not row["ok"]}
    assert not ok and FAULTS[fault] <= tripped, rows
    if fault == "pass_detached":
        # the forward pass is the sound program's: the first loss holds
        assert "loss.step1" not in tripped, rows


SCOPES = ["loop.body", "rattn.rope", "rattn.attend", "loop.exit_gate",
          "loop.exit_head", "loss.exit_weighted"]


@pytest.fixture(scope="module")
def step_text(sides):
    """The compiled train step at the rehearse size."""
    import jax

    net = sides.net

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    args = (struct(net.params), struct(net.state), struct(net.opt_state),
            struct(net._rng), [struct(sides.x)], [struct(sides.y)], None, None)
    return net._get_jitted("train").lower(*args).compile().as_text()


@pytest.mark.parametrize("scope", SCOPES)
def test_every_scope_is_in_the_compiled_step_forward_and_backward(
        step_text, scope):
    names = set(re.findall(r'op_name="([^"]*)"', step_text))
    layer = {"rattn": "RotaryAttention:attn", "loop.body": "LoopVertex:loop",
             }.get(scope if scope == "loop.body" else scope.split(".")[0], "")
    under = [o for o in names if scope in o and layer in o]
    assert any("transpose(" not in o for o in under), scope
    assert any("transpose(" in o for o in under), scope


def test_the_body_s_layers_keep_their_scopes_inside_the_scan(step_text):
    """The passes are one ``while`` under ``loop.body``, forward and
    backward, and every layer of the body is in the text once a layer (not
    once a layer and pass) under ``<LayerClass>:<name>``: what the
    per-layer readers match. (Operations inside a ``while`` body carry the
    scopes opened inside it, not the loop's own.)"""
    names = set(re.findall(r'op_name="([^"]*)"', step_text))
    loops = [o for o in names if o.endswith("loop.body/while")]
    assert any("transpose(" in o for o in loops)
    assert any("transpose(" not in o for o in loops)
    for marker in ("RotaryAttention:attn", "GatedFeedForward:ffn",
                   "RMSNorm:pre", "RMSNorm:post", "RMSNorm:final_norm",
                   "LoopVertex:l0_attn", "LoopVertex:l1_ffn"):
        assert any(marker in o for o in names), marker
    held = {m for o in names if "rattn.attend" in o
            for m in re.findall(r"LoopVertex:(l\d+)_attn", o)}
    assert held == {"l0", "l1"}


# ------------------------------------------------------------- hand counts
def test_parameter_hand_count_at_the_published_widths(full):
    """ISSUE 32's arithmetic, reckoned again: every width as published, 8
    of 48 layers, the whole vocabulary; the looped block's weights once."""
    d, ff, vocab = 2048, 5632, 49152
    attn = 4 * d * 16 * 128
    mlp = 3 * d * ff
    layer = attn + mlp + 4 * d
    total = 8 * layer + 2 * vocab * d + d + (d + 1)
    assert (attn, mlp, layer) == (16_777_216, 34_603_008, 51_388_416)
    assert vocab * d == 100_663_296
    assert total == 612_438_017
    assert full.reference.count_params(full.config) == total
    assert len(full.reference.blocks(full.config)) == 8
    assert full.config["total_ut_steps"] == 4


def test_the_zoo_builder_draws_that_many_from_the_public_keys(full):
    """``models.Ouro`` from the public config's keys alone, cut by its
    arguments (shapes only, nothing drawn): 612,438,017 parameters held
    ONCE under the loop's vertex with one Adam state; the whole published
    model 2.67 billion."""
    import jax
    from deeplearning4j_tpu.models import Ouro
    from deeplearning4j_tpu.nn.conf.graph import LoopVertex
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    public = full.adapter.public_config(full.config)
    assert public["num_hidden_layers"] == 48

    def count(zoo):
        net = ComputationGraph(zoo.conf())
        drawn = jax.eval_shape(net._draw, jax.random.key(0))[0]
        n = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(drawn))
        return net, drawn, n

    net, drawn, n = count(Ouro(public, layers=8, sequence_length=8192))
    assert n == 612_438_017
    loop = net.vertices["loop"][0]
    assert isinstance(loop, LoopVertex) and loop.steps == 4
    assert sorted(drawn) == ["embed", "head", "loop"]
    assert len(drawn["loop"]) == 17                # 8 x 2 sub-blocks + norm
    opt = jax.eval_shape(net.init_opt_state, drawn)
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(
        opt["loop"])) == 2 * (8 * 51_388_416 + 2048) + 1
    assert sorted(full.reference.param_shapes(full.config)) == sorted(
        full.adapter.flat(drawn))
    _, _, n = count(Ouro(public))
    assert 2.6e9 < n < 2.7e9


def test_flop_hand_count_at_the_published_widths(full):
    """Forward matrix-product FLOPs a token at T = 8192: 32 layer
    applications and four heads."""
    d, t = 2048, 8192
    layer = 2 * (4 * d * 2048 + 3 * d * 5632
                 + 16 * (128 + 128) * (t + 1) / 2)
    want = 4 * (8 * layer + 2 * d * 49152 + 2 * d)
    got = flops.forward_flops_per_item(full.reference.layers(full.config))
    assert got == pytest.approx(want, rel=1e-12)
    assert all(layer["kind"] == "dense"
               for layer in full.reference.layers(full.config))
    # 5.17 GFLOP a token forward, 15.5 to train, 127 TFLOP a step
    assert 5.16e9 < got < 5.18e9
    assert 15.4e9 < 3 * got < 15.6e9
    assert 126e12 < 3 * got * 8192 < 128e12


def test_kernel_cost_function(full):
    ref, cfg = full.reference, full.config
    attend = ref.rattn_attend_cost(cfg, 8192)
    # 16 heads, 136 tile pairs of 512 x 512, two products of width 128
    assert attend["flops"] == 16 * 136 * 2 * 2 * 512 * 512 * 128
    whole = 16 * 2 * 2 * 8192 * 8192 * 128
    assert whole / 2 < attend["flops"] < 0.54 * whole
    # q and o once, k and v a pair, bfloat16; no repeat: the heads are equal
    assert attend["bytes"] == 2 * 16 * (2 * 8192 * 128 + 136 * 2 * 512 * 128)
    assert attend["flops"] / 197e12 > attend["bytes"] / 819e9   # MXU bound
    grouped = ref.rattn_attend_cost(dict(cfg, num_key_value_heads=4), 8192)
    assert grouped["bytes"] > attend["bytes"]
    assert grouped["flops"] == attend["flops"]


# ------------------------------------------------------------ the readers
_HLO = '''
HloModule jit_train_step
%fused_computation.1 { ... }
ENTRY %main {
  %custom-call.1 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(LoopVertex:loop)/loop.body/while/body/LoopVertex:l0_attn/RotaryAttention:attn/rattn.attend/pallas_call" source_file="x.py" source_line=1}
  %fusion.2 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(LoopVertex:loop)/loop.body/while/body/LoopVertex:l0_attn/RotaryAttention:attn/rattn.rope/mul"}
  %fusion.3 = bf16[8]{0} fusion(%p0), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(LoopVertex:loop))/loop.body/while/body/LoopVertex:l0_ffn/GatedFeedForward:ffn/dot_general"}
  %fusion.4 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(LoopVertex:loop)/loop.body/while/body/LoopVertex:l0_ffn/RMSNorm:pre/mul"}
  %fusion.5 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(loop.exit_gate)/reduce_sum"}
  %while.6 = f32[8]{0} while(%p0), metadata={op_name="jit(train_step)/jvp(loop.exit_head)/while"}
  %fusion.7 = f32[8]{0} fusion(%p0), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(loop.exit_head)/while/body/checkpoint/dot_general"}
  %fusion.8 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(loss.exit_weighted))/mul"}
  ROOT %fusion.9 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/adam/mul"}
}
'''


def _ctx(full, with_view=True):
    ms = 1e-3
    ops = [("%custom-call.1 = bf16[8]{0} custom-call(%p0)", 0 * ms, 8 * ms),
           ("%fusion.2 = bf16[8]{0} fusion(%p0)", 8 * ms, 10 * ms),
           ("%fusion.3 = bf16[8]{0} fusion(%p0)", 10 * ms, 16 * ms),
           ("%fusion.4 = bf16[8]{0} fusion(%p0)", 16 * ms, 17 * ms),
           ("%fusion.5 = f32[8]{0} fusion(%p0)", 17 * ms, 18 * ms),
           # the loop and the operation of its body overlap: counted once
           ("%while.6 = f32[8]{0} while(%p0)", 18 * ms, 23 * ms),
           ("%fusion.7 = f32[8]{0} fusion(%p0)", 19 * ms, 22 * ms),
           ("%fusion.8 = f32[8]{0} fusion(%p0)", 23 * ms, 25 * ms),
           ("%fusion.9 = f32[8]{0} fusion(%p0)", 25 * ms, 30 * ms)]
    # two steps, the second a copy of the first 40 ms later
    ops = ops + [(n, s + 40 * ms, e + 40 * ms) for n, s, e in ops]
    modules = [("jit_train_step", 0.0, 30 * ms),
               ("jit_train_step", 40 * ms, 70 * ms)]
    cell = types.SimpleNamespace(reference=full.reference,
                                 config=full.config, traffic=full.traffic,
                                 layer_reader=full.layer_reader)
    if with_view:
        cell.program_view = {"hlo_text": _HLO, "tokens_per_step": 8192,
                             "moe": {}}
    return {"cell": cell, "raw": {"steps": 7},
            "trace": trace.Trace([trace.DeviceTimeline(0, ops, modules)], []),
            "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite")}


def _read(full, name, ctx):
    return full.layer_reader(name)(ctx)


def test_device_ms_per_step_by_layer_kind_and_by_exit_scope(full):
    ctx = _ctx(full)
    assert _read(full, "loop.attn_device_ms_per_step", ctx) == \
        pytest.approx(10.0)
    assert _read(full, "loop.ffn_device_ms_per_step", ctx) == \
        pytest.approx(6.0)
    # gate 1 + head 5 (the while and its body once) + mixing 2
    assert _read(full, "loop.exits_device_ms_per_step", ctx) == \
        pytest.approx(8.0)
    assert hlo_ops.ms_per_step_under(ctx, "loop.body") == pytest.approx(17.0)


def test_the_roofline_share_counts_every_pass(full):
    ctx = _ctx(full)
    one = full.reference.rattn_attend_cost(full.config, 8192)
    # 8 layers x 4 passes, the forward twice and a backward of 2.5, 8 ms
    least = one["flops"] / 197e12 * 4.5 * 8 * 4
    assert _read(full, "loop.attend_roofline_pct", ctx) == pytest.approx(
        100 * least / 8e-3)
    # 136 pairs x 16 heads x 32 applications x 4.5 forwards: 42 TFLOP
    assert 41e12 < one["flops"] * 4.5 * 32 < 43e12


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_view_reports_nothing(full, name):
    """On a parent that lacks what this PR adds the readers return None
    and do not raise."""
    assert _read(full, name, _ctx(full, with_view=False)) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_manifest_lists_each_new_metric_for_this_cell_alone(full, name):
    manifest = loader.load_manifest(bench_paths.ROOT)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_items_per_s"
    reader = loader.import_file(
        f"{bench_paths.ROOT}/benchmark/layer_metrics/{name}.py",
        "layer_metric")
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert name in full.cell["per_layer"]


def test_the_configuration_file_states_the_cut(full):
    cfg = loader.read_json(f"{bench_paths.ROOT}/benchmark/configs/"
                           f"{CONFIG}.json")
    catalog = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    for key, value in catalog.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["deployment"]["pipeline_stages"] == 6
    assert cfg["deployment"]["layers_a_stage"] * 6 == 48
    assert "between the block and the norm" in cfg["deployment"]["loop"]
    for key in ("sandwich_norms", "final_norm_every_pass", "exit_gate",
                "objective", "entropy_weight", "left_out", "sequence_length",
                "weights", "compute_dtype", "updater"):
        assert key in cfg["assumed"], key
    assert cfg["entropy_weight"] == 0.05
    assert cfg["updater"]["learning_rate"] == 1e-5
    assert set(full.cell["limits"]) == {"loss", "grad_norm_worst",
                                        "grad_norm_median",
                                        "delta_norm_worst"}
    assert len(full.cell["limits"]["loss"]) == 3
    assert set(full.cell["limits_why"]) >= {"readings", "loss",
                                            "grad_norm_worst",
                                            "grad_norm_median",
                                            "delta_norm_worst", "control"}
    assert full.cell["traffic"] == "fit_tokens_1x8192"
    # the manifest's entries for this configuration and cell
    manifest = loader.load_manifest(bench_paths.ROOT)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"]
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "fit_tokens_1x8192", 1)]
    assert manifest["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in manifest["per_layer"][-4:]] == NEW_METRICS

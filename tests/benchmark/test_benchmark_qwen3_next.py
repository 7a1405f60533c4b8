"""The ``qwen3_next_80b_a3b_ep16`` configuration: the program against its
plain reference on the CPU at the file's ``rehearse`` size in float32
(forward logits, loss, every gradient leaf, each layer kind alone, the 16
shares against the uncut routed layer), the cell through its driver with
the float8 control failing, the scopes in the compiled step, the hand
counts of parameters and FLOPs at the published widths, and each new
per-layer reader on a synthetic trace."""

import json
import math
import re
import types

import numpy as np
import pytest

import bench_paths
from harness import feed, flops, hlo_ops, loader, peaks, trace

CELL = "qwen3_next_train_8k_ep16share"
CONFIG = "qwen3_next_80b_a3b_ep16"
# float32 on the CPU, two orders of the same sums through four blocks
FORWARD_TOL = 5e-6      # softmax outputs, absolute
LOSS_TOL = 2e-6         # relative
GRAD_TOL = 1e-4         # a leaf's max |difference| over its max |value|
NEW_METRICS = ["gdn.device_ms_per_step", "gdn.scan_roofline_pct",
               "gattn.device_ms_per_step", "gattn.attend_roofline_pct",
               "moe512.device_ms_per_step", "moe512.experts_roofline_pct",
               "moe512.expert_load_max_over_mean"]


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
    chunk (64), of the attention tile (64) or of the loss block (64)."""
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
        probs_p = net.output(x)[0]
        probs_r = jax.nn.softmax(ref.logits(cfg, p0, jnp.asarray(x)), -1)
    return types.SimpleNamespace(
        cfg=cfg, ref=ref, net=net, p0=p0, x=x, y=y,
        loss_p=float(loss_p), loss_r=float(loss_r),
        grads_p={f"{v}/{k}": a for v, leaves in grads_p.items()
                 for k, a in leaves.items()},
        grads_r=grads_r, probs_p=np.asarray(probs_p),
        probs_r=np.asarray(probs_r))


def _reference_module():
    return loader.import_file(
        f"{bench_paths.ROOT}/benchmark/references/{CONFIG}.py", "reference")


def _rehearse_leaves():
    cfg = loader.read_json(f"{bench_paths.ROOT}/benchmark/configs/"
                           f"{CONFIG}.json")
    return list(_reference_module().param_shapes({**cfg, **cfg["rehearse"]}))


def test_forward_and_loss_follow_the_reference(sides):
    assert np.max(np.abs(sides.probs_p - sides.probs_r)) < FORWARD_TOL
    assert abs(sides.loss_p - sides.loss_r) < LOSS_TOL * abs(sides.loss_r)
    assert abs(sides.loss_r - math.log(sides.cfg["vocab_size"])) < 1.0


@pytest.mark.parametrize("leaf", _rehearse_leaves())
def test_every_gradient_leaf_follows_the_reference(sides, leaf):
    got, want = np.asarray(sides.grads_p[leaf]), np.asarray(
        sides.grads_r[leaf])
    assert got.shape == want.shape and np.all(np.isfinite(got))
    assert np.max(np.abs(want)) > 0, "a leaf with no gradient tests nothing"
    assert np.max(np.abs(got - want)) < GRAD_TOL * np.max(np.abs(want))


@pytest.mark.parametrize("kind", ["gdn", "gattn", "moe"])
def test_each_layer_kind_alone_follows_the_reference(sides, kind):
    """One layer's ``apply`` on the reference's leaves against the
    reference's function of the same name: output and the gradient of
    every leaf and of the input."""
    import jax
    import jax.numpy as jnp

    ref, cfg = sides.ref, sides.cfg
    blk = next(b for b in ref.blocks(cfg)
               if kind in (b["attn"], b["ffn"]))
    vertex = blk["name"] + ("_ffn" if kind == "moe" else "_attn")
    layer = sides.net.vertices[vertex][0]
    own = {k.split("/")[1]: v for k, v in sides.p0.items()
           if k.startswith(vertex + "/")}
    x = jax.random.normal(jax.random.key(3), (2, 150, cfg["hidden_size"]))

    def program(own, x):
        return layer.apply(own, sides.net.state[vertex], x)[0]

    def reference(own, x):
        return getattr(ref, kind)(
            ref.dims(cfg), {vertex + "/" + k: v for k, v in own.items()},
            vertex + "/", x, "highest")

    def run(fn):
        def loss(own, x):
            o = fn(own, x)
            return jnp.sum(jnp.sin(o)), o
        return jax.value_and_grad(loss, (0, 1), has_aux=True)(own, x)

    with jax.default_matmul_precision("highest"):
        got, want = run(program), run(reference)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * max(
            1.0, float(jnp.max(jnp.abs(b))))


def test_the_sixteen_shares_add_up_to_the_uncut_reference_layer():
    """The guide's shares test: the PROGRAM's sixteen shares of two experts
    each, the gated shared expert counted once (on the first share), add
    up to what the REFERENCE gives for the whole layer of 32 experts."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.conf.experts import RoutedExperts

    ref = _reference_module()
    m = {"d": 24, "experts_total": 32, "experts_held": 32, "expert_offset": 0,
         "top_k": 5, "expert_ff": 16, "shared_ff": 16}
    shapes = {"Wr": (24, 32), "Wgate": (32, 24, 16), "Wup": (32, 24, 16),
              "Wdown": (32, 16, 24), "Sgate": (24, 16), "Sup": (24, 16),
              "Sdown": (16, 24), "Wsg": (24, 1)}
    keys = jax.random.split(jax.random.key(0), len(shapes))
    p = {n: jax.random.normal(k, s) / math.sqrt(s[-2])
         for k, (n, s) in zip(keys, shapes.items())}
    x = jax.random.normal(jax.random.key(1), (2, 40, 24))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(m, {"f/" + n: a for n, a in p.items()}, "f/", x,
                       "highest")
        total = jnp.zeros_like(want)
        it = InputType.recurrent(24, 40)
        for share in range(16):
            first = share == 0
            layer = RoutedExperts(
                n_experts=32, experts_held=2, expert_offset=2 * share,
                top_k=5, expert_size=16, shared_size=16 if first else 0,
                router_activation="softmax", shared_gate=first)
            own = {n: (a[2 * share:2 * share + 2]
                       if n in ("Wgate", "Wup", "Wdown") else a)
                   for n, a in p.items()
                   if first or n in ("Wr", "Wgate", "Wup", "Wdown")}
            part, state = layer.apply(own, layer.init(jax.random.key(0),
                                                      it)[1], x)
            assert int(state["pairs_dropped"]) == 0
            total = total + part
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5 * max(
        1.0, float(jnp.max(jnp.abs(want))))


def test_the_cell_runs_through_its_driver_and_the_control_fails(cell, tmp_path):
    """Set-up's first steps through ``net.fit(DevicePrefetchIterator)``,
    the reference after them: ``correct`` in float32 within the cell's
    limits, the float8 control outside one of them, no pair dropped, the
    counters read."""
    import jax

    quiet = lambda *a: None
    session = cell.driver.setup(cell, jax.devices()[:1], 2_147_483_999, quiet)
    raw = cell.driver.run_window(session, 0.3, None)
    assert raw["steps"] > 0 and raw["compiles_in_window"] == 0
    assert raw["failed"] == 0 and raw["moe_dropped_tokens_total"] == 0
    assert raw["items"] == raw["steps"] * 2 * 128
    assert sum(raw["moe_pairs_held_in_window"].values()) > 0
    view = cell.program_view
    assert set(view["moe"]) == {"l0_ffn", "l1_ffn", "l2_ffn", "l3_ffn"}
    # a traced window on the same session: the text is the executable's own
    took = feed.TraceSlice(str(tmp_path), 0.05, 0.05)
    raw = cell.driver.run_window(session, 0.4, took)
    view = cell.program_view
    assert took.done and raw["compiles_for_hlo_text"] == 0
    assert raw["compiles_in_window"] == 0 and raw["failed"] == 0
    assert "GatedDeltaNet:l0_attn" in view["hlo_text"]
    assert "GatedAttention:l3_attn" in view["hlo_text"]
    assert 0 < view["moe_slice"]["steps"] <= raw["steps"]
    ok, rows = cell.driver.check(session, quiet)
    assert ok, rows
    ok, rows = cell.driver.control(session, quiet)
    assert not ok, rows


SCOPES = ["gdn.conv", "gdn.scan", "gdn.out_gate", "gattn.qk_norm_rope",
          "gattn.attend", "gattn.out_gate", "moe.route", "moe.dispatch",
          "moe.experts", "moe.shared", "moe.shared_gate", "loss.blocked"]


@pytest.fixture(scope="module")
def step_op_names(sides):
    """``op_name``s of the compiled train step at the rehearse size."""
    import jax

    net = sides.net

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    args = (struct(net.params), struct(net.state), struct(net.opt_state),
            struct(net._rng), [struct(sides.x)], [struct(sides.y)], None, None)
    text = net._get_jitted("train").lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", SCOPES)
def test_every_scope_is_in_the_compiled_step_forward_and_backward(
        step_op_names, scope):
    layer = {"gdn": "GatedDeltaNet:", "gattn": "GatedAttention:",
             "moe": "RoutedExperts:", "loss": ""}[scope.split(".")[0]]
    under = [o for o in step_op_names if scope in o and layer in o]
    assert any("transpose(" not in o for o in under), scope
    assert any("transpose(" in o for o in under), scope


# ------------------------------------------------------------- hand counts
def test_parameter_hand_count_at_the_published_widths(full):
    """ISSUE 30's table, reckoned again: every width as published, 4 of 48
    layers, 32 of 512 experts, 18,992 of 151,936 rows."""
    d = 2048
    gdn = (d * (2 * 16 * 128 + 2 * 32 * 128) + d * 64 + 4 * (2 * 2048 + 4096)
           + 32 + 32 + 128 + 4096 * d)
    gattn = d * 16 * 2 * 256 + 2 * d * 2 * 256 + 2 * 256 + 16 * 256 * d
    expert = 3 * d * 512
    routed = d * 512 + 32 * expert + expert + d     # router, held, shared, gate
    norms = 2 * d
    total = 3 * (gdn + routed + norms) + (gattn + routed + norms) \
        + 2 * 18992 * d + d
    assert (gdn, gattn, routed) == (33_718_464, 27_263_488, 104_859_648)
    assert gdn + routed + norms == 138_582_208
    assert gattn + routed + norms == 132_127_232
    assert total == 625_667_136
    assert full.reference.count_params(full.config) == total
    assert [b["attn"] + "+" + b["ffn"] for b in
            full.reference.blocks(full.config)] == [
        "gdn+moe", "gdn+moe", "gdn+moe", "gattn+moe"]


def test_the_zoo_builder_draws_that_many_from_the_public_keys(full):
    """``models.Qwen3Next`` from the public config's keys alone, cut by its
    arguments: registered layers of the right kinds, and (shapes only,
    nothing drawn) 625,667,136 parameters; the whole published model
    80 billion."""
    import jax
    from deeplearning4j_tpu.models import Qwen3Next
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    public = full.adapter.public_config(full.config)
    assert (public["num_hidden_layers"], public["num_experts"],
            public["vocab_size"]) == (48, 512, 151936)

    def count(zoo):
        net = ComputationGraph(zoo.conf())
        kinds = [type(net.vertices[f"l{i}_attn"][0]).__name__
                 for i in range(zoo.layers)]
        drawn = jax.eval_shape(net._draw, jax.random.key(0))[0]
        return kinds, sum(math.prod(a.shape)
                          for a in jax.tree_util.tree_leaves(drawn))

    kinds, n = count(Qwen3Next(public, layers=4, experts_held=32,
                               vocab_rows=18992, sequence_length=8192))
    assert kinds == ["GatedDeltaNet"] * 3 + ["GatedAttention"]
    assert n == 625_667_136
    kinds, n = count(Qwen3Next(public))
    assert kinds == (["GatedDeltaNet"] * 3 + ["GatedAttention"]) * 12
    assert 79.5e9 < n < 80.5e9


def test_flop_hand_count_at_the_published_widths(full):
    """Forward matrix-product FLOPs a token at T = 8192."""
    d, t = 2048, 8192
    gdn = 2 * (d * 12288 + d * 64 + 3 * 32 * 128 * 128 + 4096 * d)
    gattn = 2 * (d * 8192 + d * 1024 + 16 * (256 + 256) * (t + 1) / 2
                 + 4096 * d)
    routed = 2 * (d * 512 + 3 * d * 512 + d + 3 * d * 512 * 10 * 32 / 512)
    want = 3 * gdn + gattn + 4 * routed + 2 * d * 18992
    got = flops.forward_flops_per_item(full.reference.layers(full.config))
    assert got == pytest.approx(want, rel=1e-12)
    assert all(layer["kind"] == "dense"
               for layer in full.reference.layers(full.config))
    # 1.38 GFLOP a token to train, 11.3 TFLOP a step of 8192 tokens
    assert 1.37e9 < 3 * got < 1.39e9


def test_kernel_cost_functions(full):
    ref, cfg = full.reference, full.config
    scan = ref.gdn_scan_cost(cfg, 8192)
    # the matrix products alone: 4 C^2 K + C^2 (K + V) + 6 C K V + 2 C^2 V
    c, k = 64, 128
    products = 32 * (8192 / c) * (4 * c * c * k + c * c * 2 * k
                                  + 6 * c * k * k + 2 * c * c * k)
    assert products < scan["flops"] < 1.2 * products
    # q, k, v of 32 heads in bfloat16, the spread decay and the output in
    # float32, b
    assert scan["bytes"] == 8192 * 32 * (2 * 3 * 128 + 4 * 128 + 4 + 4 * 128)
    attend = ref.gattn_attend_cost(cfg, 8192)
    # 16 query heads, 136 tile pairs of 512 x 512, two products of width 256
    assert attend["flops"] == 16 * 136 * 2 * 2 * 512 * 512 * 256
    # between the causal half of the whole triangle and the whole square
    whole = 16 * 2 * 2 * 8192 * 8192 * 256
    assert whole / 2 < attend["flops"] < 0.54 * whole
    assert attend["bytes"] > 16 * 136 * 2 * 512 * 256 * 2    # k, v a pair
    assert attend["flops"] / 197e12 > attend["bytes"] / 819e9   # MXU bound
    moe = ref.moe_experts_cost(cfg, 5120, 32)
    assert moe["flops"] == 5120 * 3 * 2 * 2048 * 512
    assert moe["bytes"] > 32 * 3 * 2048 * 512 * 2       # the weights, bf16
    assert moe["bytes"] / 819e9 > moe["flops"] / 197e12     # bytes bound


# ------------------------------------------------------------ the readers
_HLO = '''
HloModule jit_train_step
%fused_computation.1 { ... }
ENTRY %main {
  %custom-call.1 = f32[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(GatedDeltaNet:l0_attn)/gdn.scan/pallas_call" source_file="x.py" source_line=1}
  %fusion.9 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(GatedDeltaNet:l0_attn)/gdn.scan/broadcast_in_dim"}
  %fusion.2 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(GatedDeltaNet:l1_attn))/gdn.conv/mul"}
  %custom-call.3 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(GatedAttention:l3_attn)/gattn.attend/pallas_call"}
  %fusion.8 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(GatedAttention:l3_attn)/gattn.qk_norm_rope/mul"}
  %custom-call.4 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(RoutedExperts:l0_ffn)/moe.experts/pallas_call"}
  ROOT %fusion.5 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(RoutedExperts:l0_ffn)/moe.shared/moe.shared_gate/mul"}
  %fusion.6 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/adam/mul"}
}
'''


def _ctx(full, with_view=True):
    ms = 1e-3
    ops = [("%custom-call.1 = f32[8]{0} custom-call(%p0)", 0 * ms, 8 * ms),
           ("%fusion.9 = f32[8]{0} fusion(%p0)", 8 * ms, 10 * ms),
           ("%fusion.2 = f32[8]{0} fusion(%p0)", 10 * ms, 14 * ms),
           ("%custom-call.3 = bf16[8]{0} custom-call(%p0)", 14 * ms, 19 * ms),
           ("%fusion.8 = bf16[8]{0} fusion(%p0)", 19 * ms, 20 * ms),
           ("%custom-call.4 = bf16[8]{0} custom-call(%p0)", 20 * ms, 22 * ms),
           ("%fusion.5 = f32[8]{0} fusion(%p0)", 22 * ms, 23 * ms),
           ("%fusion.6 = f32[8]{0} fusion(%p0)", 23 * ms, 30 * ms)]
    # two steps, the second a copy of the first 40 ms later
    ops = ops + [(n, s + 40 * ms, e + 40 * ms) for n, s, e in ops]
    modules = [("jit_train_step", 0.0, 30 * ms),
               ("jit_train_step", 40 * ms, 70 * ms)]
    cell = types.SimpleNamespace(reference=full.reference,
                                 config=full.config, traffic=full.traffic,
                                 layer_reader=full.layer_reader)
    if with_view:
        tokens = [250] + [150] * 30 + [250]
        cell.program_view = {
            "hlo_text": _HLO, "tokens_per_step": 8192,
            "moe": {"l0_ffn": {"expert_tokens": tokens,
                               "pairs_held": sum(tokens),
                               "pairs_dropped": 0}}}
        # the slice's own steps: two of them, 5000 pairs each
        cell.program_view["moe_slice"] = {"steps": 2, "layers": {
            "l0_ffn": {"expert_tokens": [2 * n for n in tokens],
                       "pairs_held": 2 * sum(tokens), "pairs_dropped": 0}}}
    return {"cell": cell, "raw": {"steps": 7},
            "trace": trace.Trace([trace.DeviceTimeline(0, ops, modules)], []),
            "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite")}


def _read(full, name, ctx):
    return full.layer_reader(name)(ctx)


def test_device_ms_per_step_by_layer_kind(full):
    ctx = _ctx(full)
    assert _read(full, "gdn.device_ms_per_step", ctx) == pytest.approx(14.0)
    assert _read(full, "gattn.device_ms_per_step", ctx) == pytest.approx(6.0)
    assert _read(full, "moe512.device_ms_per_step", ctx) == pytest.approx(3.0)
    # the nested scope is found by either name
    assert hlo_ops.ms_per_step_under(ctx, "moe.shared_gate") == \
        pytest.approx(1.0)
    assert hlo_ops.ms_per_step_under(ctx, "moe.shared") == pytest.approx(1.0)


def test_roofline_shares_are_least_time_over_measured_time(full):
    ctx = _ctx(full)
    ref, cfg = full.reference, full.config
    one = ref.gdn_scan_cost(cfg, 8192)
    # three layers, four forwards' worth a step, 10 ms under gdn.scan
    least = max(one["flops"] / 197e12, one["bytes"] / 819e9) * 3 * 4
    assert _read(full, "gdn.scan_roofline_pct", ctx) == pytest.approx(
        100 * least / 10e-3)
    one = ref.gattn_attend_cost(cfg, 8192)
    # one layer, the forward twice and a backward of 2.5 forwards, 5 ms
    least = one["flops"] / 197e12 * 4.5
    assert _read(full, "gattn.attend_roofline_pct", ctx) == pytest.approx(
        100 * least / 5e-3)
    moe = ref.moe_experts_cost(cfg, 5000, 32)
    least = max(moe["flops"] / 197e12, moe["bytes"] / 819e9) * 4
    assert _read(full, "moe512.experts_roofline_pct", ctx) == pytest.approx(
        100 * least / 2e-3)


def test_expert_load_is_the_busiest_over_the_mean(full):
    assert _read(full, "moe512.expert_load_max_over_mean",
                 _ctx(full)) == pytest.approx(250 / (5000 / 32))


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
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False}
    for key, value in catalog.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 32, 18992)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert cfg["num_experts"] * 16 == cfg["published"]["num_experts"]
    for key in ("sequence_length", "weights", "auxiliary_loss", "mtp_block",
                "fused_projection_columns", "updater"):
        assert key in cfg["assumed"], key
    assert cfg["updater"]["learning_rate"] == 1e-5
    assert set(full.cell["limits"]) == {"loss", "grad_norm_worst",
                                        "grad_norm_median",
                                        "delta_norm_worst"}
    assert len(full.cell["limits"]["loss"]) == 3
    assert full.cell["traffic"] == "fit_tokens_1x8192"
    assert json.dumps(full.traffic).count("8192") >= 2
    # the manifest's entries for this configuration and cell
    manifest = loader.load_manifest(bench_paths.ROOT)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"]
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "fit_tokens_1x8192", 1)]

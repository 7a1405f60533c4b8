"""The ``kimi_linear_48b_a3b_ep32`` configuration: the program against its
plain reference on the CPU at the file's ``rehearse`` size in float32
(forward logits, loss, every gradient leaf, each layer kind alone), the
cell through its driver, the hand counts of parameters and FLOPs at the
published widths, and each new per-layer reader on a synthetic trace."""

import json
import math
import types

import numpy as np
import pytest

import bench_paths
from harness import feed, flops, hlo_ops, loader, peaks, trace

CELL = "kimi_linear_train_8k_ep32share"
# float32 on the CPU, two orders of the same sums through five blocks
FORWARD_TOL = 5e-6      # softmax outputs, absolute
LOSS_TOL = 2e-6         # relative
GRAD_TOL = 1e-4         # a leaf's max |difference| over its max |value|


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


def _rehearse_leaves():
    cfg = loader.read_json(bench_paths.ROOT + "/benchmark/configs/"
                           "kimi_linear_48b_a3b_ep32.json")
    cfg = {**cfg, **cfg["rehearse"]}
    ref = loader.import_file(bench_paths.ROOT + "/benchmark/references/"
                             "kimi_linear_48b_a3b_ep32.py", "reference")
    return list(ref.param_shapes(cfg))


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


@pytest.mark.parametrize("kind", ["kda", "mla", "moe"])
def test_each_layer_kind_alone_follows_the_reference(sides, kind):
    """One layer's ``apply`` on the reference's leaves against the
    reference's function of the same name."""
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
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(own, sides.net.state[vertex], x)
        want = getattr(ref, kind)(ref.dims(cfg), sides.p0, vertex + "/", x,
                                  "highest")
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * max(
        1.0, float(jnp.max(jnp.abs(want))))


def test_the_cell_runs_through_its_driver_and_the_control_fails(cell, tmp_path):
    """Set-up's first steps through ``net.fit(DevicePrefetchIterator)``,
    the reference after them: bf16 compute against float32 within the
    cell's limits, the float8 control outside one of them, no pair
    dropped, the counters read."""
    import jax

    quiet = lambda *a: None
    session = cell.driver.setup(cell, jax.devices()[:1], 2_147_483_999, quiet)
    raw = cell.driver.run_window(session, 0.3, None)
    assert raw["steps"] > 0 and raw["compiles_in_window"] == 0
    assert raw["failed"] == 0 and raw["moe_dropped_tokens_total"] == 0
    assert raw["items"] == raw["steps"] * 2 * 128
    assert sum(raw["moe_pairs_held_in_window"].values()) > 0
    view = cell.program_view
    assert set(view["moe"]) == {"l2_ffn", "l3_ffn", "l4_ffn", "l5_ffn"}
    assert "hlo_text" not in view and "moe_slice" not in view
    # a traced window on the same session: the text is the executable's own
    # (no compile for it), the slice's steps and pairs are its own
    took = feed.TraceSlice(str(tmp_path), 0.05, 0.05)
    raw = cell.driver.run_window(session, 0.4, took)
    view = cell.program_view
    assert took.done and raw["compiles_for_hlo_text"] == 0
    assert raw["compiles_in_window"] == 0 and raw["failed"] == 0
    assert "KimiDeltaAttention:l1_attn" in view["hlo_text"]
    assert 0 < view["moe_slice"]["steps"] <= raw["steps"]
    for layer, counts in view["moe_slice"]["layers"].items():
        assert 0 <= counts["pairs_held"] <= view["moe"][layer]["pairs_held"]
        assert counts["pairs_dropped"] == 0
    ok, rows = cell.driver.check(session, quiet)
    assert ok, rows
    ok, rows = cell.driver.control(session, quiet)
    assert not ok, rows


# ------------------------------------------------------------- hand counts
def test_parameter_hand_count_at_the_published_widths(full):
    """ISSUE 26's table, reckoned again: every width as published, 5 of 27
    layers, 8 of 256 experts, 20,480 of 163,840 rows."""
    d, inner, r = 2304, 32 * 128, 128
    kda = (3 * d * inner + inner * d + 2 * (d * r + r * inner) + d * 32
           + 3 * inner * 4 + 32 + inner + 128)
    mla = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 32 * 128 * d
    expert = 3 * d * 1024
    routed = d * 256 + 8 * expert + expert          # router, held, shared
    dense = 3 * d * 9216
    norms = 2 * d
    total = ((kda + dense + norms) + 3 * (kda + routed + norms)
             + (mla + routed + norms) + 2 * 20480 * d + d)
    assert round(kda / 1e6, 1) == 39.5 and round(mla / 1e6, 1) == 29.1
    assert round(expert / 1e6, 2) == 7.08
    assert total == 602_433_408
    assert full.reference.count_params(full.config) == total
    assert [b["attn"] + "+" + b["ffn"] for b in
            full.reference.blocks(full.config)] == [
        "kda+dense", "kda+moe", "kda+moe", "mla+moe", "kda+moe"]


def test_flop_hand_count_at_the_published_widths(full):
    """Forward matrix-product FLOPs a token at T = 8192."""
    d, inner, r, t = 2304, 4096, 128, 8192
    kda = 2 * (d * 3 * inner + 2 * (d * r + r * inner) + d * 32
               + 3 * 32 * 128 * 128 + inner * d)
    mla = 2 * (d * 32 * 192 + d * 576 + 512 * 32 * 256
               + 32 * (192 + 128) * (t + 1) / 2 + 4096 * d)
    routed = 2 * (d * 256 + 3 * d * 1024 + 3 * d * 1024 * 8 * 8 / 256)
    dense = 2 * 3 * d * 9216
    want = 4 * kda + mla + dense + 4 * routed + 2 * d * 20480
    got = flops.forward_flops_per_item(full.reference.layers(full.config))
    assert got == pytest.approx(want, rel=1e-12)
    assert all(layer["kind"] == "dense"
               for layer in full.reference.layers(full.config))
    # 2.30 GFLOP a token to train, 18.9 TFLOP a step of 8192 tokens
    assert 2.29e9 < 3 * got < 2.31e9


def test_kernel_cost_functions(full):
    ref, cfg = full.reference, full.config
    scan = ref.kda_scan_cost(cfg, 8192)
    # the matrix products alone: 4 C^2 K + C^2 (K + V) + 6 C K V + 2 C^2 V
    c, k = 64, 128
    products = 32 * (8192 / c) * (4 * c * c * k + c * c * 2 * k
                                  + 6 * c * k * k + 2 * c * c * k)
    assert products < scan["flops"] < 1.2 * products
    # q, k, v in bfloat16, g and the output in float32, b
    assert scan["bytes"] == 8192 * 32 * (2 * 3 * 128 + 4 * 128 + 4 + 4 * 128)
    moe = ref.moe_experts_cost(cfg, 2048, 8)
    assert moe["flops"] == 2048 * 3 * 2 * 2304 * 1024
    assert moe["bytes"] > 8 * 3 * 2304 * 1024 * 2       # the weights, bf16


# ------------------------------------------------------------ the readers
_HLO = '''
HloModule jit_train_step
%fused_computation.1 { ... }
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(KimiDeltaAttention:l1_attn)/kda.scan/while/body/dot_general" source_file="x.py" source_line=1}
  %while.7 = (f32[8]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(train_step)/jvp(KimiDeltaAttention:l1_attn)/kda.scan/while"}
  %fusion.2 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(KimiDeltaAttention:l2_attn))/kda.conv/mul"}
  %fusion.3 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(MultiHeadLatentAttention:l4_attn)/mla.attend/dot_general"}
  %custom-call.4 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(RoutedExperts:l2_ffn)/moe.experts/pallas_call"}
  ROOT %fusion.5 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(RoutedExperts:l2_ffn)/moe.route/top_k"}
  %fusion.6 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/adam/mul"}
}
'''


def _ctx(full, with_view=True):
    ms = 1e-3
    ops = [("%while.7 = (f32[8]{0}) while(%t)", 0 * ms, 10 * ms),
           ("%fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop", 1 * ms, 9 * ms),
           ("%fusion.2 = f32[8]{0} fusion(%p0)", 10 * ms, 14 * ms),
           ("%fusion.3 = bf16[8]{0} fusion(%p0)", 14 * ms, 20 * ms),
           ("%custom-call.4 = bf16[8]{0} custom-call(%p0)", 20 * ms, 22 * ms),
           ("%fusion.5 = f32[8]{0} fusion(%p0)", 22 * ms, 23 * ms),
           ("%fusion.6 = f32[8]{0} fusion(%p0)", 23 * ms, 30 * ms)]
    # two steps, the second a copy of the first 40 ms later
    ops = ops + [(n, s + 40 * ms, e + 40 * ms) for n, s, e in ops]
    modules = [("jit_train_step", 0.0, 30 * ms),
               ("jit_train_step", 40 * ms, 70 * ms)]
    cell = types.SimpleNamespace(reference=full.reference,
                                 config=full.config)
    if with_view:
        cell.program_view = {
            "hlo_text": _HLO, "tokens_per_step": 8192,
            "moe": {"l2_ffn": {"expert_tokens": [300, 100, 200, 200, 200,
                                                 200, 200, 200],
                               "pairs_held": 1600, "pairs_dropped": 0}}}
        # the slice's own steps: two of them, 1600 pairs each
        cell.program_view["moe_slice"] = {"steps": 2, "layers": {
            "l2_ffn": {"expert_tokens": [600, 200, 400, 400, 400, 400, 400,
                                         400],
                       "pairs_held": 3200, "pairs_dropped": 0}}}
    return {"cell": cell, "raw": {"steps": 7},
            "trace": trace.Trace([trace.DeviceTimeline(0, ops, modules)], []),
            "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite")}


def _read(full, name, ctx):
    return full.layer_reader(name)(ctx)


def test_scopes_are_read_from_the_hlo_text():
    by_name = hlo_ops.scopes(_HLO)
    assert "KimiDeltaAttention:l1_attn)/kda.scan" in by_name["fusion.1"]
    assert "RoutedExperts:l2_ffn" in by_name["custom-call.4"]
    assert "moe.route" in by_name["fusion.5"]          # a ROOT instruction
    assert hlo_ops.instruction_of(
        "%custom-call.4 = bf16[8]{0} custom-call(%p0)") == "custom-call.4"


def test_device_ms_per_step_by_layer_kind(full):
    ctx = _ctx(full)
    # the while and its body overlap: counted once (10 ms), plus kda.conv
    assert _read(full, "kda.device_ms_per_step", ctx) == pytest.approx(14.0)
    assert _read(full, "mla.device_ms_per_step", ctx) == pytest.approx(6.0)
    assert _read(full, "moe.device_ms_per_step", ctx) == pytest.approx(3.0)
    busy_ms = 1e3 * trace.busy_seconds(ctx["trace"])[0] / 2
    assert 14.0 + 6.0 + 3.0 <= busy_ms


def test_roofline_shares_are_least_time_over_measured_time(full):
    ctx = _ctx(full)
    ref, cfg = full.reference, full.config
    one = ref.kda_scan_cost(cfg, 8192)
    least = max(one["flops"] / 197e12, one["bytes"] / 819e9) * 4 * 4
    assert _read(full, "kda.scan_roofline_pct", ctx) == pytest.approx(
        100 * least / 10e-3)
    moe = ref.moe_experts_cost(cfg, 1600, 8)
    least = max(moe["flops"] / 197e12, moe["bytes"] / 819e9) * 4
    assert _read(full, "moe.experts_roofline_pct", ctx) == pytest.approx(
        100 * least / 2e-3)
    assert moe["bytes"] / 819e9 > moe["flops"] / 197e12     # bytes bound


def test_expert_load_is_the_busiest_over_the_mean(full):
    assert _read(full, "moe.expert_load_max_over_mean",
                 _ctx(full)) == pytest.approx(300 / 200)


@pytest.mark.parametrize("name", [
    "kda.device_ms_per_step", "mla.device_ms_per_step",
    "moe.device_ms_per_step", "kda.scan_roofline_pct",
    "moe.experts_roofline_pct", "moe.expert_load_max_over_mean"])
def test_a_program_without_the_view_reports_nothing(full, name):
    """On a parent that lacks what this PR adds the readers return None
    and do not raise."""
    assert _read(full, name, _ctx(full, with_view=False)) is None


def test_the_configuration_file_states_the_cut(full):
    cfg = loader.read_json(bench_paths.ROOT + "/benchmark/configs/"
                           "kimi_linear_48b_a3b_ep32.json")
    catalog_widths = {
        "hidden_size": 2304, "intermediate_size": 9216, "head_dim": 72,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "moe_intermediate_size": 1024,
        "num_experts_per_token": 8, "num_attention_heads": 32,
        "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5}
    for key, value in catalog_widths.items():
        assert cfg[key] == value, key
    assert cfg["linear_attn_config"]["head_dim"] == 128
    assert cfg["linear_attn_config"]["short_conv_kernel_size"] == 4
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 20480)
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32
    assert "kda_low_rank_width" in cfg["assumed"]
    assert set(full.cell["limits"]) == {"loss", "grad_norm_worst",
                                        "grad_norm_median",
                                        "delta_norm_worst"}
    assert len(full.cell["limits"]["loss"]) == 3
    assert json.dumps(full.traffic).count("8192") >= 2

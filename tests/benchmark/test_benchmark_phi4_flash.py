"""The ``phi4_mini_flash_pp6_vp8`` configuration: the program against its
plain reference on the CPU at the file's ``rehearse`` size in float32
(forward, loss, every gradient leaf, the reference's chain rule over pieces
with the shared values' cotangents against its own loss as one function),
the cell through its driver on two seeds with the float8 control and four
planted faults failing (one decay a channel, the lambda term dropped, the
memory read after the gate, the window left out), the scopes, counters and
owners of the compiled step, the hand counts of parameters, FLOPs and the
kernels' costs at the published widths, the configuration's file and the
manifest's entries BY NAME, and each new per-layer reader on a synthetic
trace."""

import contextlib
import dataclasses
import math
import re
import types

import numpy as np
import pytest

import bench_paths
from harness import feed, flops, loader, peaks, trace

CELL = "phi4_mini_flash_train_8k_pp6_vp8"
CONFIG = "phi4_mini_flash_pp6_vp8"
# float32 on the CPU, two orders of the same sums through six blocks
FORWARD_TOL = 5e-6      # softmax outputs, absolute
LOSS_TOL = 2e-6         # relative
GRAD_TOL = 3e-4         # a leaf's max |difference| over its max |value|
NEW_METRICS = ["mamba1.device_ms_per_step", "mamba1.scan_device_ms_per_step",
               "mamba1.scan_roofline_pct", "dattn.swa_device_ms_per_step",
               "dattn.full_device_ms_per_step",
               "dattn.cross_device_ms_per_step", "dattn.attend_roofline_pct",
               "gmu.device_ms_per_step", "ffn10240.device_ms_per_step",
               "tied25008.loss_device_ms_per_step"]
KINDS = ["mamba", "swa", "mamba_memory", "full_shared", "gmu", "cross"]
INDICES = [14, 15, 16, 17, 18, 19]
VERTICES = ["l14_ssm", "l15_attn", "l16_ssm", "l17_attn", "l18_gmu",
            "l19_attn"]
# key biases move no softmax: their gradient is rounding alone on both sides
NO_GRADIENT = {"l15_attn/bqkv": slice(64, 96), "l17_attn/bqkv": slice(64, 96)}


@pytest.fixture(scope="module")
def cell():
    return loader.resolve_cell(bench_paths.ROOT, CELL, rehearse=True)


@pytest.fixture(scope="module")
def full():
    return loader.resolve_cell(bench_paths.ROOT, CELL)


@pytest.fixture(scope="module")
def sides(cell):
    """The network and the reference on the same seeded weights and ids,
    with both sides' loss and gradients. T = 150: not a multiple of the
    scan's chunk (32), of the attention's tile (32) or of the loss block
    (64)."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        cfg = dict(cell.config, compute_dtype="float32")
        ref = cell.reference
        p0 = ref.init_params(cfg, 7)
        net = cell.build(cfg, dict(p0))
        ids = np.random.default_rng(0).integers(
            0, cfg["vocab_size"], (2, 151)).astype(np.int32)
        x, y = ids[:, :-1], ids[:, 1:]

        def program_loss(params):
            return net._loss_fn(params, net.state, [jnp.asarray(x)],
                                [jnp.asarray(y)], None, None, None)[0]

        loss_p, grads_p = jax.jit(jax.value_and_grad(program_loss))(
            net.params)
        loss_r, grads_r = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(cfg, p, jnp.asarray(x), jnp.asarray(y))))(p0)
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


def _config_file():
    return loader.read_json(f"{bench_paths.ROOT}/benchmark/configs/"
                            f"{CONFIG}.json")


def _rehearse_leaves():
    cfg = _config_file()
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
    if leaf in NO_GRADIENT:
        # the keys' bias shifts every score of a row alike
        assert (np.max(np.abs(want[NO_GRADIENT[leaf]]))
                < 1e-5 * np.max(np.abs(want)))


def test_the_two_sides_hold_the_same_leaves_and_the_tied_one_once(sides):
    flat = set(sides.grads_p)
    assert flat == set(sides.grads_r) == set(_rehearse_leaves())
    # the table, 6 x (2 norms + a SwiGLU), 2 Mamba, 2 attentions, a GMU, a
    # cross-attention, the final norm
    assert len(flat) == 1 + 6 * 7 + 2 * 9 + 2 * 9 + 2 + 9 + 2
    assert "embed/W" in flat and "head/W" not in flat
    assert sides.net.params["head"] == {}
    assert sides.net.vertices["head"][0].tied_to == "embed"
    assert not {"l19_attn/Wqkv", "l19_attn/Wk", "l19_attn/Wv"} & flat
    with pytest.raises(NotImplementedError):
        sides.ref.param_shapes({**sides.cfg, "tie_word_embeddings": False})
    with pytest.raises(NotImplementedError):
        sides.ref.param_shapes({**sides.cfg, "mamba_d_state": 32})
    with pytest.raises(NotImplementedError):
        sides.ref.param_shapes({**sides.cfg, "head_pairing": "halves"})


def test_the_rehearsal_holds_what_the_cell_is_for(cell, full):
    """The six layers in their published order with their published
    indices, four chunks of the scan with a carried state, four tiles of
    the attention with a window of one tile, two blocks of the loss."""
    small, big = cell.config, full.config
    for cfg in (small, big):
        assert [b["kind"] for b in cell.reference.blocks(cfg)] == KINDS
        assert [b["index"] for b in cell.reference.blocks(cfg)] == INDICES
        assert [b["vertex"] for b in cell.reference.blocks(cfg)] == VERTICES
        assert cfg["layer_kinds_kept"] == KINDS
    assert small["compute_dtype"] == "float32"
    t = small["sequence_length"]
    prog = small["program"]
    assert t // prog["scan_chunk"] >= 4
    assert t // prog["attention_block"] >= 4
    assert small["sliding_window"] == prog["attention_block"]
    assert t // prog["loss_block"] >= 2
    assert big["sliding_window"] == big["program"]["attention_block"] == 512
    assert (small["mamba_d_state"], small["mamba_d_conv"],
            small["mamba_expand"]) == (16, 4, 2)


def test_the_reference_s_pieces_are_its_loss_s_own_gradient(sides):
    """``loss_and_grads`` (block by block, the memory's and the shared keys
    and values' cotangents carried beside x's) against ``jax.grad`` of
    ``loss`` as one function."""
    import jax.numpy as jnp

    value, grads = sides.ref.loss_and_grads(
        sides.cfg, sides.p0, jnp.asarray(sides.x), jnp.asarray(sides.y))
    assert abs(float(value) - sides.loss_r) < 1e-6 * abs(sides.loss_r)
    assert set(grads) == set(sides.grads_r)
    for leaf, want in sides.grads_r.items():
        want = np.asarray(want)
        assert (np.max(np.abs(np.asarray(grads[leaf]) - want))
                <= 1e-4 * np.max(np.abs(want)) + 1e-12), leaf


STEP_COUNTERS = {"ssm.mamba1": 2, "kernel.xla_selective_scan": 2,
                 "ssm.gated_memory": 1, "attention.differential": 3,
                 "attention.differential_windowed": 1,
                 "attention.shared_kv": 1, "head.tied": 1,
                 "kernel.xla_blocked_attention": 3,
                 "loss.blocked_one_pass": 1}


@pytest.mark.parametrize("seed", [2_147_483_999, 2_150_000_123])
def test_the_cell_runs_through_its_driver_and_the_control_fails(cell, seed,
                                                                tmp_path):
    """Set-up's first steps through ``net.fit(DevicePrefetchIterator)``,
    the reference after them: ``correct`` in float32 within the cell's
    limits, the float8 control outside one of them, the step's trace-time
    counters read (one count a layer; on the CPU the ``jax.numpy`` tiles:
    the chip's step reads ``kernel.pallas_blocked_attention`` in their
    place)."""
    import jax

    quiet = lambda *a: None
    session = cell.driver.setup(cell, jax.devices()[:1], seed, quiet)
    took = feed.TraceSlice(str(tmp_path), 0.05, 0.05)
    raw = cell.driver.run_window(session, 0.4, took)
    view = cell.program_view
    assert raw["steps"] > 0 and raw["items"] == raw["steps"] * 2 * 128
    assert took.done and raw["compiles_for_hlo_text"] == 0
    assert raw["compiles_in_window"] == 0 and raw["failed"] == 0
    assert view["moe"] == {} and raw["moe_pairs_held_in_window"] == {}
    for vertex, kind in zip(VERTICES, KINDS):
        cls = {"m": "Mamba1Mixer", "g": "GatedMemoryUnit"}.get(
            kind[0], "DifferentialAttention")
        assert f"{cls}:{vertex}" in view["hlo_text"]
    counters = session.net.compile_watch.counters()
    assert {k: counters.get(k, 0) for k in STEP_COUNTERS} == STEP_COUNTERS
    assert counters.get("kernel.pallas_blocked_attention", 0) == 0
    ok, rows = cell.driver.check(session, quiet)
    assert ok, rows
    ok, rows = cell.driver.control(session, quiet)
    assert not ok, rows


@contextlib.contextmanager
def planted(fault: str):
    """The program with one fault in it, for as long as the block lasts
    (the network is built and its step traced inside set-up):

    * ``decay_one_a_channel``: ``A[c, n]`` replaced by its mean over n, so
      that a channel's 16 states decay alike: Mamba-2's decay in Mamba-1's
      place;
    * ``lambda_term_dropped``: every differential attention hands on ``A1``
      alone (``attention.differential`` without its second term);
    * ``memory_after_gate``: the value layer 16 hands the cross-decoder is
      ``y * SiLU(z)``, its scan output AFTER the gate;
    * ``window_left_out``: the window layers attend to every earlier key."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import Phi4Flash
    from deeplearning4j_tpu.nn.conf import attention, state_space

    if fault == "decay_one_a_channel":
        owner, name = state_space, "chunked_selective_scan"
        sound_scan = state_space.chunked_selective_scan

        def faulty(x, dt, a_rate, *rest, **kw):
            flat = jnp.broadcast_to(jnp.mean(a_rate, -1, keepdims=True),
                                    a_rate.shape)
            return sound_scan(x, dt, flat, *rest, **kw)
    elif fault == "lambda_term_dropped":
        owner, name = attention, "differential"

        def faulty(first, second, lam):
            return first
    elif fault == "memory_after_gate":
        owner, name = state_space.Mamba1Mixer, "apply"
        sound_apply = state_space.Mamba1Mixer.apply

        def faulty(self, params, state, x, **kw):
            out, st = sound_apply(self, params, state, x, **kw)
            if not self.share_scan:
                return out, st
            out, values = out
            gate = (x @ params["Win"])[..., params["Wdt"].shape[1]:]
            return (out, {"scan": state_space.gated_memory(
                values["scan"], gate)}), st
    elif fault == "window_left_out":
        owner, name = Phi4Flash, "conf"
        sound_conf = Phi4Flash.conf

        def faulty(self):
            conf = sound_conf(self)
            return dataclasses.replace(conf, vertices={
                n: ((dataclasses.replace(obj, window=0), ins)
                    if getattr(obj, "window", 0) else (obj, ins))
                for n, (obj, ins) in conf.vertices.items()})
    else:
        raise KeyError(fault)
    sound = getattr(owner, name)
    setattr(owner, name, faulty)
    try:
        yield
    finally:
        setattr(owner, name, sound)


# what each fault has to trip at the least, of the cell's own limits
FAULTS = {fault: {"grad_norm.median_leaf"}
          for fault in ("decay_one_a_channel", "lambda_term_dropped",
                        "memory_after_gate", "window_left_out")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_driver_s_check(cell, fault):
    """Set-up's first steps through ``net.fit`` with the fault in the
    program, then the driver's own ``check`` against the sound reference:
    not ``correct``, by the numbers the fault is there to move. That holds
    at this size in float32; the readings at the timed size on the chip
    stand in the cell's ``limits_why``."""
    import jax

    quiet = lambda *a: None
    with planted(fault):
        session = cell.driver.setup(cell, jax.devices()[:1], 2_147_484_123,
                                    quiet)
    ok, rows = cell.driver.check(session, quiet)
    tripped = {row["what"] for row in rows if not row["ok"]}
    assert not ok and FAULTS[fault] <= tripped, rows


SCOPES = ["mamba1.in_proj", "mamba1.conv", "mamba1.dt_bc", "mamba1.scan",
          "mamba1.gate_out", "gmu.gate", "dattn.qkv", "dattn.attend",
          "dattn.combine", "dattn.out", "loss.blocked"]


@pytest.fixture(scope="module")
def step_op_names(sides):
    """``op_name``s of the compiled train step at the rehearse size (the
    persistent cache off: its key leaves metadata out)."""
    import jax

    net = sides.net

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    args = (struct(net.params), struct(net.state), struct(net.opt_state),
            struct(net._rng), [struct(sides.x)], [struct(sides.y)], None, None)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        lowered = net._get_jitted("train").lower(*args)
        text = lowered.compile().as_text()
        debug = lowered.as_text(debug_info=True)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    return types.SimpleNamespace(
        compiled=set(re.findall(r'op_name="([^"]*)"', text)), lowered=debug)


@pytest.mark.parametrize("scope", SCOPES)
def test_every_scope_is_in_the_compiled_step_forward_and_backward(
        step_op_names, scope):
    layer = {"mamba1": "Mamba1Mixer:", "gmu": "GatedMemoryUnit:",
             "dattn": "DifferentialAttention:",
             "loss": ""}[scope.split(".")[0]]
    under = [o for o in step_op_names.compiled if scope in o and layer in o]
    assert any("transpose(" not in o for o in under), scope
    # the loss's gradients come out of its forward rule's one loop
    assert scope == "loss.blocked" or any("transpose(" in o for o in under)
    # and in the lowered text, before the compiler touched it
    assert scope in step_op_names.lowered
    assert layer in step_op_names.lowered


def test_every_operation_of_the_step_has_an_owner(step_op_names):
    """The two mixers, the three attentions and the memory unit are told
    apart by their markers, the norms are ``LayerNorm``'s, and nothing jax
    emitted lies outside an owner."""
    from deeplearning4j_tpu.obs.owners import owner_of
    from harness import layer_scopes

    emitted = [o for o in step_op_names.compiled
               if o.startswith("jit(") or re.match(r"[A-Za-z_]\w*:", o)]
    assert [o for o in emitted if owner_of(o) is None] == []
    assert {m for o in emitted for m in re.findall(
        r"Mamba1Mixer:(l\d+_ssm)", o)} == {"l14_ssm", "l16_ssm"}
    assert {m for o in emitted for m in re.findall(
        r"DifferentialAttention:(l\d+_attn)", o)} == {
        "l15_attn", "l17_attn", "l19_attn"}
    for vertex in ("l15_attn", "l17_attn", "l19_attn"):
        wanted = layer_scopes.under("DifferentialAttention", [vertex],
                                    "dattn.attend")
        mine = [o for o in emitted if wanted(o)]
        assert any("transpose(" in o for o in mine)
        assert any("transpose(" not in o for o in mine)
    owners = {owner_of(o) for o in emitted}
    assert {"LayerNorm", "GatedMemoryUnit", "Mamba1Mixer",
            "DifferentialAttention", "GatedFeedForward"} <= owners


def test_parameter_hand_count_at_the_published_widths(full):
    """By hand from the published widths: a Mamba layer 119,895,040, an
    attention layer 98,322,304, a gated-memory layer 104,867,840, a
    cross-attention layer 91,766,144 (each with its SwiGLU of 78,643,200
    and two norms of 2 x 2,560); the whole model's 8 + 1 + 7 pairs and the
    tied table of 200,064 rows 3,852,562,944; this chip's six layers and
    25,008 rows 697,094,272."""
    ref, cfg = full.reference, full.config
    d, inner, ff = 2560, 5120, 10240
    mlp_and_norms = 3 * d * ff + 4 * d
    mamba = (d * 2 * inner + 4 * inner + inner + inner * 192 + 160 * inner
             + inner + inner * 16 + inner + inner * d) + mlp_and_norms
    attn = (d * 5120 + 5120 + d * d + d + 4 * 64 + 128) + mlp_and_norms
    gmu = 2 * d * inner + mlp_and_norms
    cross = (d * d + d + d * d + d + 4 * 64 + 128) + mlp_and_norms
    assert (mamba, attn, gmu, cross) == (119_895_040, 98_322_304,
                                         104_867_840, 91_766_144)
    whole = 9 * mamba + 9 * attn + 7 * gmu + 7 * cross + 200_064 * d + 2 * d
    assert whole == 3_852_562_944
    assert ref.params_count(ref.published_config(cfg)) == whole
    here = 2 * mamba + 2 * attn + gmu + cross + 25_008 * d + 2 * d
    assert here == 697_094_272 == ref.params_count(cfg)
    assert 200_064 // 8 == 25_008 == cfg["vocab_size"]


def test_the_zoo_builder_draws_that_many_from_the_public_keys(full):
    """``Phi4Flash`` from the PUBLIC keys alone (no ``layer_types``, no
    Mamba sizes: it derives and assumes them) at the cut, by
    ``jax.eval_shape`` of its ``init``: no array is made."""
    import jax
    from deeplearning4j_tpu.models import Phi4Flash
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    cfg = full.config
    public = {k: cfg["published"].get(k, cfg[k]) for k in (
        "hidden_size", "intermediate_size", "layer_norm_eps", "mb_per_layer",
        "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
        "sliding_window", "tie_word_embeddings", "mlp_bias", "lm_head_bias",
        "vocab_size")}
    zoo = Phi4Flash(public, layer_indices=cfg["layer_indices"],
                    vocab_rows=cfg["vocab_size"],
                    sequence_length=cfg["sequence_length"])
    assert [zoo.layer_types[i] for i in INDICES] == KINDS
    assert zoo.layer_types.count("mamba") == 8
    assert zoo.layer_types.count("swa") == 8
    assert zoo.layer_types.count("gmu") == zoo.layer_types.count("cross") == 7
    net = ComputationGraph(zoo.conf())
    drawn = jax.eval_shape(lambda k: net._draw(k)[0], jax.random.key(0))
    count = sum(math.prod(a.shape) for leaves in drawn.values()
                for a in leaves.values())
    assert count == full.reference.params_count(cfg) == 697_094_272
    shapes = full.reference.param_shapes(cfg)
    assert {f"{v}/{k}": a.shape for v, leaves in drawn.items()
            for k, a in leaves.items()} == shapes
    with pytest.raises(ValueError):
        Phi4Flash(public, layer_indices=[18, 19]).conf()


def test_flop_hand_count_at_the_published_widths(full):
    """Matrix products a token, forward, by hand: the six SwiGLUs are 62% of
    the step's; training is three forwards: 4.58 GFLOP a token."""
    ref, cfg = full.reference, full.config
    d, inner, ff, t = 2560, 5120, 10240, 8192
    ffn = 2 * d * 3 * ff
    mamba = 2 * (d * 2 * inner + inner * 192 + 160 * inner + inner * d)
    gmu = 2 * 2 * d * inner

    def attn(keys, own_kv):
        return (2 * d * d * 2 + (2 * d * 2560 if own_kv else 0)
                + 2 * 40 * keys * (64 + 128))

    causal = (t + 1) / 2
    banded = (512 * 513 // 2 + (t - 512) * 512) / t
    by_hand = (6 * ffn + 2 * mamba + gmu + attn(banded, True)
               + attn(causal, True) + attn(causal, False) + 2 * d * 25_008)
    got = flops.forward_flops_per_item(ref.layers(cfg))
    assert abs(got - by_hand) < 1e-9 * by_hand
    assert abs(3 * got / 1e9 - 4.58) < 0.005
    assert abs(6 * ffn / got - 0.618) < 0.001


def test_kernel_cost_functions(full):
    ref, cfg = full.reference, full.config
    t = 8192
    whole = ref.attend_cost(cfg, t, None)
    assert whole["flops"] == 40 * (t * (t + 1) // 2) * 2 * (64 + 128)
    assert whole["bytes"] == 2 * t * (40 * (64 + 128) + 2 * 20 * 64)
    band = ref.attend_cost(cfg, t, 512)
    assert band["flops"] == 40 * (512 * 513 // 2 + (t - 512) * 512) * 2 * 192
    assert band["bytes"] == whole["bytes"]
    assert ref.attend_cost(cfg, t, t)["flops"] == whole["flops"]
    fwd = ref.selective_scan_cost(cfg, t)
    bwd = ref.selective_scan_cost(cfg, t, backward=True)
    assert fwd["flops"] == 7 * t * 5120 * 16 and bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == t * (5120 * (2 + 4 + 2) + 2 * 16 * 2)
    assert bwd["bytes"] == fwd["bytes"] + t * (5120 * (2 + 4) + 2 * 16 * 2)
    # against the published peaks the scan's count is bound by its bytes
    pk = peaks.peaks_for("TPU v5 lite")
    assert (fwd["bytes"] / pk["hbm_bytes_per_s"]
            > fwd["flops"] / pk["bf16_flops_per_s"])


_HLO = '''
HloModule jit_train_step
ENTRY main {
  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/jvp(Mamba1Mixer:l14_ssm)/mamba1.in_proj/dot_general"}
  %while.2 = f32[8]{0} while(), metadata={op_name="jit(train_step)/jvp(Mamba1Mixer:l16_ssm)/mamba1.scan/while"}
  %fusion.3 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(Mamba1Mixer:l16_ssm))/mamba1.scan/while/body/mul"}
  %fusion.4 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/jvp(DifferentialAttention:l15_attn)/dattn.attend/blocked"}
  %fusion.5 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/jvp(DifferentialAttention:l17_attn)/dattn.attend/blocked"}
  %fusion.6 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/jvp(DifferentialAttention:l17_attn)/dattn.qkv/dot_general"}
  %fusion.7 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(DifferentialAttention:l19_attn))/dattn.attend/blocked"}
  %fusion.8 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/jvp(GatedMemoryUnit:l18_gmu)/gmu.gate/mul"}
  %fusion.9 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/jvp(GatedFeedForward:l14_ffn)/dot_general"}
  %fusion.10 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/jvp(TokenOutputLayer:head)/loss.blocked/while"}
  %fusion.11 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/jvp(EmbeddingSequenceLayer:embed)/gather"}
  %fusion.12 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(train_step)/jvp(LayerNorm:l14_ln1)/mul"}
}
'''
# (instruction, start, end) in seconds: 2 steps in the slice
_OPS = [("%fusion.1 = x", 0.000, 0.010), ("%while.2 = x", 0.010, 0.030),
        ("%fusion.3 = x", 0.030, 0.050), ("%fusion.4 = x", 0.050, 0.056),
        ("%fusion.5 = x", 0.056, 0.068), ("%fusion.6 = x", 0.068, 0.070),
        ("%fusion.7 = x", 0.070, 0.090), ("%fusion.8 = x", 0.090, 0.094),
        ("%fusion.9 = x", 0.094, 0.134), ("%fusion.10 = x", 0.134, 0.140),
        ("%fusion.11 = x", 0.140, 0.142), ("%fusion.12 = x", 0.142, 0.143)]
_MS = {"mamba1.device_ms_per_step": 25.0,
       "mamba1.scan_device_ms_per_step": 20.0,
       "dattn.swa_device_ms_per_step": 3.0,
       "dattn.full_device_ms_per_step": 7.0,
       "dattn.cross_device_ms_per_step": 10.0,
       "gmu.device_ms_per_step": 2.0, "ffn10240.device_ms_per_step": 20.0,
       "tied25008.loss_device_ms_per_step": 4.0}


def _ctx(full, with_view=True, hlo=_HLO):
    device = types.SimpleNamespace(ops=list(_OPS))
    tr = types.SimpleNamespace(devices=[device])
    cell = dataclasses.replace(full)
    if with_view:
        cell.program_view = {"hlo_text": hlo, "tokens_per_step": 8192}
    return {"cell": cell, "trace": tr, "chips": 1,
            "peaks": peaks.peaks_for("TPU v5 lite"), "raw": {}}


def _read(full, name, ctx, monkeypatch):
    monkeypatch.setattr(trace, "steps", lambda tr: 2)
    return full.layer_reader(name)(ctx)


@pytest.mark.parametrize("name", sorted(_MS))
def test_device_ms_per_step_by_what_the_configuration_adds(full, name,
                                                           monkeypatch):
    got = _read(full, name, _ctx(full), monkeypatch)
    assert got == pytest.approx(_MS[name]), name


def test_roofline_shares_are_least_time_over_measured_time(full,
                                                           monkeypatch):
    ref, cfg, pk = full.reference, full.config, peaks.peaks_for("TPU v5 lite")
    fwd = ref.selective_scan_cost(cfg, 8192)
    bwd = ref.selective_scan_cost(cfg, 8192, backward=True)
    least = 2 * (2 * fwd["bytes"] + bwd["bytes"]) / pk["hbm_bytes_per_s"]
    got = _read(full, "mamba1.scan_roofline_pct", _ctx(full), monkeypatch)
    assert got == pytest.approx(100 * least / 0.020)
    flops_ = 4.5 * (ref.attend_cost(cfg, 8192, 512)["flops"]
                    + 2 * ref.attend_cost(cfg, 8192, None)["flops"])
    got = _read(full, "dattn.attend_roofline_pct", _ctx(full), monkeypatch)
    # fusion.4, .5 and .7: 6 + 12 + 20 ms over two steps
    assert got == pytest.approx(100 * flops_ / pk["bf16_flops_per_s"] / 0.019)
    assert 0 < got


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_view_reports_nothing(full, name, monkeypatch):
    assert _read(full, name, _ctx(full, with_view=False), monkeypatch) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_layers_reports_nothing(full, name,
                                                      monkeypatch):
    bare = re.sub(r"Mamba1Mixer|DifferentialAttention|GatedMemoryUnit|"
                  r"GatedFeedForward|TokenOutputLayer|EmbeddingSequenceLayer|"
                  r"loss\.|mamba1\.", "Other", _HLO)
    assert _read(full, name, _ctx(full, hlo=bare), monkeypatch) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_reader_s_file_imports_and_names_its_layer(full, name):
    module = loader.import_file(
        f"{bench_paths.ROOT}/benchmark/layer_metrics/{name}.py",
        "layer_metric")
    manifest = loader.load_manifest(bench_paths.ROOT)
    names = [m["name"] for m in manifest["per_layer"]]
    entry = manifest["per_layer"][names.index(name)]
    assert callable(module.read)
    assert (module.LAYER, module.UNIT, module.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert entry["workloads"] == [CELL]
    assert entry["source"] == "device_trace"
    assert name in full.cell["per_layer"]


def test_the_manifest_holds_the_configuration_and_the_cell_by_name(full):
    manifest = loader.load_manifest(bench_paths.ROOT)
    configs = [c["name"] for c in manifest["configs"]]
    cells = [w["name"] for w in manifest["workloads"]]
    config = manifest["configs"][configs.index(CONFIG)]
    entry = manifest["workloads"][cells.index(CELL)]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(config["source"]) <= 200 and "phi4flash" in config["source"]
    assert config["source"] == full.config["source"]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "fit_tokens_1x8192", 1)
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    # the four shared entries that list no cells are reported here too
    shared = [m["name"] for m in manifest["per_layer"]
              if "workloads" not in m and m["moves"] == "train_items_per_s"]
    assert set(shared) <= set(full.cell["per_layer"])
    assert sorted(full.cell["per_layer"]) == sorted(shared + NEW_METRICS)


def test_the_configuration_file_states_the_cut(full):
    cfg = _config_file()
    import json
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_hidden_layers": 32, "num_key_value_heads": 20,
               "resid_pdrop": 0, "sliding_window": 512,
               "tie_word_embeddings": True, "mlp_bias": False,
               "lm_head_bias": False, "vocab_size": 200064}
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in catalog.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6 == len(cfg["layer_indices"])
    assert cfg["layer_indices"] == INDICES
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    dep = cfg["deployment"]
    assert dep["pipeline_stages"] == 6 and dep["chips_sharing_a_stage"] == 8
    assert dep["stage_cuts_at_layers"] == [2, 8, 14, 20, 26]
    for key in ("depth", "layers", "vocabulary", "shared_values",
                "exchange"):
        assert dep[key]
    assert set(cfg["assumed"]) >= {
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
        "layer_kinds", "head_pairing", "lambda_init", "attention_bias",
        "mlp_order", "init", "sequence_length", "updater", "compute_dtype"}
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_expand"],
            cfg["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert cfg["sequence_length"] == 8192
    assert cfg["updater"]["learning_rate"] == 1e-5
    assert cfg["compute_dtype"] == "bfloat16"
    small = cfg["rehearse"]
    assert small["compute_dtype"] == "float32"
    assert len(json.dumps(cfg)) < 32_000

"""The ``granite_4p0_h_micro_pp4_vp8`` configuration: the program against
its plain reference on the CPU at the file's ``rehearse`` size in float32
(forward, loss, every gradient leaf, three steps of Adam, each new layer
kind alone, the reference's chain rule over pieces against its own loss as
one function), the cell through its driver with the float8 control and
three planted faults (the scan's decay left out, the gate after the norm,
the mixer branch's multiplier dropped) failing, the scopes and counters of
the compiled step, the hand counts of parameters, FLOPs and the kernels'
costs at the published widths, and each new per-layer reader on a synthetic
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

CELL = "granite4_h_micro_train_pp4_vp8"
CONFIG = "granite_4p0_h_micro_pp4_vp8"
# float32 on the CPU, two orders of the same sums through ten blocks
FORWARD_TOL = 5e-6      # softmax outputs, absolute
LOSS_TOL = 2e-6         # relative
GRAD_TOL = 2e-4         # a leaf's max |difference| over its max |value|
NEW_METRICS = ["ssm.device_ms_per_step", "ssm.scan_device_ms_per_step",
               "ssm.scan_roofline_pct", "ssm.conv_gate_roofline_pct",
               "nope.device_ms_per_step", "nope.attend_roofline_pct",
               "ffn8192.device_ms_per_step",
               "tied12544.loss_device_ms_per_step"]
KINDS = ["ssm"] * 5 + ["nope"] + ["ssm"] * 4


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


def test_the_two_sides_hold_the_same_leaves_and_the_tied_one_once(sides):
    flat = set(sides.grads_p)
    assert flat == set(sides.grads_r) == set(_rehearse_leaves())
    assert len(flat) == 1 + 9 * 13 + 9 + 1
    assert "embed/W" in flat and "head/W" not in flat
    assert sides.net.params["head"] == {}
    assert sides.net.vertices["head"][0].tied_to == "embed"
    with pytest.raises(NotImplementedError):
        sides.ref.param_shapes({**sides.cfg, "tie_word_embeddings": False})


def test_the_rehearsal_holds_what_the_cell_is_for(cell, full):
    """The ten layers in their published order, several chunks of the scan
    with a carried state, several tiles of the attention, the multipliers
    as published."""
    small, big = cell.config, full.config
    for cfg in (small, big):
        assert [b["attn"] for b in cell.reference.blocks(cfg)] == KINDS
    assert small["sequence_length"] // small["mamba_chunk_size"] == 4
    assert small["sequence_length"] // small["program"][
        "attention_block"] == 4
    for key in ("embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling", "mamba_n_groups",
                "mamba_d_conv", "mamba_conv_bias", "rms_norm_eps"):
        assert small[key] == big[key], key
    assert small["mamba_n_heads"] * small["mamba_d_head"] == 2 * small[
        "hidden_size"]
    assert cell.reference.count_params(small) == sum(
        math.prod(s) for s in cell.reference.param_shapes(small).values())


@pytest.mark.parametrize("kind", ["ssm", "nope"])
def test_each_layer_kind_alone_follows_the_reference(sides, kind):
    """One block's mixer, program against reference, forward and the
    input's gradient."""
    import jax
    import jax.numpy as jnp

    cfg, ref, net = sides.cfg, sides.ref, sides.net
    blk = next(b for b in ref.blocks(cfg) if b["attn"] == kind)
    vertex = blk["name"] + ("_ssm" if kind == "ssm" else "_attn")
    layer = net.vertices[vertex][0]
    own = {k[len(vertex) + 1:]: v for k, v in sides.p0.items()
           if k.startswith(vertex + "/")}
    h = jax.random.normal(jax.random.key(3), (2, 150, cfg["hidden_size"]))
    probe = jax.random.normal(jax.random.key(4), h.shape)
    m = ref.dims(cfg)
    plain = ref.mixer if kind == "ssm" else ref.attention
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.jit(jax.value_and_grad(
            lambda xx: jnp.sum(plain(m, own, xx, "highest") * probe)))(h)
        got, g_got = jax.jit(jax.value_and_grad(
            lambda xx: jnp.sum(layer.apply(own, {}, xx)[0] * probe)))(h)
    assert abs(float(got - want)) < 1e-4 * max(abs(float(want)), 1.0)
    assert float(jnp.max(jnp.abs(g_got - g_want))) < GRAD_TOL * float(
        jnp.max(jnp.abs(g_want)))


def test_the_reference_s_pieces_are_its_loss_s_own_gradient(sides):
    """``loss_and_grads`` (the chain rule over jitted pieces, what the
    timed size has room for) against ``jax.grad`` of ``loss`` as one
    function."""
    import jax.numpy as jnp

    value, grads = sides.ref.loss_and_grads(
        sides.cfg, sides.p0, jnp.asarray(sides.x), jnp.asarray(sides.y))
    assert abs(float(value) - sides.loss_r) < LOSS_TOL * abs(sides.loss_r)
    assert set(grads) == set(sides.grads_r)
    for leaf, want in sides.grads_r.items():
        top = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(grads[leaf] - want))) < 1e-5 * top, leaf


def test_three_adam_steps_follow_the_reference(cell):
    """Set-up's own path at the small size: three steps through
    ``net.fit``, the reference's three after them, leaf by leaf; the tied
    leaf has ONE Adam state."""
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
        for i, (x, y) in enumerate(batches):
            net.fit(DataSet(x, y))
            losses.append(float(net.score()))
            if i == 0:               # read now: the next step takes it
                first = {k: float(jnp.linalg.norm(a)) for k, a in
                         cell.adapter.first_moment_flat(net).items()}
        out = ref.train_steps(cfg, ref.init_params(cfg, 11), batches)
        now = cell.adapter.params_flat(net)
        start = ref.init_params(cfg, 11)
        moved = {k: float(jnp.linalg.norm(now[k] - start[k])) for k in now}
    assert set(first) == set(now) == set(out["delta_norms"]) == set(
        out["grad_norms"])
    assert "head/W" not in first
    for got, want in zip(losses, out["losses"]):
        assert abs(got - want) < 1e-5 * abs(want)
    for leaf, want in out["grad_norms"].items():
        got = first[leaf] / (1 - cfg["updater"]["beta1"])
        assert abs(got - want) <= 2e-3 * max(want, 1e-9), leaf
    for leaf, want in out["delta_norms"].items():
        assert abs(moved[leaf] - want) <= 2e-3 * max(want, 1e-9), leaf
    assert min(out["delta_norms"].values()) > 0      # every leaf moved
    # the seeded start made again is the start (the timed size keeps none)
    again = ref.change_norms(cfg, 11, now)
    for leaf, want in out["delta_norms"].items():
        assert abs(again[leaf] - want) <= 1e-6 + 1e-5 * want, leaf


STEP_COUNTERS = {"ssm.mamba2": 9, "kernel.xla_ssd_scan": 9, "head.tied": 1,
                 "attention.nope": 1, "attention.rotary_blocked": 1,
                 "kernel.xla_blocked_attention": 1,
                 "loss.blocked_one_pass": 1, "remat.kept_values": 3}


def test_the_cell_runs_through_its_driver_and_the_control_fails(cell,
                                                                tmp_path):
    """Set-up's first steps through ``net.fit(DevicePrefetchIterator)``,
    the reference after them: ``correct`` in float32 within the cell's
    limits, the float8 control outside one of them, the step's trace-time
    counters read (one count a layer; on the CPU the ``jax.numpy`` tiles:
    the chip's step reads ``kernel.pallas_blocked_attention`` in their
    place)."""
    import jax

    quiet = lambda *a: None
    session = cell.driver.setup(cell, jax.devices()[:1], 2_147_483_999, quiet)
    took = feed.TraceSlice(str(tmp_path), 0.05, 0.05)
    raw = cell.driver.run_window(session, 0.4, took)
    view = cell.program_view
    assert raw["steps"] > 0 and raw["items"] == raw["steps"] * 2 * 128
    assert took.done and raw["compiles_for_hlo_text"] == 0
    assert raw["compiles_in_window"] == 0 and raw["failed"] == 0
    assert view["moe"] == {} and raw["moe_pairs_held_in_window"] == {}
    for i, kind in enumerate(KINDS):
        marker = (f"Mamba2Mixer:l{i}_ssm" if kind == "ssm"
                  else f"RotaryAttention:l{i}_attn")
        assert marker in view["hlo_text"]
    counters = session.net.compile_watch.counters()
    assert {k: counters.get(k, 0) for k in STEP_COUNTERS} == STEP_COUNTERS
    assert counters.get("kernel.pallas_blocked_attention", 0) == 0
    assert counters.get("attention.rotary_windowed", 0) == 0
    ok, rows = cell.driver.check(session, quiet)
    assert ok, rows
    ok, rows = cell.driver.control(session, quiet)
    assert not ok, rows


@contextlib.contextmanager
def planted(fault: str):
    """The program with one fault in it, for as long as the block lasts
    (the network is built and its step traced inside set-up):

    * ``decay_left_out``: every scan runs ``S_t = S_{t-1} + dt B x^T``
      (A = 0 handed to ``chunked_ssd``);
    * ``gate_after_norm``: every ``Mamba2Mixer`` normalises y first and
      gates after (``state_space.gated_norm`` swapped for that order);
    * ``residual_multiplier_dropped``: the MIXER branch of every block is
      added at 1.0 and not at ``residual_multiplier``."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import GraniteHybrid
    from deeplearning4j_tpu.nn.conf import state_space
    from deeplearning4j_tpu.nn.conf.graph import ScaleVertex

    if fault == "decay_left_out":
        owner, name = state_space, "chunked_ssd"
        sound_scan = state_space.chunked_ssd

        def faulty(x, dt, a_rate, bm, cm, chunk=256):
            return sound_scan(x, dt, jnp.zeros_like(a_rate), bm, cm, chunk)
    elif fault == "gate_after_norm":
        owner, name = state_space, "gated_norm"

        def faulty(y, gate, weight, eps):
            return state_space.rms_norm(y, weight, eps) * gate
    elif fault == "residual_multiplier_dropped":
        owner, name = GraniteHybrid, "conf"
        sound_conf = GraniteHybrid.conf

        def faulty(self):
            conf = sound_conf(self)
            return dataclasses.replace(conf, vertices={
                n: ((ScaleVertex(scale=1.0), ins)
                    if n.endswith("_mix_scale") else (obj, ins))
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
FAULTS = {"decay_left_out": {"grad_norm.worst_leaf",
                             "grad_norm.median_leaf"},
          "gate_after_norm": {"grad_norm.worst_leaf",
                              "grad_norm.median_leaf"},
          "residual_multiplier_dropped": {"grad_norm.worst_leaf",
                                          "grad_norm.median_leaf"}}


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


SCOPES = ["ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
          "ssm.out_proj", "rattn.attend", "loss.blocked"]


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
        text = net._get_jitted("train").lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", SCOPES)
def test_every_scope_is_in_the_compiled_step_forward_and_backward(
        step_op_names, scope):
    layer = {"ssm": "Mamba2Mixer:", "rattn": "RotaryAttention:",
             "loss": ""}[scope.split(".")[0]]
    under = [o for o in step_op_names if scope in o and layer in o]
    assert any("transpose(" not in o for o in under), scope
    # the loss's gradients come out of its forward rule's one loop
    assert scope == "loss.blocked" or any("transpose(" in o for o in under)


def test_every_operation_of_the_step_has_an_owner(step_op_names):
    """The nine mixers and the one attention layer are told apart by their
    markers, no rotation ran, the scale vertices' multiplies are owned, and
    nothing jax emitted lies outside an owner."""
    from deeplearning4j_tpu.obs.owners import owner_of
    from harness import layer_scopes

    emitted = [o for o in step_op_names
               if o.startswith("jit(") or re.match(r"[A-Za-z_]\w*:", o)]
    assert [o for o in emitted if owner_of(o) is None] == []
    assert {m for o in emitted for m in re.findall(
        r"Mamba2Mixer:(l\d+_ssm)", o)} == {
        f"l{i}_ssm" for i in range(10) if i != 5}
    wanted = layer_scopes.under("RotaryAttention", ["l5_attn"],
                                "rattn.attend")
    mine = [o for o in emitted if wanted(o)]
    assert any("transpose(" in o for o in mine)
    assert any("transpose(" not in o for o in mine)
    assert not [o for o in emitted if "rattn.rope" in o]
    assert "ScaleVertex" in {owner_of(o) for o in emitted}


# ------------------------------------------------------------- hand counts
def test_parameter_hand_count_at_the_published_widths(full):
    """ISSUE 46's count, reckoned again: every width as published, 10 of 40
    layers, 12,544 of 100,352 rows, the tied matrix once."""
    d = 2048
    embedding = 12544 * d
    mixer = (d * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * d)
    attn = d * 32 * 64 + 2 * d * 8 * 64 + 32 * 64 * d
    ffn = 3 * d * 8192
    assert (embedding, mixer, attn, ffn) == (
        25_690_112, 25_847_232, 10_485_760, 50_331_648)
    mamba_layer, attn_layer = mixer + ffn + 2 * d, attn + ffn + 2 * d
    assert (mamba_layer, attn_layer) == (76_182_976, 60_821_504)
    total = embedding + 9 * mamba_layer + attn_layer + d
    assert total == 772_160_448
    assert full.reference.count_params(full.config) == total
    whole = {**full.config, **full.config["published"]}
    assert full.reference.count_params(whole) == 3_191_396_096


def test_the_zoo_builder_draws_that_many_from_the_public_keys(full):
    """``models.GraniteHybrid`` from the public config's keys alone, cut by
    its arguments (shapes only, nothing drawn)."""
    import jax
    from deeplearning4j_tpu.models import GraniteHybrid
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    public = full.adapter.public_config(full.config)
    assert (public["num_hidden_layers"], public["vocab_size"]) == (
        40, 100352)

    def count(zoo):
        net = ComputationGraph(zoo.conf())
        drawn = jax.eval_shape(net._draw, jax.random.key(0))[0]
        return sum(math.prod(a.shape)
                   for a in jax.tree_util.tree_leaves(drawn))

    assert count(GraniteHybrid(public, layers=10, vocab_rows=12544,
                               sequence_length=8192)) == 772_160_448
    assert count(GraniteHybrid(public)) == 3_191_396_096


def test_flop_hand_count_at_the_published_widths(full):
    """Forward matrix-product FLOPs a token at T = 8,192: the mixers' two
    products, their scans at (L + 1) / 2 steps a step inside a chunk of 256
    and two state products a head, the one attention layer's scores and
    values at (T + 1) / 2 keys a query, ten SwiGLUs, the head."""
    d, t = 2048, 8192
    products = 2 * (d * 8512 + 4096 * d)
    inside = (256 + 1) / 2
    scan = 2 * (128 * inside + 64 * inside * 64 + 64 * 128 * 2 * 64)
    attn_proj = 2 * (d * 2048 + 2 * d * 512 + 2048 * d)
    attn_keys = 2 * 32 * (64 + 64) * (t + 1) / 2
    ffn = 2 * 3 * d * 8192
    head = 2 * d * 12544
    want = 9 * (products + scan) + attn_proj + attn_keys + 10 * ffn + head
    got = flops.forward_flops_per_item(full.reference.layers(full.config))
    assert got == pytest.approx(want, rel=1e-12)
    assert [round(x / 1e6) for x in (9 * products, 9 * scan, attn_proj,
                                     attn_keys, 10 * ffn, head)] == [
        465, 29, 21, 34, 1007, 51]
    assert 1605e6 < got < 1607e6
    # 4.82 GFLOP a token to train, 39.5 TFLOP a step of 8,192 tokens
    assert 39.4e12 < 3 * got * t < 39.6e12
    # what the configuration adds: the nine mixers 31% of the operations,
    # the SwiGLUs of 8192 63%
    assert 9 * (products + scan) / got == pytest.approx(0.307, abs=0.005)
    assert 10 * ffn / got == pytest.approx(0.626, abs=0.005)
    assert full.reference.kept_positions(t) == t * (t + 1) // 2


def test_kernel_cost_functions(full):
    ref, cfg = full.reference, full.config
    t = 8192
    whole = ref.attend_cost(cfg, t, None)
    assert whole["flops"] == 32 * (t * (t + 1) // 2) * 2 * 2 * 64
    assert whole["bytes"] == 2 * t * 64 * (2 * 32 + 2 * 8)
    assert whole["flops"] / 197e12 > whole["bytes"] / 819e9     # MXU bound
    # the scan: 32 chunks of 256; the mask keeps 32,896 positions a chunk
    kept = 256 * 257 // 2
    fwd = ref.ssd_scan_cost(cfg, t)
    bwd = ref.ssd_scan_cost(cfg, t, backward=True)
    assert fwd["flops"] == 32 * (2 * 128 * kept
                                 + 64 * (2 * 64 * kept + 4 * 256 * 128 * 64))
    assert bwd["flops"] == 2 * fwd["flops"]
    # x, B, C read and y written in bfloat16, dt in float32: 17 KB a token
    assert fwd["bytes"] == t * (2 * (4096 + 256 + 4096) + 4 * 64)
    assert bwd["bytes"] == t * (2 * (2 * 4352 + 2 * 4096) + 2 * 4 * 64)
    assert round(fwd["bytes"] / t / 1024) == 17
    # near the chip's ridge: 0.13 ms of operations, 0.17 ms of bytes
    assert fwd["flops"] / 197e12 == pytest.approx(0.132e-3, rel=0.02)
    assert fwd["bytes"] / 819e9 == pytest.approx(0.171e-3, rel=0.02)
    # a shorter sequence than a chunk is one chunk of its own length
    short = ref.ssd_scan_cost(cfg, 100)
    assert short["flops"] == 2 * 128 * 5050 + 64 * (
        2 * 64 * 5050 + 4 * 100 * 128 * 64)
    # the convolution and the gated norm: bound by bytes
    fwd = ref.conv_gate_cost(cfg, t)
    bwd = ref.conv_gate_cost(cfg, t, backward=True)
    assert fwd["bytes"] == t * (2 * (2 * 4352 + 3 * 4096) + 4 * 2 * 64)
    assert bwd["bytes"] == t * (2 * (3 * 4352 + 5 * 4096) + 4 * 3 * 64)
    assert fwd["flops"] == t * (4352 * 12 + 9 * 4096)
    step_bytes = 2 * fwd["bytes"] + bwd["bytes"]
    assert step_bytes / 819e9 == pytest.approx(1.52e-3, rel=0.01)
    assert step_bytes / 819e9 > 20 * (2 * fwd["flops"] + bwd["flops"]) \
        / 197e12


# ------------------------------------------------------------ the readers
_HLO = '''
HloModule jit_train_step
%fused_computation.1 { ... }
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%p0), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(Mamba2Mixer:l0_ssm)/ssm.in_proj/dot_general" source_file="x.py" source_line=1}
  %fusion.2 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(Mamba2Mixer:l0_ssm)/ssm.conv/mul"}
  %while.1 = f32[8]{0} while(%p0), condition=%c, body=%b, metadata={op_name="jit(train_step)/jvp(Mamba2Mixer:l0_ssm)/ssm.scan/while"}
  %fusion.3 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(Mamba2Mixer:l3_ssm))/ssm.scan/while/body/checkpoint/mul"}
  %fusion.4 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(Mamba2Mixer:l3_ssm))/ssm.gate_norm/mul"}
  %custom-call.1 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(RotaryAttention:l5_attn)/rattn.attend/jit(_forward)/mla_attend_fwd/pallas_call"}
  %fusion.5 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(RotaryAttention:l5_attn)/dot_general"}
  %fusion.6 = bf16[8]{0} fusion(%p0), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(GatedFeedForward:l0_ffn)/dot_general"}
  %fusion.7 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(ScaleVertex:l0_mix_scale)/mul"}
  %fusion.9 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(EmbeddingSequenceLayer:embed)/gather"}
  %fusion.10 = f32[8]{0} fusion(%p0), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(TokenOutputLayer:head)/loss.blocked/while/body/dot_general"}
  ROOT %fusion.8 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/optim.update/mul"}
}
'''


def _ctx(full, with_view=True, hlo=_HLO):
    ms = 1e-3
    spans = [("%fusion.1 = bf16[8]{0} fusion(%p0)", 6), ("%fusion.2", 2),
             ("%while.1", 5), ("%fusion.3", 3), ("%fusion.4", 4),
             ("%custom-call.1", 10), ("%fusion.5", 2), ("%fusion.6", 20),
             ("%fusion.7", 1), ("%fusion.9", 2), ("%fusion.10", 3),
             ("%fusion.8", 2)]
    ops, at = [], 0.0
    for name, length in spans:
        full_name = name if " = " in name else name + " = x[8]{0} op(%p0)"
        ops.append((full_name, at * ms, (at + length) * ms))
        at += length
    assert at == 60
    # two steps, the second a copy of the first 70 ms later
    ops = ops + [(n, s + 70 * ms, e + 70 * ms) for n, s, e in ops]
    modules = [("jit_train_step", 0.0, 60 * ms),
               ("jit_train_step", 70 * ms, 130 * ms)]
    cell = types.SimpleNamespace(reference=full.reference,
                                 config=full.config, traffic=full.traffic,
                                 layer_reader=full.layer_reader)
    if with_view:
        cell.program_view = {"hlo_text": hlo, "tokens_per_step": 8192,
                             "moe": {}}
    return {"cell": cell, "raw": {"steps": 7},
            "trace": trace.Trace([trace.DeviceTimeline(0, ops, modules)], []),
            "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite")}


def _read(full, name, ctx):
    return full.layer_reader(name)(ctx)


def test_device_ms_per_step_by_what_the_configuration_adds(full):
    ctx = _ctx(full)
    # the mixers 6 + 2 + 5 + 3 + 4 ms, their scans 5 + 3, the attention
    # layer 10 + 2, the SwiGLU 20, the embedding's gather 2 and the loss 3
    assert _read(full, "ssm.device_ms_per_step", ctx) == pytest.approx(20.0)
    assert _read(full, "ssm.scan_device_ms_per_step", ctx) == \
        pytest.approx(8.0)
    assert _read(full, "nope.device_ms_per_step", ctx) == pytest.approx(12.0)
    assert _read(full, "ffn8192.device_ms_per_step", ctx) == \
        pytest.approx(20.0)
    assert _read(full, "tied12544.loss_device_ms_per_step", ctx) == \
        pytest.approx(5.0)


def test_roofline_shares_are_least_time_over_measured_time(full):
    ctx = _ctx(full)
    ref, cfg = full.reference, full.config
    # nine scans, the forward twice and the backward, 8 ms under ssm.scan
    fwd, bwd = ref.ssd_scan_cost(cfg, 8192), ref.ssd_scan_cost(
        cfg, 8192, backward=True)
    least = 9 * max((2 * fwd["flops"] + bwd["flops"]) / 197e12,
                    (2 * fwd["bytes"] + bwd["bytes"]) / 819e9)
    assert least == pytest.approx(6.1e-3, rel=0.02)
    assert _read(full, "ssm.scan_roofline_pct", ctx) == pytest.approx(
        100 * least / 8e-3)
    # nine layers' convolution and gated norm, 2 + 4 ms under both scopes
    fwd, bwd = ref.conv_gate_cost(cfg, 8192), ref.conv_gate_cost(
        cfg, 8192, backward=True)
    least = 9 * (2 * fwd["bytes"] + bwd["bytes"]) / 819e9
    assert _read(full, "ssm.conv_gate_roofline_pct", ctx) == pytest.approx(
        100 * least / 6e-3)
    # one attention layer, the forward twice and a backward of 2.5
    # forwards, 10 ms under its rattn.attend
    one = ref.attend_cost(cfg, 8192, None)
    least = one["flops"] / 197e12 * 4.5
    assert _read(full, "nope.attend_roofline_pct", ctx) == pytest.approx(
        100 * least / 10e-3)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_view_reports_nothing(full, name):
    """Where the driver kept no view the readers return None and do not
    raise."""
    assert _read(full, name, _ctx(full, with_view=False)) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_layers_reports_nothing(full, name):
    """On a program that has none of the layers the readers look for:
    nothing, no raise."""
    bare = "\n".join(line for line in _HLO.splitlines()
                     if "Mamba2Mixer" not in line
                     and "RotaryAttention" not in line
                     and "GatedFeedForward" not in line
                     and "embed" not in line and "head" not in line)
    assert _read(full, name, _ctx(full, hlo=bare)) is None


@pytest.mark.parametrize("name", NEW_METRICS[:6])
def test_a_configuration_without_these_kinds_reports_nothing(full, name):
    """On a cell whose reference has no ``ssd_scan_cost`` and lists no
    ``"nope"`` layer (the LFM2 cell's): nothing, no raise. (The dense
    layers' and the tied head's readers go by the program's text alone: the
    manifest's ``workloads`` says where they are reported.)"""
    other = loader.resolve_cell(bench_paths.ROOT, "lfm2_train_16k_ep4share")
    ctx = _ctx(full)
    ctx["cell"].reference, ctx["cell"].config = other.reference, other.config
    ctx["cell"].traffic = other.traffic
    if name in ("ssm.device_ms_per_step", "ssm.scan_device_ms_per_step"):
        # these two read a marker alone: nothing on the other cell's text
        ctx["cell"].program_view["hlo_text"] = _HLO.replace(
            "Mamba2Mixer", "GatedShortConv").replace("ssm.", "sconv.")
    assert _read(full, name, ctx) is None


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
    cfg = _config_file()
    catalog = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True}
    for key, value in catalog.items():
        assert cfg[key] == value, key
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 12544)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "vocab_size": 100352}
    assert cfg["deployment"]["pipeline_stages"] == 4
    assert cfg["deployment"]["chips_sharing_a_stage"] == 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] * 4 == cfg["published"][
        "num_hidden_layers"]
    assert cfg["mamba_init"] == {"A_log": "log_arange_1_to_heads", "D": 1.0,
                                 "dt_min": 0.001, "dt_max": 0.1,
                                 "conv_bias_std": 0.1}
    assert (cfg["mamba_in_proj_order"],
            cfg["mamba_time_step_limit"]) == ("z|xBC|dt", [0.0, None])
    for key in ("mamba_init", "gated_norm_order",
                "mamba_time_step_limit", "mamba_in_proj_order", "final_norm",
                "sequence_length", "weights", "compute_dtype", "updater"):
        assert key in cfg["assumed"], key
    # a value the reference does not compute is refused, not ignored
    for key, value in [("mamba_in_proj_order", "xBC|z|dt"),
                       ("mamba_time_step_limit", [0.001, 0.1])]:
        with pytest.raises(NotImplementedError):
            full.reference.dims({**cfg, key: value})
    assert cfg["program"]["attention_block"] == 512
    assert cfg["program"]["loss_block"] == 1024
    assert cfg["program"]["remat"] == "full"
    assert 0 <= cfg["program"]["projections_kept"] <= 9
    assert cfg["control_precision"] == "fp8"
    assert cfg["updater"]["learning_rate"] == 1e-5
    assert set(full.cell["limits"]) == {"loss", "grad_norm_worst",
                                        "grad_norm_median",
                                        "delta_norm_worst"}
    assert len(full.cell["limits"]["loss"]) == 3
    for key in ("readings", "loss", "grad_norm_worst", "grad_norm_median",
                "delta_norm_worst", "control", "planted_faults"):
        assert key in full.cell["limits_why"], key
    for fault in FAULTS:
        assert fault in full.cell["limits_why"]["planted_faults"]
    # the traffic: the accepted 8k mix, nothing added
    assert full.cell["traffic"] == "fit_tokens_1x8192"
    assert full.traffic["sequence_length"] == cfg["sequence_length"] == 8192
    # the manifest's entries for this configuration and cell
    manifest = loader.load_manifest(bench_paths.ROOT)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    assert entry["reduced"] == cfg["reduced"]
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "fit_tokens_1x8192", 1)]
    assert all(len(w["why"]) <= 200 for w in manifest["workloads"])
    # appended after the eight cells the benchmark had, one of them on
    # four chips; what later PRs append comes after
    assert [w["name"] for w in manifest["workloads"]].index(CELL) == 8
    assert [w["chips"] for w in manifest["workloads"][:9]].count(4) == 1
    listed = [m["name"] for m in manifest["per_layer"]]
    at = listed.index(NEW_METRICS[0])
    assert listed[at:at + 8] == NEW_METRICS

"""The ``mellum2_12b_a2p5b_ep4`` configuration: the program against its
plain reference on the CPU at the file's ``rehearse`` size in float32
(forward, loss, every gradient leaf, three steps of Adam, each layer kind
alone, the four expert shares with the attention counted once against the
uncut reference layer), the cell through its driver with the float8
control and two planted faults (the window ignored, the YaRN block ignored)
failing, the scopes and counters of the compiled step, the hand counts of
parameters and FLOPs at the published widths, and each new per-layer reader
on a synthetic trace."""

import contextlib
import dataclasses
import json
import math
import re
import types

import numpy as np
import pytest

import bench_paths
from harness import feed, flops, loader, peaks, trace

CELL = "mellum2_train_16k_ep4share"
CONFIG = "mellum2_12b_a2p5b_ep4"
# float32 on the CPU, two orders of the same sums through four blocks
FORWARD_TOL = 5e-6      # softmax outputs, absolute
LOSS_TOL = 2e-6         # relative
GRAD_TOL = 1e-4         # a leaf's max |difference| over its max |value|
NEW_METRICS = ["swa.device_ms_per_step", "swa.attend_roofline_pct",
               "fullattn.device_ms_per_step", "fullattn.attend_roofline_pct",
               "moe64.device_ms_per_step", "moe64.experts_roofline_pct",
               "moe64.expert_load_max_over_mean"]


@pytest.fixture(scope="module")
def cell():
    return loader.resolve_cell(bench_paths.ROOT, CELL, rehearse=True)


@pytest.fixture(scope="module")
def full():
    return loader.resolve_cell(bench_paths.ROOT, CELL)


@pytest.fixture(scope="module")
def sides(cell):
    """The network and the reference on the same seeded weights and ids,
    with both sides' loss and gradients. T = 200 in tiles of 32 under a
    window of 32: not a multiple of the tile or of the loss block (64),
    the band two tiles of seven, and past the rehearsal's original length
    (64) of the scaled rotation."""
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


def test_the_rehearsal_holds_what_the_cell_is_for(cell):
    """The band, its far edge, a row with no key in the band's oldest tile
    and the ramp of the scaled rotation are all inside the rehearsal."""
    cfg, ref = cell.config, cell.reference
    kinds = [(b["attn"], b["window"], b["rope"]["rope_type"], b["ffn"])
             for b in ref.blocks(cfg)]
    assert kinds == [("swa", 32, "default", "moe")] * 3 + [
        ("full", None, "yarn", "moe")]
    block = cfg["program"]["attention_block"]
    assert cfg["sliding_window"] == block < cfg["sequence_length"] // 2
    low, high, inv_freq, factor = ref.yarn_parameters(
        cfg["rope_parameters"]["full_attention"], cfg["head_dim"])
    assert (low, high) == (0, 2) and factor == 1.2772588722239782
    plain = 5e5 ** (-2.0 * np.arange(8) / 16)
    np.testing.assert_allclose(inv_freq[0], plain[0])
    np.testing.assert_allclose(inv_freq[1], plain[1] * (0.5 + 0.5 / 16))
    np.testing.assert_allclose(inv_freq[2:], plain[2:] / 16)


def test_the_reference_s_yarn_block_is_the_published_one_by_hand(full):
    ref, cfg = full.reference, full.config
    low, high, inv_freq, factor = ref.yarn_parameters(
        cfg["rope_parameters"]["full_attention"], 128)
    assert (low, high, factor) == (18, 35, 1.2772588722239782)
    assert factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-15)
    plain = 5e5 ** (-2.0 * np.arange(64) / 128)
    np.testing.assert_allclose(inv_freq[:19], plain[:19])
    np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16)
    r = (26 - 18) / 17
    assert inv_freq[26] == pytest.approx(
        (1 - r) * plain[26] + r * plain[26] / 16)
    # the program's layer makes the same frequencies from the same entry
    from deeplearning4j_tpu.nn.conf.attention import yarn_inv_freq
    np.testing.assert_allclose(
        np.asarray(yarn_inv_freq(128, 5e5, 16, 8192, 32, 1)), inv_freq,
        rtol=1e-6)


@pytest.mark.parametrize("kind", ["swa", "full", "moe"])
def test_each_layer_kind_alone_follows_the_reference(sides, kind):
    """One layer's ``apply`` on the reference's leaves against the
    reference's function for it: output and the gradient of every leaf and
    of the input."""
    import jax
    import jax.numpy as jnp

    ref, cfg = sides.ref, sides.cfg
    blk = next(b for b in ref.blocks(cfg) if kind in (b["attn"], b["ffn"]))
    vertex = blk["name"] + ("_ffn" if kind == "moe" else "_attn")
    layer = sides.net.vertices[vertex][0]
    own = {k.split("/")[1]: v for k, v in sides.p0.items()
           if k.startswith(vertex + "/")}
    x = jax.random.normal(jax.random.key(3), (2, 150, cfg["hidden_size"]))

    def program(own, x):
        return layer.apply(own, sides.net.state[vertex], x)[0]

    def reference(own, x):
        p = {vertex + "/" + k: v for k, v in own.items()}
        if kind == "moe":
            return ref.moe(ref.dims(cfg), p, vertex + "/", x, "highest")
        return ref.attention(ref.dims(cfg), p, vertex + "/", x, "highest",
                             blk["rope"], blk["window"])

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


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_the_four_shares_add_up_to_the_uncut_reference_layer(kind):
    """The guide's shares test on a whole block: the PROGRAM's attention
    layer once (it is whole on every chip) plus its four shares of 16
    experts each (offsets 0, 16, 32, 48 of the published 64, top-8) add up
    to what the REFERENCE gives for the uncut block of 64 experts."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
    from deeplearning4j_tpu.nn.conf.experts import RoutedExperts
    from deeplearning4j_tpu.nn.conf.normalization import RMSNorm

    ref = _reference_module()
    cfg = loader.read_json(f"{bench_paths.ROOT}/benchmark/configs/"
                           f"{CONFIG}.json")
    cfg = {**cfg, **cfg["rehearse"], "num_experts": 64,
           "num_experts_per_tok": 8, "num_hidden_layers": 1,
           "layer_types": [kind],
           "published": {"num_experts": 64}}
    blk, = ref.blocks(cfg)
    p = {k: v for k, v in ref.init_params(cfg, 5).items()
         if k.startswith("l0_")}
    d, t = cfg["hidden_size"], 100
    x = jax.random.normal(jax.random.key(1), (2, t, d))
    it = InputType.recurrent(d, t)

    def own(vertex):
        return {k.split("/")[1]: v for k, v in p.items()
                if k.startswith(vertex + "/")}

    with jax.default_matmul_precision("highest"):
        want = ref._block(json.dumps(cfg), json.dumps(blk), "highest", p, x)
        norm = RMSNorm(eps=cfg["rms_norm_eps"])
        attn = RotaryAttention(
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            rope_theta=5e5, window=blk["window"] or 0, qk_norm=True,
            rope_scaling=(dict(blk["rope"]) if kind == "full_attention"
                          else None), block=32)
        a, _ = norm.apply(own("l0_attn_norm"), {}, x)
        h1 = x + attn.apply(own("l0_attn"), {}, a)[0]      # counted once
        n, _ = norm.apply(own("l0_ffn_norm"), {}, h1)
        total = h1
        for share in range(4):
            layer = RoutedExperts(
                n_experts=64, experts_held=16, expert_offset=16 * share,
                top_k=8, expert_size=cfg["moe_intermediate_size"],
                shared_size=0, router_activation="softmax")
            mine = {k: (v[16 * share:16 * share + 16] if k != "Wr" else v)
                    for k, v in own("l0_ffn").items()}
            part, state = layer.apply(
                mine, layer.init(jax.random.key(0), it)[1], n)
            assert int(state["pairs_dropped"]) == 0
            total = total + part
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5 * max(
        1.0, float(jnp.max(jnp.abs(want))))


STEP_COUNTERS = {"attention.rotary_blocked": 4, "attention.rotary_windowed": 3,
                 "kernel.xla_blocked_attention": 4,
                 "loss.blocked_one_pass": 1}


def test_the_cell_runs_through_its_driver_and_the_control_fails(cell, tmp_path):
    """Set-up's first steps through ``net.fit(DevicePrefetchIterator)``,
    the reference after them: ``correct`` in float32 within the cell's
    limits, the float8 control outside one of them, no pair dropped, the
    step's trace-time counters read (on the CPU the ``jax.numpy`` tiles;
    the chip's step reads ``kernel.pallas_blocked_attention`` 4)."""
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
    for i in range(4):
        assert f"RotaryAttention:l{i}_attn" in view["hlo_text"]
    assert 0 < view["moe_slice"]["steps"] <= raw["steps"]
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
    (the step is traced inside set-up):

    * ``window_ignored``: every ``RotaryAttention`` runs the causal
      triangle, whatever its ``window``;
    * ``yarn_ignored``: every ``RotaryAttention`` turns by the plain
      frequencies with factor 1, whatever its ``rope_scaling``."""
    from deeplearning4j_tpu.nn.conf.attention import RotaryAttention

    field = {"window_ignored": {"window": 0},
             "yarn_ignored": {"rope_scaling": None}}[fault]
    sound = RotaryAttention.apply

    def faulty(self, params, state, x, **kw):
        return sound(dataclasses.replace(self, **field), params, state, x,
                     **kw)

    RotaryAttention.apply = faulty
    try:
        yield
    finally:
        RotaryAttention.apply = sound


# what each fault has to trip at the least, of the cell's own limits
FAULTS = {"window_ignored": {"grad_norm.worst_leaf", "grad_norm.median_leaf",
                             "delta_norm.worst_leaf"},
          "yarn_ignored": {"grad_norm.worst_leaf", "grad_norm.median_leaf",
                           "delta_norm.worst_leaf"}}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_driver_s_check(cell, fault):
    """Set-up's first steps through ``net.fit`` with the fault in the
    program, then the driver's own ``check`` against the sound reference:
    not ``correct``, by the numbers the fault is there to move. That holds
    at this size in float32; the readings at the timed size on the chip
    stand in PERF.md section 4."""
    import jax

    quiet = lambda *a: None
    with planted(fault):
        session = cell.driver.setup(cell, jax.devices()[:1], 2_147_484_123,
                                    quiet)
    ok, rows = cell.driver.check(session, quiet)
    tripped = {row["what"] for row in rows if not row["ok"]}
    assert not ok and FAULTS[fault] <= tripped, rows


SCOPES = ["rattn.qk_norm", "rattn.rope", "rattn.attend", "moe.route",
          "moe.dispatch", "moe.experts", "loss.blocked"]


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
    layer = {"rattn": "RotaryAttention:", "moe": "RoutedExperts:",
             "loss": ""}[scope.split(".")[0]]
    under = [o for o in step_op_names if scope in o and layer in o]
    assert any("transpose(" not in o for o in under), scope
    assert any("transpose(" in o for o in under), scope


def test_window_and_full_layers_are_told_apart_by_their_marker(
        step_op_names):
    """What ``harness/layer_scopes.py`` matches: every attention layer's
    tiles under ``RotaryAttention:<its vertex>`` and ``rattn.attend``."""
    from harness import layer_scopes

    for names in (["l0_attn", "l1_attn", "l2_attn"], ["l3_attn"]):
        wanted = layer_scopes.under("RotaryAttention", names, "rattn.attend")
        mine = [o for o in step_op_names if wanted(o)]
        assert any("transpose(" in o for o in mine)
        assert any("transpose(" not in o for o in mine)
        assert {m for o in mine for m in re.findall(
            r"RotaryAttention:(l\d+_attn)", o)} == set(names)
    assert not layer_scopes.under("RotaryAttention", [])("RotaryAttention:x")
    one = layer_scopes.under("RotaryAttention", ["l1_attn"])
    assert one("jvp(RotaryAttention:l1_attn)/rattn.rope/mul")
    assert not one("jvp(RotaryAttention:l10_attn)/rattn.rope/mul")


# ------------------------------------------------------------- hand counts
def test_parameter_hand_count_at_the_published_widths(full):
    """ISSUE 37's count, reckoned again: every width as published, 4 of 28
    layers, 16 of 64 experts, 24,576 of 98,304 rows."""
    d = 2304
    attn = d * 32 * 128 + 2 * d * 4 * 128 + 32 * 128 * d
    qk_norms = 2 * 128
    expert = 3 * d * 896
    routed = d * 64 + 16 * expert
    layer = attn + qk_norms + routed + 2 * d
    total = 4 * layer + 2 * 24576 * d + d
    assert (attn, expert, routed) == (21_233_664, 6_193_152, 99_237_888)
    assert layer == 120_476_416
    assert total == 595_154_176
    assert full.reference.count_params(full.config) == total
    assert [(b["attn"], b["window"], b["ffn"]) for b in
            full.reference.blocks(full.config)] == [
        ("swa", 1024, "moe")] * 3 + [("full", None, "moe")]


def test_the_zoo_builder_draws_that_many_from_the_public_keys(full):
    """``models.Mellum2`` from the public config's keys alone, cut by its
    arguments: (shapes only, nothing drawn) 595,154,176 parameters; the
    whole published model 12 billion, 2.5 of them a token."""
    import jax
    from deeplearning4j_tpu.models import Mellum2
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    public = full.adapter.public_config(full.config)
    assert (public["num_hidden_layers"], public["num_experts"],
            public["vocab_size"]) == (28, 64, 98304)

    def count(zoo):
        net = ComputationGraph(zoo.conf())
        windows = [net.vertices[f"l{i}_attn"][0].window
                   for i in range(zoo.layers)]
        drawn = jax.eval_shape(net._draw, jax.random.key(0))[0]
        return windows, sum(math.prod(a.shape)
                            for a in jax.tree_util.tree_leaves(drawn))

    windows, n = count(Mellum2(public, layers=4, experts_held=16,
                               vocab_rows=24576, sequence_length=16384))
    assert windows == [1024, 1024, 1024, 0]
    assert n == 595_154_176
    windows, n = count(Mellum2(public))
    assert windows == [1024, 1024, 1024, 0] * 7
    assert 11.5e9 < n < 12.5e9
    active = n - 28 * (64 - 8) * 3 * 2304 * 896
    assert 2.3e9 < active < 2.7e9


def test_flop_hand_count_at_the_published_widths(full):
    """Forward matrix-product FLOPs a token at T = 16,384: a window
    layer's scores and values at min(t + 1, 1024) keys a query, 992 on
    average, the full layer's at (T + 1) / 2."""
    d, t, w = 2304, 16384, 1024
    keys_swa = (w * (w + 1) / 2 + (t - w) * w) / t
    assert keys_swa == 992.03125
    assert full.reference.kept_positions(t, w) == keys_swa * t
    assert full.reference.kept_positions(t, None) == t * (t + 1) // 2
    assert full.reference.kept_positions(t, t) == t * (t + 1) // 2

    def attn(keys):
        return 2 * (d * 4096 + d * 1024 + 32 * (128 + 128) * keys + 4096 * d)

    routed = 2 * (d * 64 + 3 * d * 896 * 8 * 16 / 64)
    want = 3 * attn(keys_swa) + attn((t + 1) / 2) + 4 * routed \
        + 2 * d * 24576
    got = flops.forward_flops_per_item(full.reference.layers(full.config))
    assert got == pytest.approx(want, rel=1e-12)
    # the window takes 38% off: four full layers would be 920 MFLOP
    assert 565e6 < got < 568e6
    four_full = got + 3 * (attn((t + 1) / 2) - attn(keys_swa))
    assert 918e6 < four_full < 922e6
    # 1.70 GFLOP a token to train, 27.8 TFLOP a step of 16,384 tokens
    assert 1.69e9 < 3 * got < 1.71e9
    assert 27.7e12 < 3 * got * t < 27.9e12


def test_kernel_cost_functions(full):
    ref, cfg = full.reference, full.config
    t = 16384
    swa = ref.attend_cost(cfg, t, 1024)
    whole = ref.attend_cost(cfg, t, None)
    # 32 query heads, two products of width 128 over the kept positions
    assert swa["flops"] == 32 * ref.kept_positions(t, 1024) * 2 * 2 * 128
    assert whole["flops"] == 32 * (t * (t + 1) // 2) * 2 * 2 * 128
    # under the 93 tile pairs of 512 x 512 that hold them (62 tiles' worth)
    assert swa["flops"] < 32 * 93 * 2 * 2 * 512 * 512 * 128
    assert swa["flops"] == pytest.approx(
        32 * 62 * 2 * 2 * 512 * 512 * 128, rel=0.01)
    assert whole["flops"] / swa["flops"] == pytest.approx(8.26, abs=0.01)
    # q and the output a query head, k and v a key/value head, bfloat16
    assert swa["bytes"] == whole["bytes"] == 2 * t * 128 * (2 * 32 + 2 * 4)
    for cost in (swa, whole):                        # MXU bound
        assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
    moe = ref.moe_experts_cost(cfg, 32768, 16)
    assert moe["flops"] == 32768 * 3 * 2 * 2304 * 896
    assert moe["bytes"] > 16 * 3 * 2304 * 896 * 2       # the weights, bf16
    # at 2,048 tokens an expert the grouped products are MXU bound
    assert moe["flops"] / 197e12 > moe["bytes"] / 819e9


# ------------------------------------------------------------ the readers
_HLO = '''
HloModule jit_train_step
%fused_computation.1 { ... }
ENTRY %main {
  %custom-call.1 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(RotaryAttention:l0_attn)/rattn.attend/jit(_forward)/mla_attend_fwd/pallas_call" source_file="x.py" source_line=1}
  %fusion.9 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(RotaryAttention:l0_attn)/rattn.rope/mul"}
  %custom-call.2 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(RotaryAttention:l2_attn))/rattn.attend/jit(_backward)/mla_attend_bwd/pallas_call"}
  %custom-call.3 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(RotaryAttention:l3_attn)/rattn.attend/jit(_forward)/mla_attend_fwd/pallas_call"}
  %fusion.8 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(RotaryAttention:l3_attn)/rattn.qk_norm/mul"}
  %custom-call.4 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(RoutedExperts:l0_ffn)/moe.experts/pallas_call"}
  ROOT %fusion.5 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(RoutedExperts:l0_ffn)/moe.route/mul"}
  %fusion.6 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/adam/mul"}
}
'''


def _ctx(full, with_view=True):
    ms = 1e-3
    ops = [("%custom-call.1 = bf16[8]{0} custom-call(%p0)", 0 * ms, 4 * ms),
           ("%fusion.9 = bf16[8]{0} fusion(%p0)", 4 * ms, 5 * ms),
           ("%custom-call.2 = bf16[8]{0} custom-call(%p0)", 5 * ms, 11 * ms),
           ("%custom-call.3 = bf16[8]{0} custom-call(%p0)", 11 * ms, 31 * ms),
           ("%fusion.8 = bf16[8]{0} fusion(%p0)", 31 * ms, 33 * ms),
           ("%custom-call.4 = bf16[8]{0} custom-call(%p0)", 33 * ms, 41 * ms),
           ("%fusion.5 = f32[8]{0} fusion(%p0)", 41 * ms, 42 * ms),
           ("%fusion.6 = f32[8]{0} fusion(%p0)", 42 * ms, 50 * ms)]
    # two steps, the second a copy of the first 60 ms later
    ops = ops + [(n, s + 60 * ms, e + 60 * ms) for n, s, e in ops]
    modules = [("jit_train_step", 0.0, 50 * ms),
               ("jit_train_step", 60 * ms, 110 * ms)]
    cell = types.SimpleNamespace(reference=full.reference,
                                 config=full.config, traffic=full.traffic,
                                 layer_reader=full.layer_reader)
    if with_view:
        tokens = [3000] + [2000] * 14 + [1768]
        cell.program_view = {
            "hlo_text": _HLO, "tokens_per_step": 16384,
            "moe": {"l0_ffn": {"expert_tokens": tokens,
                               "pairs_held": sum(tokens),
                               "pairs_dropped": 0}}}
        # the slice's own steps: two of them, 32,768 pairs each
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
    # l0 and l2 slide (4 + 1 + 6 ms), l3 does not (20 + 2 ms)
    assert _read(full, "swa.device_ms_per_step", ctx) == pytest.approx(11.0)
    assert _read(full, "fullattn.device_ms_per_step", ctx) == \
        pytest.approx(22.0)
    assert _read(full, "moe64.device_ms_per_step", ctx) == pytest.approx(9.0)


def test_roofline_shares_are_least_time_over_measured_time(full):
    ctx = _ctx(full)
    ref, cfg = full.reference, full.config
    # three window layers, the forward twice and a backward of 2.5
    # forwards, 10 ms under their rattn.attend
    one = ref.attend_cost(cfg, 16384, 1024)
    least = one["flops"] / 197e12 * 3 * 4.5
    assert _read(full, "swa.attend_roofline_pct", ctx) == pytest.approx(
        100 * least / 10e-3)
    # one full layer, 20 ms
    one = ref.attend_cost(cfg, 16384, None)
    least = one["flops"] / 197e12 * 4.5
    assert _read(full, "fullattn.attend_roofline_pct", ctx) == pytest.approx(
        100 * least / 20e-3)
    moe = ref.moe_experts_cost(cfg, 32768, 16)
    least = max(moe["flops"] / 197e12, moe["bytes"] / 819e9) * 4
    assert _read(full, "moe64.experts_roofline_pct", ctx) == pytest.approx(
        100 * least / 8e-3)


def test_expert_load_is_the_busiest_over_the_mean(full):
    assert _read(full, "moe64.expert_load_max_over_mean",
                 _ctx(full)) == pytest.approx(3000 / 2048)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_view_reports_nothing(full, name):
    """On a parent that lacks what this PR adds the readers return None
    and do not raise."""
    assert _read(full, name, _ctx(full, with_view=False)) is None


@pytest.mark.parametrize("name", NEW_METRICS[:4])
def test_a_configuration_without_attention_kinds_reports_nothing(full, name):
    """The window / full readers on a cell whose reference tells no such
    kinds apart (the Ouro cell's ``RotaryAttention``): nothing, no raise."""
    ouro = loader.resolve_cell(bench_paths.ROOT, "ouro_train_8k_ut4")
    ctx = _ctx(full)
    ctx["cell"].reference, ctx["cell"].config = ouro.reference, ouro.config
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
    cfg = loader.read_json(f"{bench_paths.ROOT}/benchmark/configs/"
                           f"{CONFIG}.json")
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
        "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "use_sliding_window": True}
    for key, value in catalog.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 24576)
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                "vocab_size": 98304}
    assert cfg["deployment"]["chips_sharing_a_layer"] == 4
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 4 == cfg["published"]["num_experts"]
    assert (cfg["qk_norm"], cfg["router_activation"]) == (True, "softmax")
    for key in ("qk_norm", "router_activation", "window_convention",
                "auxiliary_loss", "mtp_head", "intermediate_size",
                "sequence_length", "weights", "compute_dtype", "updater"):
        assert key in cfg["assumed"], key
    assert cfg["program"]["attention_block"] == 512
    assert cfg["program"]["loss_block"] == 1024
    assert cfg["program"]["remat"] == "full"
    assert cfg["control_precision"] == "fp8"
    assert cfg["updater"]["learning_rate"] == 1e-5
    assert set(full.cell["limits"]) == {"loss", "grad_norm_worst",
                                        "grad_norm_median",
                                        "delta_norm_worst"}
    assert len(full.cell["limits"]["loss"]) == 3
    # each limit between its two readings on the chip (PERF.md section 4):
    # the sound runs' largest and the lowest reading of a planted fault
    for key, (sound, fault) in {"grad_norm_worst": (0.0024, 0.306),
                                "grad_norm_median": (5.2e-4, 0.0050),
                                "delta_norm_worst": (3.5e-4, 0.0034)}.items():
        assert 3 * sound <= full.cell["limits"][key] <= fault / 2, key
    # the traffic: the siblings' mix with the length doubled, nothing else
    assert full.cell["traffic"] == "fit_tokens_1x16384"
    sibling = loader.read_json(f"{bench_paths.ROOT}/benchmark/traffic/"
                               "fit_tokens_1x8192.json")
    assert json.loads(json.dumps(full.traffic).replace("16384", "8192")) \
        == sibling
    assert full.traffic["sequence_length"] == cfg["sequence_length"] == 16384
    # the manifest's entries for this configuration and cell
    manifest = loader.load_manifest(bench_paths.ROOT)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"]
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "fit_tokens_1x16384", 1)]
    # appended after the five cells the benchmark had, one of them on four
    # chips; what later PRs append comes after
    assert [w["name"] for w in manifest["workloads"]].index(CELL) == 5
    assert [w["chips"] for w in manifest["workloads"][:6]].count(4) == 1
    listed = [m["name"] for m in manifest["per_layer"]]
    at = listed.index(NEW_METRICS[0])
    assert listed[at:at + 7] == NEW_METRICS

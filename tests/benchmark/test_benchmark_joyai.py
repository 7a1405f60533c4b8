"""The ``joyai_llm_flash_48b_a2p7b_ep32`` configuration: the program against
its plain reference on the CPU at the file's ``rehearse`` size in float32
(forward, both loss terms, every gradient leaf, three steps of Adam, the
latent layer and the rotation alone, the routed layer's 32 shares with the
shared expert counted once against the uncut reference layer, the module's
loss term and the embedding's two uses), the cell through its driver with
the float8 control and two planted faults (the rotation left out, the
module fed the current token's embedding) failing, the scopes and counters
of the compiled step, the hand counts of parameters and FLOPs at the
published widths, each new per-layer reader on a synthetic trace, and the
manifest's entries."""

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

CELL = "joyai_flash_train_8k_ep32share"
CONFIG = "joyai_llm_flash_48b_a2p7b_ep32"
# float32 on the CPU, two orders of the same sums through four blocks
FORWARD_TOL = 5e-6      # softmax outputs, absolute
LOSS_TOL = 2e-6         # relative
GRAD_TOL = 1e-4         # a leaf's max |difference| over its max |value|
NEW_METRICS = ["rmla.device_ms_per_step", "rmla.attend_roofline_pct",
               "rmla.qlora_rope_device_ms_per_step", "mtp.device_ms_per_step",
               "moe768.device_ms_per_step", "moe768.experts_roofline_pct",
               "moe768.expert_load_max_over_mean"]


@pytest.fixture(scope="module")
def cell():
    return loader.resolve_cell(bench_paths.ROOT, CELL, rehearse=True)


@pytest.fixture(scope="module")
def full():
    return loader.resolve_cell(bench_paths.ROOT, CELL)


def _program_loss(net, x, y):
    import jax.numpy as jnp

    def loss(params):
        return net._loss_fn(params, net.state, [jnp.asarray(x)],
                            [jnp.asarray(y)], None, None, None)[0]
    return loss


def _flat(grads):
    return {f"{v}/{k}": a for v, leaves in grads.items()
            for k, a in leaves.items()}


@pytest.fixture(scope="module")
def sides(cell):
    """The network and the reference on the same seeded weights and ids,
    with both sides' loss and gradients. T = 200 in tiles of 64: not a
    multiple of the tile or of the loss block (64)."""
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
        loss_p, grads_p = jax.value_and_grad(_program_loss(net, x, y))(
            net.params)
        loss_r, grads_r = jax.value_and_grad(
            lambda p: ref.loss(cfg, p, jnp.asarray(x), jnp.asarray(y)))(p0)
        terms = ref.loss_terms(cfg, p0, jnp.asarray(x), jnp.asarray(y))
        probs_p = net.output(x)[0]
        probs_r = jax.nn.softmax(ref.logits(cfg, p0, jnp.asarray(x)), -1)
    return types.SimpleNamespace(
        cfg=cfg, ref=ref, net=net, p0=p0, x=x, y=y,
        loss_p=float(loss_p), loss_r=float(loss_r),
        terms=tuple(float(a) for a in terms),
        grads_p=_flat(grads_p), grads_r=grads_r,
        probs_p=np.asarray(probs_p), probs_r=np.asarray(probs_r))


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
    main, module = sides.terms
    assert sides.loss_r == pytest.approx(main + 0.3 * module, rel=1e-6)
    # seeded weights: both terms near ln(vocabulary), the module's over
    # 199 of 200 positions
    assert abs(main - math.log(sides.cfg["vocab_size"])) < 1.0
    assert abs(module - math.log(sides.cfg["vocab_size"])) < 1.0


@pytest.mark.parametrize("leaf", _rehearse_leaves())
def test_every_gradient_leaf_follows_the_reference(sides, leaf):
    got, want = np.asarray(sides.grads_p[leaf]), np.asarray(
        sides.grads_r[leaf])
    assert got.shape == want.shape and np.all(np.isfinite(got))
    assert np.max(np.abs(want)) > 0, "a leaf with no gradient tests nothing"
    assert np.max(np.abs(got - want)) < GRAD_TOL * np.max(np.abs(want))


def test_the_rehearsal_holds_what_the_cell_is_for(cell):
    """Every kind of block, the module on top, more than one attention
    tile, half the published experts held."""
    cfg, ref = cell.config, cell.reference
    assert [(b["name"], b["ffn"], b["module"]) for b in ref.blocks(cfg)] == [
        ("l1", "dense", False), ("l2", "moe", False), ("l3", "moe", False),
        ("mtp1", "moe", True)]
    assert cfg["sequence_length"] > cfg["program"]["attention_block"]
    assert cfg["sequence_length"] > cfg["program"]["loss_block"]
    assert cfg["q_lora_rank"] and cfg["rope_interleave"]
    assert cfg["qk_rope_head_dim"] % 2 == 0
    assert cfg["n_routed_experts"] * 2 == cfg["published"]["n_routed_experts"]
    assert cfg["mtp_weight"] == 0.3 and cfg["num_nextn_predict_layers"] == 1


# ------------------------------------------------------------ layer by layer
def test_the_reference_s_rotation_is_the_program_s(cell):
    """Two ways to one rotation: adjacent pairs by reshape (reference) and
    by a shift of one along the widths (program)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.attention import rotate_interleaved

    x = jax.random.normal(jax.random.key(0), (2, 70, 3, 8))
    got = rotate_interleaved(x, jnp.arange(70), 32e6)
    want = cell.reference.rotate(x, 32e6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    shared = x[:, :, 0]
    np.testing.assert_allclose(
        np.asarray(rotate_interleaved(shared, jnp.arange(70), 32e6)),
        np.asarray(cell.reference.rotate(shared, 32e6)), atol=1e-5)


@pytest.fixture(scope="module")
def latent(cell):
    """One latent layer of the rehearse size alone, both sides' output and
    gradients (T = 150 in tiles of 64)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.attention import MultiHeadLatentAttention

    cfg, ref = cell.config, cell.reference
    m = ref.dims(cfg)
    p = {k.split("/")[1]: v for k, v in ref.init_params(cfg, 3).items()
         if k.startswith("l2_attn/")}
    layer = MultiHeadLatentAttention(
        n_heads=m["heads"], nope_dim=m["nope"], rope_dim=m["rope"],
        v_dim=m["v_dim"], kv_rank=m["kv_rank"], q_rank=m["q_rank"],
        rope_theta=m["theta"], block=64, eps=m["eps"])
    x = jax.random.normal(jax.random.key(1), (2, 150, m["d"]))
    w = jax.random.normal(jax.random.key(2), (2, 150, m["d"]))

    def program(p, x):
        return layer.apply(p, {}, x)[0]

    def reference(p, x):
        return ref.attention(m, {"a/" + k: v for k, v in p.items()}, "a/", x,
                             "highest")

    def both(f):
        out = f(p, x)
        return out, jax.grad(lambda p, x: jnp.sum(f(p, x) * w),
                             argnums=(0, 1))(p, x)

    with jax.default_matmul_precision("highest"):
        return both(program), both(reference), layer, p, x


def test_the_latent_layer_alone_follows_the_reference(latent):
    (out_p, _), (out_r, _), layer, p, x = latent
    assert float(np.max(np.abs(out_p - out_r))) < 1e-5
    # and the reference WITHOUT its rotation is another function
    import jax
    with jax.default_matmul_precision("highest"):
        bare = dataclasses.replace(layer, rope_theta=0.0).apply(p, {}, x)[0]
    assert float(np.max(np.abs(bare - out_r))) > 1e-3


@pytest.mark.parametrize("leaf", ["Wqa", "q_norm", "Wqb", "Wkva", "kv_norm",
                                  "Wkvb", "Wo", "x"])
def test_the_latent_layer_s_gradients_follow_the_reference(latent, leaf):
    (_, (gp, gx)), (_, (rp, rx)), *_ = latent
    got, want = (gx, rx) if leaf == "x" else (gp[leaf], rp[leaf])
    assert float(np.max(np.abs(want))) > 0
    assert float(np.max(np.abs(got - want))) < GRAD_TOL * float(
        np.max(np.abs(want)))


def test_the_32_shares_add_up_to_the_uncut_reference_layer():
    """The guide's shares test at the rehearse widths with the PUBLISHED
    router (256 outputs, top-8, x 2.5): the program's 32 shares of 8
    experts each (offsets 0, 8, .. 248), the shared expert counted ONCE,
    add up to what the reference gives for the uncut layer of 256."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.conf.experts import RoutedExperts

    ref = _reference_module()
    cfg = _config_file()
    cfg = {**cfg, **cfg["rehearse"], "n_routed_experts": 256,
           "num_experts_per_tok": 8,
           "published": {"n_routed_experts": 256}}
    m = ref.dims(cfg)
    p = {k: v for k, v in ref.init_params(cfg, 5).items()
         if k.startswith("l2_ffn/")}
    d, t = cfg["hidden_size"], 100
    x = jax.random.normal(jax.random.key(1), (2, t, d))
    it = InputType.recurrent(d, t)
    own = {k.split("/")[1]: v for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want = ref.moe(m, p, "l2_ffn/", x, "highest")
        shared = ref.shared_part(p, "l2_ffn/", x, "highest")
        total = -31.0 * shared          # every share brings it: count once
        for share in range(32):
            layer = RoutedExperts(
                n_experts=256, experts_held=8, expert_offset=8 * share,
                top_k=8, expert_size=cfg["moe_intermediate_size"],
                shared_size=cfg["moe_intermediate_size"], scaling=2.5)
            mine = {k: (v[8 * share:8 * share + 8]
                        if k in ("Wgate", "Wup", "Wdown") else v)
                    for k, v in own.items()}
            part, state = layer.apply(
                mine, layer.init(jax.random.key(0), it)[1], x)
            assert int(state["pairs_dropped"]) == 0
            total = total + part
    assert float(jnp.max(jnp.abs(shared))) > 0.1
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5 * max(
        1.0, float(jnp.max(jnp.abs(want))))


@pytest.fixture(scope="module")
def by_weight(cell, sides):
    """The program's loss and embedding gradient at three module weights,
    on ``sides``' weights and ids."""
    import jax

    out = {}
    with jax.default_matmul_precision("highest"):
        for w in (0.0, 1.0):
            cfg = dict(sides.cfg, mtp_weight=w)
            net = cell.build(cfg, dict(sides.p0))
            value, grads = jax.value_and_grad(
                _program_loss(net, sides.x, sides.y))(net.params)
            out[w] = (float(value), _flat(grads))
    return out


def test_the_module_alone_is_the_second_loss_term(sides, by_weight):
    """L(lambda) = L_main + lambda L_mtp: the program at lambda 0 and 1
    against the reference's two terms."""
    main, module = sides.terms
    assert by_weight[0.0][0] == pytest.approx(main, rel=LOSS_TOL)
    assert by_weight[1.0][0] - by_weight[0.0][0] == pytest.approx(
        module, rel=1e-5)
    assert sides.loss_p == pytest.approx(
        by_weight[0.0][0] + 0.3 * (by_weight[1.0][0] - by_weight[0.0][0]),
        rel=1e-6)


def test_the_embedding_s_gradient_holds_both_uses(sides, by_weight):
    """The module's use of the one table (the next token's embedding)
    reaches ``embed/W`` beside the trunk's: the gradient at lambda is the
    trunk's plus lambda times the module's, and the module's is the
    reference's gradient of L_mtp alone."""
    import jax
    import jax.numpy as jnp

    g0, g1 = (by_weight[w][1]["embed/W"] for w in (0.0, 1.0))
    module = np.asarray(g1 - g0)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: sides.ref.loss_terms(
            sides.cfg, p, jnp.asarray(sides.x), jnp.asarray(sides.y))[1])(
                sides.p0)["embed/W"]
    assert np.max(np.abs(want)) > 0
    assert np.max(np.abs(module - want)) < GRAD_TOL * np.max(np.abs(want))
    np.testing.assert_allclose(np.asarray(sides.grads_p["embed/W"]),
                               np.asarray(g0) + 0.3 * module, atol=1e-7)
    # with no weight on it the module learns nothing and the trunk's
    # leaves get the main loss's gradient alone
    for leaf, g in by_weight[0.0][1].items():
        if leaf.startswith("mtp1_"):
            assert float(np.max(np.abs(g))) == 0.0, leaf


def test_the_module_reads_the_next_token_and_masks_its_last_position(sides):
    """The reference embeds the LABELS (position T gets Emb(t_{T+1})), the
    program shifts its embedding vertex (position T gets zeros): they agree
    (``sides``), because nothing scored reads position T. The label of the
    module's position i is the id two steps on."""
    import jax
    import jax.numpy as jnp

    cfg, ref = sides.cfg, sides.ref
    x, y = jnp.asarray(sides.x), jnp.asarray(sides.y)
    with jax.default_matmul_precision("highest"):
        _, base = ref.loss_terms(cfg, sides.p0, x, y)
        # the last label is position T's next token and position T - 1's
        # second next: L_mtp moves with it through T - 1 alone
        other = y.at[:, -1].set((y[:, -1] + 1) % cfg["vocab_size"])
        _, moved = ref.loss_terms(cfg, sides.p0, x, other)
        # the module's state at position T under another next token
        _, state_a = ref.states(cfg, sides.p0, x, y)
        _, state_b = ref.states(cfg, sides.p0, x, other)
    assert float(moved) != pytest.approx(float(base), rel=1e-7)
    # causal: only position T's state saw the other embedding
    assert float(jnp.max(jnp.abs(state_a[:, :-1] - state_b[:, :-1]))) == 0.0
    assert float(jnp.max(jnp.abs(state_a[:, -1] - state_b[:, -1]))) > 1e-3


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


# ------------------------------------------------------- through the driver
STEP_COUNTERS = {"attention.mla_blocked": 4, "attention.mla_rotary": 4,
                 "attention.mla_q_lora": 4, "kernel.xla_blocked_attention": 4,
                 "mtp.modules": 1, "loss.blocked_one_pass": 1}


def test_the_cell_runs_through_its_driver_and_the_control_fails(cell, tmp_path):
    """Set-up's first steps through ``net.fit(DevicePrefetchIterator)``,
    the reference after them: ``correct`` in float32 within the cell's
    limits, the float8 control outside one of them, no pair dropped, the
    step's trace-time counters read (on the CPU the ``jax.numpy`` tiles;
    the chip's step reads ``kernel.pallas_blocked_attention`` 7 with 7
    latent blocks)."""
    import jax

    quiet = lambda *a: None
    session = cell.driver.setup(cell, jax.devices()[:1], 2_147_483_999, quiet)
    raw = cell.driver.run_window(session, 0.3, None)
    assert raw["steps"] > 0 and raw["compiles_in_window"] == 0
    assert raw["failed"] == 0 and raw["moe_dropped_tokens_total"] == 0
    assert raw["items"] == raw["steps"] * 2 * 128
    assert sum(raw["moe_pairs_held_in_window"].values()) > 0
    view = cell.program_view
    assert set(view["moe"]) == {"l2_ffn", "l3_ffn", "mtp1_ffn"}
    # a traced window on the same session: the text is the executable's own
    took = feed.TraceSlice(str(tmp_path), 0.05, 0.05)
    raw = cell.driver.run_window(session, 0.4, took)
    view = cell.program_view
    assert took.done and raw["compiles_for_hlo_text"] == 0
    assert raw["compiles_in_window"] == 0 and raw["failed"] == 0
    for name in ("l1_attn", "l2_attn", "l3_attn", "mtp1_attn"):
        assert f"MultiHeadLatentAttention:{name}" in view["hlo_text"]
    assert 0 < view["moe_slice"]["steps"] <= raw["steps"]
    counters = session.net.compile_watch.counters()
    assert {k: counters.get(k, 0) for k in STEP_COUNTERS} == STEP_COUNTERS
    assert counters.get("kernel.pallas_blocked_attention", 0) == 0
    assert counters.get("moe.dispatch_gather", 0) \
        + counters.get("moe.dispatch_scatter", 0) == 3
    ok, rows = cell.driver.check(session, quiet)
    assert ok, rows
    ok, rows = cell.driver.control(session, quiet)
    assert not ok, rows


@contextlib.contextmanager
def planted(fault: str):
    """The program with one fault in it, for as long as the block lasts
    (the step is traced inside set-up):

    * ``rotation_left_out``: every ``MultiHeadLatentAttention`` runs
      without its rotation, whatever its ``rope_theta``;
    * ``module_reads_this_token``: ``TimeShiftVertex`` hands its input on
      unshifted, so the module is fed Emb(t_i) in place of Emb(t_{i+1})."""
    from deeplearning4j_tpu.nn.conf.attention import MultiHeadLatentAttention
    from deeplearning4j_tpu.nn.conf.graph import TimeShiftVertex

    if fault == "rotation_left_out":
        cls, sound = MultiHeadLatentAttention, MultiHeadLatentAttention.apply

        def faulty(self, params, state, x, **kw):
            return sound(dataclasses.replace(self, rope_theta=0.0), params,
                         state, x, **kw)
    elif fault == "module_reads_this_token":
        cls, sound = TimeShiftVertex, TimeShiftVertex.apply

        def faulty(self, *inputs):
            return inputs[0]
    else:
        raise KeyError(fault)
    cls.apply = faulty
    try:
        yield
    finally:
        cls.apply = sound


# what each fault has to trip at the least, of the cell's own limits
FAULTS = {"rotation_left_out": {"grad_norm.worst_leaf",
                                "grad_norm.median_leaf",
                                "delta_norm.worst_leaf"},
          "module_reads_this_token": {"grad_norm.worst_leaf",
                                      "grad_norm.median_leaf",
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


SCOPES = ["mla.q_lora", "mla.rope", "mla.attend", "mtp.combine", "moe.route",
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
    layer = {"mla": "MultiHeadLatentAttention:", "moe": "RoutedExperts:",
             "mtp": "MultiTokenCombine:mtp1_combine",
             "loss": ""}[scope.split(".")[0]]
    under = [o for o in step_op_names if scope in o and layer in o]
    assert any("transpose(" not in o for o in under), scope
    assert any("transpose(" in o for o in under), scope


def test_the_module_s_block_is_told_from_the_trunk_s_by_name(step_op_names):
    """What ``mtp.device_ms_per_step`` matches: every vertex of the module
    under ``<Class>:mtp1_*``, the trunk's under ``<Class>:l<i>_*``."""
    reader = loader.import_file(
        f"{bench_paths.ROOT}/benchmark/layer_metrics/"
        "mtp.device_ms_per_step.py", "layer_metric")
    mine = {m for o in step_op_names
            for m in re.findall(r"(\w+:mtp1_\w+)", o)
            if reader._MODULE.search(o)}
    assert {"TimeShiftVertex:mtp1_shift", "StackStatesVertex:mtp1_in",
            "MultiTokenCombine:mtp1_combine",
            "MultiHeadLatentAttention:mtp1_attn", "RoutedExperts:mtp1_ffn",
            "RMSNorm:mtp1_norm", "ElementWiseVertex:mtp1_ffn_add"} <= mine
    assert not reader._MODULE.search(
        "jit(train_step)/jvp(MultiHeadLatentAttention:l2_attn)/mla.rope/mul")
    assert reader._MODULE.search(
        "jit(train_step)/transpose(jvp(RoutedExperts:mtp1_ffn))/moe.experts")


# ------------------------------------------------------------- hand counts
def test_parameter_hand_count_at_the_published_widths(full):
    """ISSUE 39's count, reckoned again: every width as published, 6 of 40
    layers, 8 of 256 experts, 16,160 of 129,280 rows. The routers'
    selection bias (6 x 256) is frozen layer state, not a parameter: the
    issue's 561,040,896 counts it."""
    d = 2048
    attn = (d * 1536 + 1536 + 1536 * 32 * 192 + d * (512 + 64) + 512
            + 512 * 32 * (128 + 128) + 32 * 128 * d)
    expert = 3 * d * 768
    dense = attn + 2 * d + 3 * d * 7168
    routed = attn + 2 * d + d * 256 + expert + 8 * expert
    module = 2 * d + 2 * d * d + routed + d
    slice_ = 2 * 16160 * d
    total = dense + 5 * routed + module + slice_ + d
    assert (attn, expert) == (26_347_520, 4_718_592)
    assert dense == 70_391_808
    assert routed == 69_343_488 - 256
    assert module == 77_738_240 - 256
    assert slice_ == 66_191_360
    assert total == 561_040_896 - 6 * 256 == 561_039_360
    assert full.reference.count_params(full.config) == total
    assert full.config["parameters"]["trained"] == total
    assert [(b["name"], b["ffn"]) for b in
            full.reference.blocks(full.config)] == [
        ("l1", "dense")] + [(f"l{i}", "moe") for i in range(2, 7)] + [
        ("mtp1", "moe")]


def test_the_zoo_builder_draws_that_many_from_the_public_keys(full):
    """``models.JoyAIFlash`` from the public config's keys alone, cut by
    its arguments: (shapes only, nothing drawn) 561,039,360 parameters and
    6 x 256 numbers of router bias in state; the whole published model 50
    billion with its prediction module (1.2 of them), about 3 a token."""
    import jax
    from deeplearning4j_tpu.models import JoyAIFlash
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    public = full.adapter.public_config(full.config)
    assert (public["num_hidden_layers"], public["n_routed_experts"],
            public["vocab_size"]) == (40, 256, 129280)

    def count(zoo):
        net = ComputationGraph(zoo.conf())
        drawn, state, _ = jax.eval_shape(net._draw, jax.random.key(0))
        bias = sum(math.prod(s["bias"].shape) for s in state.values()
                   if "bias" in s)
        return bias, sum(math.prod(a.shape)
                         for a in jax.tree_util.tree_leaves(drawn))

    bias, n = count(JoyAIFlash(public, layers=6, experts_held=8,
                               vocab_rows=16160, sequence_length=8192))
    assert (bias, n) == (6 * 256, 561_039_360)
    bias, n = count(JoyAIFlash(public))
    assert bias == 40 * 256                  # 39 routed layers + the module's
    assert 49e9 < n < 51e9
    # a token runs 8 of 256 experts in each of the 40 routed blocks
    active = n - 40 * (256 - 8) * 3 * 2048 * 768
    assert 2.5e9 < active < 3.5e9


def test_flop_hand_count_at_the_published_widths(full):
    """Forward matrix-product FLOPs a token at T = 8192: seven latent
    blocks' scores and values at (T + 1) / 2 keys a query."""
    d, t = 2048, 8192
    keys = (t + 1) / 2
    attn = 2 * (d * 1536 + 1536 * 32 * 192 + d * 576 + 512 * 32 * 256
                + 32 * (192 + 128) * keys + 4096 * d)
    routed = 2 * (d * 256 + 3 * d * 768 + 3 * d * 768 * 8 * 8 / 256)
    want = 7 * attn + 2 * 3 * d * 7168 + 6 * routed + 2 * 2 * d * d \
        + 2 * 2 * d * 16160
    got = flops.forward_flops_per_item(full.reference.layers(full.config))
    assert got == pytest.approx(want, rel=1e-12)
    assert 1.26e9 < got < 1.28e9             # 1.27 GFLOP a token forward
    products = 7 * 2 * 32 * (192 + 128) * keys
    assert 0.45 < products / got < 0.47      # 46% of it the tile pairs
    assert 31.0e12 < 3 * got * t < 31.4e12   # 31.2 TFLOP a step


def test_kernel_cost_functions(full):
    ref, cfg = full.reference, full.config
    t = 8192
    cost = ref.mla_attend_cost(cfg, t)
    # 32 heads, q k^T over 192 widths and p v over 128, the kept positions
    assert cost["flops"] == 32 * (t * (t + 1) // 2) * 2 * (192 + 128)
    # under the 136 tile pairs of 512 x 512 that hold them
    assert cost["flops"] < 32 * 136 * 2 * 512 * 512 * 320
    assert cost["flops"] == pytest.approx(
        32 * 128.02 * 2 * 512 * 512 * 320, rel=1e-3)
    # q and the output a head, k_nope and v a head, the rotated key once
    assert cost["bytes"] == 2 * t * (32 * (192 + 128 + 128 + 128) + 64)
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9      # MXU bound
    # the Kimi cell's latent layer at the same kernel shape counts alike
    assert ref.kept_positions(t) == t * (t + 1) // 2
    moe = ref.moe_experts_cost(cfg, 2048, 8)
    assert moe["flops"] == 2048 * 3 * 2 * 2048 * 768
    assert moe["bytes"] > 8 * 3 * 2048 * 768 * 2        # the weights, bf16
    # at 256 tokens an expert the grouped products are bound by bytes
    assert moe["flops"] / 197e12 < moe["bytes"] / 819e9


# ------------------------------------------------------------ the readers
_HLO = '''
HloModule jit_train_step
%fused_computation.1 { ... }
ENTRY %main {
  %custom-call.1 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(MultiHeadLatentAttention:l1_attn)/mla.attend/jit(_forward)/mla_attend_fwd/pallas_call" source_file="x.py" source_line=1}
  %fusion.9 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(MultiHeadLatentAttention:l1_attn)/mla.rope/mul"}
  %fusion.10 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(MultiHeadLatentAttention:l2_attn))/mla.q_lora/dot_general"}
  %custom-call.2 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(MultiHeadLatentAttention:mtp1_attn))/mla.attend/jit(_backward)/mla_attend_bwd/pallas_call"}
  %fusion.8 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(MultiTokenCombine:mtp1_combine)/mtp.combine/dot_general"}
  %fusion.7 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(TimeShiftVertex:mtp1_shift)/concatenate"}
  %custom-call.4 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(RoutedExperts:l2_ffn)/moe.experts/pallas_call"}
  ROOT %fusion.5 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(RoutedExperts:mtp1_ffn)/moe.route/mul"}
  %fusion.6 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/optim.update/mul"}
}
'''


def _ctx(full, with_view=True):
    ms = 1e-3
    ops = [("%custom-call.1 = bf16[8]{0} custom-call(%p0)", 0 * ms, 40 * ms),
           ("%fusion.9 = bf16[8]{0} fusion(%p0)", 40 * ms, 41 * ms),
           ("%fusion.10 = bf16[8]{0} fusion(%p0)", 41 * ms, 43 * ms),
           ("%custom-call.2 = bf16[8]{0} custom-call(%p0)", 43 * ms, 103 * ms),
           ("%fusion.8 = bf16[8]{0} fusion(%p0)", 103 * ms, 104 * ms),
           ("%fusion.7 = bf16[8]{0} fusion(%p0)", 104 * ms, 104.5 * ms),
           ("%custom-call.4 = bf16[8]{0} custom-call(%p0)", 105 * ms,
            113 * ms),
           ("%fusion.5 = f32[8]{0} fusion(%p0)", 113 * ms, 114 * ms),
           ("%fusion.6 = f32[8]{0} fusion(%p0)", 114 * ms, 120 * ms)]
    # two steps, the second a copy of the first 130 ms later
    ops = ops + [(n, s + 130 * ms, e + 130 * ms) for n, s, e in ops]
    modules = [("jit_train_step", 0.0, 120 * ms),
               ("jit_train_step", 130 * ms, 250 * ms)]
    cell = types.SimpleNamespace(reference=full.reference,
                                 config=full.config, traffic=full.traffic,
                                 layer_reader=full.layer_reader)
    if with_view:
        tokens = [400] + [250] * 6 + [148]
        cell.program_view = {
            "hlo_text": _HLO, "tokens_per_step": 8192,
            "moe": {"l2_ffn": {"expert_tokens": tokens,
                               "pairs_held": sum(tokens),
                               "pairs_dropped": 0}}}
        # the slice's own steps: two of them, 2,048 pairs each
        cell.program_view["moe_slice"] = {"steps": 2, "layers": {
            "l2_ffn": {"expert_tokens": [2 * n for n in tokens],
                       "pairs_held": 2 * sum(tokens), "pairs_dropped": 0}}}
    return {"cell": cell, "raw": {"steps": 7},
            "trace": trace.Trace([trace.DeviceTimeline(0, ops, modules)], []),
            "chips": 1, "peaks": peaks.peaks_for("TPU v5 lite")}


def _read(full, name, ctx):
    return full.layer_reader(name)(ctx)


def test_device_ms_per_step_by_what_the_configuration_adds(full):
    ctx = _ctx(full)
    # every latent layer, the module's too: 40 + 1 + 2 + 60 ms
    assert _read(full, "rmla.device_ms_per_step", ctx) == pytest.approx(103.0)
    # mla.rope 1 ms + mla.q_lora 2 ms
    assert _read(full, "rmla.qlora_rope_device_ms_per_step", ctx) == \
        pytest.approx(3.0)
    # the module's vertices: its attention 60, combine 1, shift 0.5,
    # router 1
    assert _read(full, "mtp.device_ms_per_step", ctx) == pytest.approx(62.5)
    # both routed layers: 8 + 1 ms
    assert _read(full, "moe768.device_ms_per_step", ctx) == pytest.approx(9.0)


def test_roofline_shares_are_least_time_over_measured_time(full):
    ctx = _ctx(full)
    ref, cfg = full.reference, full.config
    # seven latent blocks, the forward twice and a backward of 2.5
    # forwards, 100 ms under their mla.attend
    one = ref.mla_attend_cost(cfg, 8192)
    least = one["flops"] / 197e12 * 7 * 4.5
    got = _read(full, "rmla.attend_roofline_pct", ctx)
    assert got == pytest.approx(100 * least / 100e-3)
    assert 100 < got < 120          # 110 ms is the least the chip can take
    moe = ref.moe_experts_cost(cfg, 2048, 8)
    least = max(moe["flops"] / 197e12, moe["bytes"] / 819e9) * 4
    assert _read(full, "moe768.experts_roofline_pct", ctx) == pytest.approx(
        100 * least / 8e-3)


def test_expert_load_is_the_busiest_over_the_mean(full):
    assert _read(full, "moe768.expert_load_max_over_mean",
                 _ctx(full)) == pytest.approx(400 / 256)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_view_reports_nothing(full, name):
    """Where the driver kept no program view the readers return None and
    do not raise."""
    assert _read(full, name, _ctx(full, with_view=False)) is None


@pytest.mark.parametrize("name", ["rmla.attend_roofline_pct",
                                  "rmla.qlora_rope_device_ms_per_step",
                                  "mtp.device_ms_per_step"])
def test_a_program_without_the_scopes_reports_nothing(full, name):
    """On the Kimi cell's reference and a program with neither scope nor
    module (this PR's parent): nothing, no raise."""
    kimi = loader.resolve_cell(bench_paths.ROOT,
                               "kimi_linear_train_8k_ep32share")
    ctx = _ctx(full)
    ctx["cell"].reference, ctx["cell"].config = kimi.reference, kimi.config
    ctx["cell"].program_view["hlo_text"] = re.sub(
        r"mla\.rope|mla\.q_lora|mtp1_", "x", _HLO)
    ctx["cell"].program_view.pop("_scopes", None)
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
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in catalog.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 8, 16160)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "n_routed_experts": 256,
                                "vocab_size": 129280}
    assert cfg["deployment"]["chips_sharing_a_layer"] == 32
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 32 == \
        cfg["published"]["n_routed_experts"]
    for part in ("experts", "vocabulary", "attention", "depth",
                 "multi_token_prediction", "exchange"):
        assert part in cfg["deployment"], part
    assert "LAST stage" in cfg["deployment"]["depth"]
    for key in ("mtp_weight", "mtp_combine_order", "mtp_state", "mtp_mean",
                "router_bias", "sequence_length", "weights", "compute_dtype",
                "updater"):
        assert key in cfg["assumed"], key
    assert cfg["mtp_weight"] == 0.3 and cfg["sequence_length"] == 8192
    assert cfg["program"]["attention_block"] == 512
    assert cfg["program"]["loss_block"] == 1024
    assert cfg["program"]["remat"] == "full"
    assert cfg["control_precision"] == "fp8"
    assert cfg["updater"]["learning_rate"] == 1e-5
    assert set(full.cell["limits"]) == {"loss", "grad_norm_worst",
                                        "grad_norm_median",
                                        "delta_norm_worst"}
    assert len(full.cell["limits"]["loss"]) == 3
    for key in ("readings", "loss", "grad_norm_worst", "grad_norm_median",
                "delta_norm_worst", "control", "planted_faults"):
        assert key in full.cell["limits_why"], key
    # each limit against its readings on the chip (PERF.md section 4): over
    # the sound runs' largest; the gradient limits under the lowest reading
    # of a planted fault, the change's under the control's
    for key, (sound, refused) in {"grad_norm_worst": (0.00503, 0.0135),
                                  "grad_norm_median": (2.82e-4, 5.23e-4),
                                  "delta_norm_worst": (1.13e-3, 1.0)}.items():
        assert 1.3 * sound <= full.cell["limits"][key] <= refused / 1.3, key
    assert all(3 * 5.4e-5 <= x for x in full.cell["limits"]["loss"])
    # the traffic: the siblings' file, as it is
    assert full.cell["traffic"] == "fit_tokens_1x8192"
    assert full.traffic["sequence_length"] == cfg["sequence_length"]
    # the manifest's entries for this configuration and cell
    manifest = loader.load_manifest(bench_paths.ROOT)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"].startswith(cfg["source"])
    assert "arXiv:2412.19437" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "fit_tokens_1x8192", 1)]
    assert len(cells[0]["why"]) <= 200
    # appended after the six cells the benchmark had, one of them on four
    # chips; held by .index, so that what later PRs append breaks nothing
    names = [w["name"] for w in manifest["workloads"]]
    at = names.index(CELL)
    assert at == 6
    assert [w["chips"] for w in manifest["workloads"][:at + 1]].count(4) == 1
    listed = [m["name"] for m in manifest["per_layer"]]
    first = listed.index(NEW_METRICS[0])
    assert listed[first:first + 7] == NEW_METRICS
    assert json.dumps(manifest).count(CELL) == 1 + 7

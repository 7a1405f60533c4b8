"""The ``lfm2_8b_a1b_ep4`` configuration: the program against its plain
reference on the CPU at the file's ``rehearse`` size in float32 (forward,
loss, every gradient leaf, three steps of Adam, each new layer kind alone,
the four expert shares of a routed layer against the uncut reference
layer, the tied leaf's two gradient terms), the cell through its driver
with the float8 control and three planted faults (the short convolution's
output gate left out, the tied head's gradient cut, the labels one step
late) failing, the scopes and counters of the compiled step, the hand counts of parameters and FLOPs at
the published widths, and each new per-layer reader on a synthetic trace."""

import contextlib
import json
import math
import re
import types

import numpy as np
import pytest

import bench_paths
from harness import feed, flops, loader, peaks, trace

CELL = "lfm2_train_16k_ep4share"
CONFIG = "lfm2_8b_a1b_ep4"
# float32 on the CPU, two orders of the same sums through six blocks
FORWARD_TOL = 5e-6      # softmax outputs, absolute
LOSS_TOL = 2e-6         # relative
GRAD_TOL = 1e-4         # a leaf's max |difference| over its max |value|
NEW_METRICS = ["sconv.device_ms_per_step", "sconv.gate_conv_roofline_pct",
               "attn64.device_ms_per_step", "attn64.attend_roofline_pct",
               "moe1792.device_ms_per_step", "moe1792.experts_roofline_pct",
               "moe1792.expert_load_max_over_mean",
               "tiedhead.loss_device_ms_per_step"]
KINDS = ["conv", "conv", "full", "conv", "conv", "conv"]


@pytest.fixture(scope="module")
def cell():
    return loader.resolve_cell(bench_paths.ROOT, CELL, rehearse=True)


@pytest.fixture(scope="module")
def full():
    return loader.resolve_cell(bench_paths.ROOT, CELL)


@pytest.fixture(scope="module")
def sides(cell):
    """The network and the reference on the same seeded weights and ids,
    with both sides' loss and gradients. T = 150 in tiles of 32: not a
    multiple of the tile or of the loss block (64)."""
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
    """The program's views and the reference name one set of leaves: the
    tied matrix is ``embed/W`` on both sides and ``head/W`` on neither."""
    flat = set(sides.grads_p)
    assert flat == set(sides.grads_r) == set(_rehearse_leaves())
    assert "embed/W" in flat and "head/W" not in flat
    assert sides.net.params["head"] == {}
    assert sides.net.vertices["head"][0].tied_to == "embed"
    untied = {**sides.cfg, "tie_word_embeddings": False}
    assert "head/W" in sides.ref.param_shapes(untied)


def test_the_rehearsal_holds_what_the_cell_is_for(cell, full):
    """The cell's six layers in their published order and kinds, a quarter
    of the experts held and an eighth of them a token, as in the cell."""
    for c in (cell, full):
        cfg, ref = c.config, c.reference
        assert [b["attn"] for b in ref.blocks(cfg)] == KINDS
        assert [b["ffn"] for b in ref.blocks(cfg)] == ["dense"] * 2 + [
            "moe"] * 4
        assert cfg["num_experts"] * 4 == cfg["published"]["num_experts"]
        assert cfg["num_experts_per_tok"] * 8 == \
            cfg["published"]["num_experts"]
        assert cfg["tie_word_embeddings"] is True
    cfg = cell.config
    assert cfg["sequence_length"] // cfg["program"]["attention_block"] == 4
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == 16
    assert full.config["hidden_size"] // full.config[
        "num_attention_heads"] == 64


@pytest.mark.parametrize("kind", ["conv", "full", "moe"])
def test_each_layer_kind_alone_follows_the_reference(sides, kind):
    """One layer's ``apply`` on the reference's leaves against the
    reference's function for it: output and the gradient of every leaf and
    of the input."""
    import jax
    import jax.numpy as jnp

    ref, cfg = sides.ref, sides.cfg
    blk = next(b for b in ref.blocks(cfg) if kind in (b["attn"], b["ffn"]))
    vertex = blk["name"] + {"conv": "_conv", "full": "_attn",
                            "moe": "_ffn"}[kind]
    layer = sides.net.vertices[vertex][0]
    own = {k.split("/")[1]: v for k, v in sides.p0.items()
           if k.startswith(vertex + "/")}
    x = jax.random.normal(jax.random.key(3), (2, 150, cfg["hidden_size"]))
    fn = {"conv": ref.short_conv, "full": ref.attention, "moe": ref.moe}[kind]

    def program(own, x):
        return layer.apply(own, sides.net.state[vertex], x)[0]

    def reference(own, x):
        p = {vertex + "/" + k: v for k, v in own.items()}
        return fn(ref.dims(cfg), p, vertex + "/", x, "highest")

    def run(fn):
        def loss(own, x):
            o = fn(own, x)
            return jnp.sum(jnp.sin(o)), o
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(own, x)

    with jax.default_matmul_precision("highest"):
        got, want = run(program), run(reference)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * max(
            1.0, float(jnp.max(jnp.abs(b))))


def test_the_router_adds_its_epsilon_to_the_chosen_scores_sum(sides):
    """``renorm_eps`` is a field on both sides: with it the four weights
    sum to scale x s / (s + eps), and the program's layer holds the file's
    value."""
    import jax
    import jax.numpy as jnp

    ref, cfg = sides.ref, sides.cfg
    layer = sides.net.vertices["l2_ffn"][0]
    assert layer.renorm_eps == cfg["renorm_eps"] == 1e-6
    assert (layer.router_activation, layer.shared_size, layer.scaling) == (
        "sigmoid", 0, 1.0)
    x = jax.random.normal(jax.random.key(0), (40, cfg["hidden_size"]))
    w_r = sides.p0["l2_ffn/Wr"]
    big = {**ref.dims(cfg), "renorm_eps": 0.5}
    with jax.default_matmul_precision("highest"):
        w, idx = ref.route(big, x, w_r, jnp.zeros(w_r.shape[1]), "highest")
        s = jnp.take_along_axis(jax.nn.sigmoid(x @ w_r), idx, -1).sum(-1)
    np.testing.assert_allclose(w.sum(-1), s / (s + 0.5), rtol=1e-5)


def test_the_tied_leaf_s_gradient_is_the_sum_of_its_two_uses(sides):
    """In the REFERENCE too: the head's term and the gather's, each alone
    by ``stop_gradient``, add up to the one leaf's gradient that the
    program's matched above."""
    import jax
    import jax.numpy as jnp

    ref, cfg, p0 = sides.ref, sides.cfg, sides.p0
    x, y = jnp.asarray(sides.x), jnp.asarray(sides.y)

    def loss(table, cut_head, cut_gather):
        cut = jax.lax.stop_gradient
        head = cut(table) if cut_head else table
        gathered = (cut(table) if cut_gather else table)[x]
        # the reference's own lines with the two uses apart
        h = gathered
        for blk in ref.blocks(cfg):
            own = {k: v for k, v in p0.items()
                   if k.startswith(blk["name"] + "_")}
            h = ref._block(json.dumps(cfg, sort_keys=True),
                           json.dumps(blk, sort_keys=True), "highest", own, h)
        h = ref.norm(h, p0["final_norm/g"], cfg["norm_eps"])
        logp = jax.nn.log_softmax(h @ head.T, -1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))

    with jax.default_matmul_precision("highest"):
        grad = jax.jit(jax.grad(loss), static_argnums=(1, 2))
        both = grad(p0["embed/W"], False, False)
        head_only = grad(p0["embed/W"], False, True)
        gather_only = grad(p0["embed/W"], True, False)
    scale = float(jnp.max(jnp.abs(both)))
    assert float(jnp.max(jnp.abs(both - sides.grads_r["embed/W"]))) \
        < 1e-5 * scale
    assert float(jnp.max(jnp.abs(both - head_only - gather_only))) \
        < 1e-5 * scale
    assert float(jnp.max(jnp.abs(head_only))) > 0.01 * scale
    assert float(jnp.max(jnp.abs(gather_only))) > 0.01 * scale


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
            if i == 0:
                first = cell.adapter.first_moment_flat(net)
        out = ref.train_steps(cfg, ref.init_params(cfg, 11), batches)
        now = cell.adapter.params_flat(net)
        start = ref.init_params(cfg, 11)
        moved = {k: float(jnp.linalg.norm(now[k] - start[k])) for k in now}
    assert set(first) == set(now) == set(out["delta_norms"])
    assert "head/W" not in first
    for got, want in zip(losses, out["losses"]):
        assert abs(got - want) < 1e-5 * abs(want)
    for leaf, want in out["delta_norms"].items():
        assert abs(moved[leaf] - want) <= 2e-3 * max(want, 1e-9), leaf
    assert min(out["delta_norms"].values()) > 0      # every leaf moved


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The guide's shares test on a routed layer: the PROGRAM's four shares
    of 8 experts each (offsets 0, 8, 16, 24 of the published 32, top-4;
    there is no shared expert to count once) add up to what the REFERENCE
    gives for the uncut layer of 32 experts."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.conf.experts import RoutedExperts

    ref = _reference_module()
    cfg = _config_file()
    cfg = {**cfg, **cfg["rehearse"], "num_experts": 32,
           "num_experts_per_tok": 4, "published": {"num_experts": 32}}
    m = ref.dims(cfg)
    p = {k: v for k, v in ref.init_params(cfg, 5).items()
         if k.startswith("l2_ffn/")}
    assert p["l2_ffn/Wgate"].shape[0] == 32
    d, t = cfg["hidden_size"], 100
    x = jax.random.normal(jax.random.key(1), (2, t, d))
    it = InputType.recurrent(d, t)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(m, p, "l2_ffn/", x, "highest")
        total = jnp.zeros_like(x)
        for share in range(4):
            layer = RoutedExperts(
                n_experts=32, experts_held=8, expert_offset=8 * share,
                top_k=4, expert_size=cfg["moe_intermediate_size"],
                shared_size=0, router_activation="sigmoid", scaling=1.0,
                renorm_eps=cfg["renorm_eps"])
            mine = {k.split("/")[1]: (v[8 * share:8 * share + 8]
                                      if not k.endswith("Wr") else v)
                    for k, v in p.items()}
            part, state = layer.apply(
                mine, layer.init(jax.random.key(0), it)[1], x)
            assert int(state["pairs_dropped"]) == 0
            assert int(state["pairs_held"]) > 0
            total = total + part
    assert float(jnp.max(jnp.abs(total - want))) < 2e-5 * max(
        1.0, float(jnp.max(jnp.abs(want))))


STEP_COUNTERS = {"conv.gated_short": 5, "head.tied": 1,
                 "attention.rotary_blocked": 1,
                 "kernel.xla_blocked_attention": 1,
                 "loss.blocked_one_pass": 1}


def test_the_cell_runs_through_its_driver_and_the_control_fails(cell, tmp_path):
    """Set-up's first steps through ``net.fit(DevicePrefetchIterator)``,
    the reference after them: ``correct`` in float32 within the cell's
    limits, the float8 control outside one of them, no pair dropped, the
    step's trace-time counters read (one count a layer; on the CPU the
    ``jax.numpy`` tiles: the chip's step reads
    ``kernel.pallas_blocked_attention`` in their place)."""
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
    # a traced window on the same session: the text is the executable's own
    took = feed.TraceSlice(str(tmp_path), 0.05, 0.05)
    raw = cell.driver.run_window(session, 0.4, took)
    view = cell.program_view
    assert took.done and raw["compiles_for_hlo_text"] == 0
    assert raw["compiles_in_window"] == 0 and raw["failed"] == 0
    for i, kind in enumerate(KINDS):
        marker = (f"GatedShortConv:l{i}_conv" if kind == "conv"
                  else f"RotaryAttention:l{i}_attn")
        assert marker in view["hlo_text"]
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

    * ``gate_c_left_out``: every ``GatedShortConv`` returns
      ``conv(B * u) W_out``, without the output gate C;
    * ``tied_head_gradient_cut``: the tied head reads the embedding's
      matrix under ``stop_gradient``, so the leaf's gradient lacks the
      head's term;
    * ``labels_one_step_late``: the head scores step t against the label
      of step t - 1, which is the id that step t read."""
    import jax
    from deeplearning4j_tpu.nn.conf.recurrent import TokenOutputLayer
    from deeplearning4j_tpu.nn.conf.short_conv import (
        GatedShortConv, causal_depthwise_conv)

    if fault == "gate_c_left_out":
        cls, name = GatedShortConv, "apply"

        def faulty(self, params, state, x, *, train=False, rng=None,
                   mask=None):
            d = params["Wout"].shape[0]
            bcu = x @ params["Win"]
            mixed = causal_depthwise_conv(bcu[..., :d] * bcu[..., 2 * d:],
                                          params["w"])
            return mixed @ params["Wout"], state
    elif fault == "tied_head_gradient_cut":
        cls, name = TokenOutputLayer, "tied_params"
        sound_tied = TokenOutputLayer.tied_params

        def faulty(self, params, other):
            return sound_tied(self, params,
                              {"W": jax.lax.stop_gradient(other["W"])})
    elif fault == "labels_one_step_late":
        cls, name = TokenOutputLayer, "compute_score"
        sound_score = TokenOutputLayer.compute_score

        def faulty(self, labels, preout, mask=None):
            late = jax.numpy.roll(labels, 1, axis=1)
            return sound_score(self, late, preout, mask)
    else:
        raise KeyError(fault)
    sound = getattr(cls, name)
    setattr(cls, name, faulty)
    try:
        yield
    finally:
        setattr(cls, name, sound)


# what each fault has to trip at the least, of the cell's own limits
FAULTS = {"gate_c_left_out": {"loss.step1", "grad_norm.worst_leaf",
                              "grad_norm.median_leaf",
                              "delta_norm.worst_leaf"},
          "tied_head_gradient_cut": {"delta_norm.worst_leaf"},
          "labels_one_step_late": {"loss.step1", "loss.step2", "loss.step3"}}


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
    if fault == "tied_head_gradient_cut":
        # the norms the check compares see the cut where Adam's step does:
        # the rows no token looked up get no gradient at all and stay
        worst = next(r for r in rows if r["what"] == "delta_norm.worst_leaf")
        assert worst["leaf"] == "embed/W"


SCOPES = ["sconv.in_proj", "sconv.gate_conv", "sconv.out_proj",
          "rattn.qk_norm", "rattn.rope", "rattn.attend", "moe.route",
          "moe.dispatch", "moe.experts", "loss.blocked"]


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
    layer = {"sconv": "GatedShortConv:", "rattn": "RotaryAttention:",
             "moe": "RoutedExperts:", "loss": ""}[scope.split(".")[0]]
    under = [o for o in step_op_names if scope in o and layer in o]
    assert any("transpose(" not in o for o in under), scope
    # the loss's gradients come out of its forward rule's one loop
    assert scope == "loss.blocked" or any("transpose(" in o for o in under)


def test_every_operation_of_the_step_has_an_owner(step_op_names):
    """The short convolutions' five vertices and the attention's one are
    told apart by their markers, the transposed table is the head's, and
    nothing jax emitted lies outside an owner."""
    from deeplearning4j_tpu.obs.owners import owner_of
    from harness import layer_scopes

    emitted = [o for o in step_op_names
               if o.startswith("jit(") or re.match(r"[A-Za-z_]\w*:", o)]
    assert [o for o in emitted if owner_of(o) is None] == []
    assert {m for o in emitted for m in re.findall(
        r"GatedShortConv:(l\d+_conv)", o)} == {
        f"l{i}_conv" for i in (0, 1, 3, 4, 5)}
    wanted = layer_scopes.under("RotaryAttention", ["l2_attn"],
                                "rattn.attend")
    mine = [o for o in emitted if wanted(o)]
    assert any("transpose(" in o for o in mine)
    assert any("transpose(" not in o for o in mine)
    assert any("TokenOutputLayer:head" in o and "transpose" in o.rsplit(
        "/", 1)[-1] for o in emitted)


# ------------------------------------------------------------- hand counts
def test_parameter_hand_count_at_the_published_widths(full):
    """ISSUE 43's count, reckoned again: every width as published, 6 of 24
    layers, 8 of 32 experts, 16,384 of 65,536 rows, the tied matrix once."""
    d = 2048
    embedding = 16384 * d
    conv = d * 3 * d + 3 * d + d * d
    attn = d * 32 * 64 + 2 * d * 8 * 64 + 32 * 64 * d + 2 * 64
    dense = 3 * d * 7168
    expert = 3 * d * 1792
    routed = d * 32 + 8 * expert
    assert (embedding, conv, attn, dense, expert, routed) == (
        33_554_432, 16_783_360, 10_485_888, 44_040_192, 11_010_048,
        88_145_920)
    total = (embedding + 5 * conv + attn + 2 * dense + 4 * routed
             + 6 * 2 * d + d)
    assert total == 568_647_808
    assert full.reference.count_params(full.config) == total
    untied = {**full.config, "tie_word_embeddings": False}
    assert full.reference.count_params(untied) == total + embedding


def test_the_zoo_builder_draws_that_many_from_the_public_keys(full):
    """``models.Lfm2Moe`` from the public config's keys alone, cut by its
    arguments: (shapes only, nothing drawn) 568,647,808 parameters here;
    the whole published model 8,339,929,856, the card's 8.3B, of them
    1.5 billion a token; untied it would be 8.47 billion."""
    import jax
    from deeplearning4j_tpu.models import Lfm2Moe
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    public = {k: v for k, v in full.adapter.public_config(full.config).items()
              if k not in ("tie_word_embeddings", "renorm_eps")}
    assert (public["num_hidden_layers"], public["num_experts"],
            public["vocab_size"]) == (24, 32, 65536)

    def count(zoo):
        net = ComputationGraph(zoo.conf())
        kinds = ["conv" if f"l{i}_conv" in net.vertices else "full"
                 for i in range(zoo.layers)]
        drawn = jax.eval_shape(net._draw, jax.random.key(0))[0]
        return kinds, sum(math.prod(a.shape)
                          for a in jax.tree_util.tree_leaves(drawn))

    kinds, n = count(Lfm2Moe(public, layers=6, experts_held=8,
                             vocab_rows=16384, sequence_length=16384))
    assert kinds == KINDS and n == 568_647_808
    kinds, n = count(Lfm2Moe(public))
    assert [i for i, k in enumerate(kinds) if k == "full"] == [
        2, 6, 10, 14, 18, 21]
    assert n == 8_339_929_856
    active = n - 22 * (32 - 4) * 3 * 2048 * 1792
    assert 1.5e9 < active < 1.6e9
    untied = Lfm2Moe({**public, "tie_word_embeddings": False})
    assert count(untied)[1] == n + 65536 * 2048 == 8_474_147_584


def test_flop_hand_count_at_the_published_widths(full):
    """Forward matrix-product FLOPs a token at T = 16,384: the short
    convolutions' two products, the one attention layer's scores and values
    at (T + 1) / 2 keys a query, one held pair of experts a token."""
    d, t = 2048, 16384
    conv = 2 * (d * 3 * d + d * d)
    attn_proj = 2 * (d * 2048 + 2 * d * 512 + 2048 * d)
    attn_keys = 2 * 32 * (64 + 64) * (t + 1) / 2
    dense = 2 * 3 * d * 7168
    routed = 2 * (d * 32 + 3 * d * 1792 * 4 * 8 / 32)
    head = 2 * d * 16384
    want = 5 * conv + attn_proj + attn_keys + 2 * dense + 4 * routed + head
    got = flops.forward_flops_per_item(full.reference.layers(full.config))
    assert got == pytest.approx(want, rel=1e-12)
    assert [round(x / 1e6) for x in (5 * conv, attn_proj, attn_keys,
                                     2 * dense, 4 * routed, head)] == [
        168, 21, 67, 176, 89, 67]
    assert 587e6 < got < 589e6
    # 1.76 GFLOP a token to train, 28.9 TFLOP a step of 16,384 tokens
    assert 1.76e9 < 3 * got < 1.77e9
    assert 28.8e12 < 3 * got * t < 29.0e12
    # what the configuration adds: the short convolutions 29% of the
    # operations, the attention layer 15%, the tied head 11%
    assert 5 * conv / got == pytest.approx(0.285, abs=0.005)
    assert (attn_proj + attn_keys) / got == pytest.approx(0.150, abs=0.005)
    assert full.reference.kept_positions(t) == t * (t + 1) // 2


def test_kernel_cost_functions(full):
    ref, cfg = full.reference, full.config
    t = 16384
    whole = ref.attend_cost(cfg, t, None)
    # 32 query heads, two products of width 64 over the kept positions
    assert whole["flops"] == 32 * (t * (t + 1) // 2) * 2 * 2 * 64
    # q and the output a query head, k and v a key/value head, bfloat16
    assert whole["bytes"] == 2 * t * 64 * (2 * 32 + 2 * 8)
    assert whole["flops"] / 197e12 > whole["bytes"] / 819e9     # MXU bound
    # the gates and the taps: 16 KB a token forward, 28 KB backward, 60 KB
    # a rematerialised step, 1.2 ms a layer; bound by bytes
    fwd = ref.gate_conv_cost(cfg, t)
    bwd = ref.gate_conv_cost(cfg, t, backward=True)
    assert fwd["bytes"] == 2 * (t * 4 * 2048 + 3 * 2048)
    assert bwd["bytes"] == 2 * (t * 7 * 2048 + 2 * 3 * 2048)
    assert round(fwd["bytes"] / t / 1024) == 16
    assert round(bwd["bytes"] / t / 1024) == 28
    step_bytes = 2 * fwd["bytes"] + bwd["bytes"]
    assert step_bytes / 819e9 == pytest.approx(1.23e-3, rel=0.01)
    assert fwd["flops"] == t * 2048 * 7 and bwd["flops"] == t * 2048 * 21
    assert step_bytes / 819e9 > 100 * (2 * fwd["flops"] + bwd["flops"]) \
        / 197e12
    moe = ref.moe_experts_cost(cfg, 16384, 8)
    assert moe["flops"] == 16384 * 3 * 2 * 2048 * 1792
    assert moe["bytes"] > 8 * 3 * 2048 * 1792 * 2       # the weights, bf16
    # at 2,048 tokens an expert the grouped products are MXU bound
    assert moe["flops"] / 197e12 > moe["bytes"] / 819e9


# ------------------------------------------------------------ the readers
_HLO = '''
HloModule jit_train_step
%fused_computation.1 { ... }
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%p0), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(GatedShortConv:l0_conv)/sconv.in_proj/dot_general" source_file="x.py" source_line=1}
  %fusion.2 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(GatedShortConv:l0_conv)/sconv.gate_conv/mul"}
  %fusion.3 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(GatedShortConv:l3_conv))/sconv.gate_conv/mul"}
  %custom-call.1 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(RotaryAttention:l2_attn)/rattn.attend/jit(_forward)/mla_attend_fwd/pallas_call"}
  %fusion.4 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(RotaryAttention:l2_attn)/rattn.rope/mul"}
  %custom-call.2 = bf16[8]{0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(RoutedExperts:l2_ffn)/moe.experts/pallas_call"}
  %fusion.5 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(RoutedExperts:l2_ffn)/moe.route/mul"}
  %fusion.6 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(EmbeddingSequenceLayer:embed)/gather"}
  %fusion.7 = bf16[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(TokenOutputLayer:head)/transpose"}
  %while.1 = f32[8]{0} while(%p0), condition=%c, body=%b, metadata={op_name="jit(train_step)/jvp(loss.score)/loss.blocked/while"}
  %fusion.8 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp(EmbeddingSequenceLayer:embed))/scatter-add"}
  ROOT %fusion.9 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/optim.update/mul"}
}
'''


def _ctx(full, with_view=True, hlo=_HLO):
    ms = 1e-3
    spans = [("%fusion.1 = bf16[8]{0} fusion(%p0)", 3), ("%fusion.2", 2),
             ("%fusion.3", 4), ("%custom-call.1", 20), ("%fusion.4", 2),
             ("%custom-call.2", 8), ("%fusion.5", 1), ("%fusion.6", 1),
             ("%fusion.7", 1), ("%while.1", 9), ("%fusion.8", 2),
             ("%fusion.9", 7)]
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
        tokens = [3000] + [2000] * 6 + [1384]
        cell.program_view = {
            "hlo_text": hlo, "tokens_per_step": 16384,
            "moe": {"l2_ffn": {"expert_tokens": tokens,
                               "pairs_held": sum(tokens),
                               "pairs_dropped": 0}}}
        # the slice's own steps: two of them, 16,384 pairs each
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
    # the short convolutions 3 + 2 + 4 ms, the attention layer 20 + 2, the
    # routed layer 8 + 1, gather + transposed table + loss loop +
    # scatter-add 1 + 1 + 9 + 2
    assert _read(full, "sconv.device_ms_per_step", ctx) == pytest.approx(9.0)
    assert _read(full, "attn64.device_ms_per_step", ctx) == \
        pytest.approx(22.0)
    assert _read(full, "moe1792.device_ms_per_step", ctx) == \
        pytest.approx(9.0)
    assert _read(full, "tiedhead.loss_device_ms_per_step", ctx) == \
        pytest.approx(13.0)


def test_roofline_shares_are_least_time_over_measured_time(full):
    ctx = _ctx(full)
    ref, cfg = full.reference, full.config
    # five short convolutions, the forward twice and the backward, 6 ms
    # under their sconv.gate_conv: 60 KB a token and layer
    fwd, bwd = ref.gate_conv_cost(cfg, 16384), ref.gate_conv_cost(
        cfg, 16384, backward=True)
    least = 5 * (2 * fwd["bytes"] + bwd["bytes"]) / 819e9
    assert least == pytest.approx(6.15e-3, rel=0.01)
    assert _read(full, "sconv.gate_conv_roofline_pct", ctx) == pytest.approx(
        100 * least / 6e-3)
    # one attention layer, the forward twice and a backward of 2.5
    # forwards, 20 ms under its rattn.attend
    one = ref.attend_cost(cfg, 16384, None)
    least = one["flops"] / 197e12 * 4.5
    assert _read(full, "attn64.attend_roofline_pct", ctx) == pytest.approx(
        100 * least / 20e-3)
    moe = ref.moe_experts_cost(cfg, 16384, 8)
    least = max(moe["flops"] / 197e12, moe["bytes"] / 819e9) * 4
    assert _read(full, "moe1792.experts_roofline_pct", ctx) == pytest.approx(
        100 * least / 8e-3)


def test_expert_load_is_the_busiest_over_the_mean(full):
    assert _read(full, "moe1792.expert_load_max_over_mean",
                 _ctx(full)) == pytest.approx(3000 / 2048)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_view_reports_nothing(full, name):
    """Where the driver kept no view the readers return None and do not
    raise."""
    assert _read(full, name, _ctx(full, with_view=False)) is None


@pytest.mark.parametrize("name", ["sconv.device_ms_per_step",
                                  "sconv.gate_conv_roofline_pct",
                                  "tiedhead.loss_device_ms_per_step"])
def test_a_program_without_the_layers_reports_nothing(full, name):
    """On a program that has neither the short convolution nor the two
    vertices of the tied head: nothing, no raise."""
    bare = "\n".join(line for line in _HLO.splitlines()
                     if "GatedShortConv" not in line
                     and "EmbeddingSequenceLayer" not in line
                     and "TokenOutputLayer" not in line)
    assert _read(full, name, _ctx(full, hlo=bare)) is None


@pytest.mark.parametrize("name", ["sconv.gate_conv_roofline_pct",
                                  "attn64.device_ms_per_step",
                                  "attn64.attend_roofline_pct"])
def test_a_configuration_without_these_kinds_reports_nothing(full, name):
    """On a cell whose reference has no ``gate_conv_cost`` and tells no
    attention kinds apart (the Ouro cell's): nothing, no raise."""
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
    cfg = _config_file()
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168,
        "layer_types": (["conv", "conv", "full_attention", "conv"] * 5
                        + ["conv", "full_attention", "conv", "conv"]),
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True}
    for key, value in catalog.items():
        assert cfg[key] == value, key
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (6, 8, 16384)
    assert cfg["published"] == {"num_hidden_layers": 24, "num_experts": 32,
                                "vocab_size": 65536}
    assert cfg["deployment"]["chips_sharing_a_layer"] == 4
    assert cfg["deployment"]["pipeline_stages"] == 4
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 4 == cfg["published"]["num_experts"]
    assert cfg["num_hidden_layers"] * 4 == cfg["published"][
        "num_hidden_layers"]
    assert (cfg["tie_word_embeddings"], cfg["renorm_eps"]) == (True, 1e-6)
    for key in ("tie_word_embeddings", "renorm_eps", "final_norm",
                "expert_bias", "sequence_length", "weights", "compute_dtype",
                "updater"):
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
    for key in ("readings", "loss", "grad_norm_worst", "grad_norm_median",
                "delta_norm_worst", "control", "planted_faults"):
        assert key in full.cell["limits_why"], key
    # each limit between its two readings on the chip (the cell's
    # ``limits_why``): the sound runs' largest of 30 and the lowest reading
    # of a planted fault that the number is there to refuse
    for key, (sound, fault) in {"grad_norm_worst": (0.00336, 0.0185),
                                "grad_norm_median": (2.11e-4, 0.111),
                                "delta_norm_worst": (3.60e-4, 0.1155)}.items():
        assert 2 * sound <= full.cell["limits"][key] <= fault / 2, key
    # the loss: between the sound runs' largest of 90 step readings and the
    # late labels' lowest of twelve
    for limit in full.cell["limits"]["loss"]:
        assert 2 * 8.4e-5 <= limit <= 0.0397 / 2
    assert "labels_one_step_late" in full.cell["limits_why"]["planted_faults"]
    # the traffic: the Mellum2 cell's mix, nothing added
    assert full.cell["traffic"] == "fit_tokens_1x16384"
    assert full.traffic["sequence_length"] == cfg["sequence_length"] == 16384
    # the manifest's entries for this configuration and cell
    manifest = loader.load_manifest(bench_paths.ROOT)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert entry["reduced"] == cfg["reduced"]
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "fit_tokens_1x16384", 1)]
    assert all(len(w["why"]) <= 200 for w in manifest["workloads"])
    # appended after the seven cells the benchmark had, one of them on
    # four chips; what later PRs append comes after
    assert [w["name"] for w in manifest["workloads"]].index(CELL) == 7
    assert [w["chips"] for w in manifest["workloads"][:8]].count(4) == 1
    listed = [m["name"] for m in manifest["per_layer"]]
    at = listed.index(NEW_METRICS[0])
    assert listed[at:at + 8] == NEW_METRICS

"""Device time by owner (``benchmark/harness/owners.py``) and the five reader
files of PR 34, on a hand-written HLO text and hand-made timelines where
every answer is known: a fusion, a ``conditional`` and a ``while`` that
carry no ``op_name`` are followed into the computations they call
(agreeing, mixed, nothing to follow), one that only moves a value follows
its operands to who made it, and an owner's time is the union of its
operations' own intervals, never a sum of rows."""

import os
import types

import pytest

import bench_paths
from deeplearning4j_tpu.obs.owners import owner_of
from harness import loader, owners, trace

MS = 1e-3

# what a compiled step looks like, cut down: instruction names as the
# trace's events give them, ``op_name`` only where jax emitted the
# instruction itself
_HLO = """HloModule jit_train_step, is_scheduled=true

%fused_adam (p.0: f32[8], p.1: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  %p.1 = f32[8]{0} parameter(1)
  %mul.1 = f32[8]{0} multiply(%p.0, %p.1), metadata={op_name="jit(train_step)/optim.update/mul"}
  ROOT %add.1 = f32[8]{0} add(%p.0, %mul.1), metadata={op_name="jit(train_step)/optim.update/add" stack_frame_id=2}
}

%fused_both (p.2: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  %pow.1 = f32[8]{0} multiply(%p.2, %p.2), metadata={op_name="jit(train_step)/optim.update/integer_pow"}
  ROOT %exp.1 = f32[8]{0} exponential(%pow.1), metadata={op_name="jit(train_step)/jvp(DenseLayer:a.b)/exp"}
}

%fused_copy (p.3: f32[8]) -> f32[8] {
  %p.3 = f32[8]{0} parameter(0)
  ROOT %copy.9 = f32[8]{0} copy(%p.3)
}

%branch_first (p.4: f32[8]) -> f32[8] {
  %p.4 = f32[8]{0} parameter(0)
  ROOT %fusion.10 = f32[8]{0} fusion(%p.4), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(train_step)/jvp(RoutedExperts:l2_ffn)/cond/branch_0_fun/moe.experts/mul"}
}

%branch_every (p.5: f32[8]) -> f32[8] {
  %p.5 = f32[8]{0} parameter(0)
  %fusion.11 = f32[8]{0} fusion(%p.5), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(train_step)/jvp(RoutedExperts:l2_ffn)/cond/branch_1_fun/moe.experts/mul"}
  ROOT %fusion.12 = f32[8]{0} fusion(%fusion.11), kind=kLoop, calls=%fused_both
}

%body (p.6: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.6 = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p.6), index=1
  %fusion.20 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_copy, metadata={op_name="RMSNorm:n1/mul"}
  %fusion.21 = f32[8]{0} fusion(%fusion.20), kind=kLoop, calls=%fused_copy, metadata={op_name="LoopVertex:loop/loop.body/RMSNorm:n1/add"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.1, %fusion.21)
}

%async_slice (p.8: f32[8]) -> f32[4] {
  %p.8 = f32[8]{0} parameter(0)
  ROOT %slice.1 = f32[4]{0} slice(%p.8), slice={[0:4]}
}

%cond (p.7: (s32[], f32[8])) -> pred[] {
  %p.7 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %b = f32[8]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%a, %b), kind=kLoop, calls=%fused_adam
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_both
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_copy
  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(train_step)/add"}
  %conditional.1 = f32[8]{0} conditional(%b, %a, %a), branch_computations={%branch_first, %branch_every}
  %while.1 = (s32[], f32[8]{0}) while(%t), condition=%cond, body=%body
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_copy, metadata={op_name="jit(train_step)/jvp(loss.score)/loss.blocked/while"}
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%fusion.1)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  %gte.2 = f32[8]{0} get-tuple-element(%while.1), index=1
  %slice-start.1 = ((f32[8]{0:T(8,128)(2,1)}), f32[4]{0:S(1)}, s32[]{:S(2)}) async-start(%gte.2), calls=%async_slice
  %slice-done.1 = f32[4]{0:S(1)} async-done(%slice-start.1)
  %copy.2 = f32[8]{0} copy(%b)
  %copy-start.2 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%copy.2)
  %copy-done.2 = f32[8]{0:S(1)} copy-done(%copy-start.2)
  %bitcast.1 = f32[8]{0} bitcast(%fusion.2)
  %add.9 = f32[8]{0} add(%fusion.1, %fusion.1)
  ROOT %copy.1 = f32[8]{0} copy(%fusion.5), metadata={op_name="jit(train_step)/jvp(params.cast)/convert_element_type"}
}

FileNames
1 "/x/optax/_src/update.py"

FunctionNames
1 "apply_updates"

FileLocations
1 {file_name_id=1 function_name_id=1 line=43 end_line=43 column=1 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=1 parent_frame_id=1}
"""


def reader(metric):
    return loader.import_file(os.path.join(
        bench_paths.BENCH, "layer_metrics", metric + ".py"),
        "layer_metric").read


def test_an_instruction_without_an_op_name_is_followed_into_what_it_calls():
    by = owners.owners(_HLO, owner_of)
    # a fusion across the optimizer's leaves: its instructions agree
    assert by["fusion.1"] == "optim"
    # a fusion that holds the optimizer's and a layer's instructions
    assert by["fusion.2"] == owners.MIXED
    # nothing inside names an owner, and an op_name that names none
    assert by["fusion.3"] == owners.UNOWNED
    assert by["fusion.4"] == owners.UNOWNED
    # a conditional: both branches are the routed layer's but for Adam's
    # power that XLA moved into the second
    assert by["conditional.1"] == owners.MIXED
    # a while: its body's instructions agree (its condition has no say)
    assert by["while.1"] == "RMSNorm"
    # an op_name that names an owner is not followed at all
    assert by["fusion.10"] == "RoutedExperts"
    assert by["fusion.5"] == "loss" and by["copy.1"] == "params.cast"
    # a layer with dots in its name, nested scopes inside a loop's body
    assert by["exp.1"] == "DenseLayer" and by["fusion.21"] == "RMSNorm"


def test_an_instruction_that_moves_a_value_belongs_to_who_made_it():
    by = owners.owners(_HLO, owner_of)
    # the scheduler's prefetch of the optimizer's result, start and done
    assert by["copy-start.1"] == by["copy-done.1"] == "optim"
    # through a get-tuple-element of a loop, into the computation it calls
    assert by["gte.2"] == by["slice-start.1"] == by["slice-done.1"] \
        == "RMSNorm"
    # a copy of a program argument has no maker in the program
    assert by["copy.2"] == by["copy-done.2"] == owners.UNOWNED
    # what it moves has two owners; and an instruction that COMPUTES
    # without an op_name does not take its operands' owner
    assert by["bitcast.1"] == owners.MIXED
    assert by["add.9"] == owners.UNOWNED
    instructions, _ = owners.parse(_HLO)
    assert instructions["slice-start.1"].opcode == "async-start"
    assert instructions["slice-start.1"].operands == ["gte.2"]
    assert instructions["conditional.1"].operands == ["b", "a", "a"]
    assert instructions["while.1"].opcode == "while"


def test_a_conditional_whose_branches_agree_is_its_layer_s():
    text = _HLO.replace("calls=%fused_both\n}", "calls=%fused_copy\n}")
    assert owners.owners(text, owner_of)["conditional.1"] == "RoutedExperts"


def test_each_instant_belongs_to_the_innermost_operation():
    ops = [("%while.1 = (s32[]) while(%t)", 0 * MS, 10 * MS),
           ("%fusion.20 = f32[8]{0} fusion(%gte.1)", 1 * MS, 4 * MS),
           ("%fusion.21 = f32[8]{0} fusion(%fusion.20)", 4 * MS, 9 * MS),
           ("%fusion.1 = f32[8]{0} fusion(%a, %b)", 10 * MS, 12 * MS)]
    own = dict(owners.self_intervals(ops))
    assert own["%while.1 = (s32[]) while(%t)"] == [
        pytest.approx((0, 1 * MS)), pytest.approx((9 * MS, 10 * MS))]
    assert own["%fusion.1 = f32[8]{0} fusion(%a, %b)"] == [
        pytest.approx((10 * MS, 12 * MS))]
    # neighbours inside a loop that overlap by a rounding, and one that
    # outlasts the loop: every instant is somebody's once
    ragged = [("w", 0.0, 10.0), ("a", 2.0, 4.0), ("b", 3.0, 9.0),
              ("c", 9.5, 11.0), ("d", 20.0, 21.0)]
    own = dict(owners.self_intervals(ragged))
    assert own == {"w": [(0.0, 2.0), (9.0, 9.5)], "a": [(2.0, 3.0)],
                   "b": [(3.0, 9.0)], "c": [(9.5, 11.0)],
                   "d": [(20.0, 21.0)]}
    assert sum(e - s for part in own.values() for s, e in part) \
        == pytest.approx(trace.total(trace.union(
            [(s, e) for _, s, e in ragged])))


def _ctx(with_text=True):
    """Two steps of 40 ms: a while of 10 ms whose body's two operations
    run 8 of them, the optimizer's fusion 6, a mixed fusion 2, a copy
    nobody owns 1, the loss 3, an operation the text does not name 1, and
    a conditional of 5 ms whose branch's operations run 4."""
    ops = [("%while.1 = (s32[], f32[8]{0}) while(%t)", 0, 10),
           ("%fusion.20 = f32[8]{0} fusion(%gte.1), kind=kLoop", 1, 4),
           ("%fusion.21 = f32[8]{0} fusion(%fusion.20)", 4, 9),
           ("%fusion.1 = f32[8]{0} fusion(%a, %b)", 10, 16),
           ("%fusion.2 = f32[8]{0} fusion(%a)", 16, 18),
           ("%fusion.3 = f32[8]{0} fusion(%a)", 18, 19),
           ("%fusion.5 = f32[8]{0} fusion(%a)", 19, 22),
           ("%fusion.99 = f32[8]{0} fusion(%a)", 22, 23),
           ("%conditional.1 = f32[8]{0} conditional(%b, %a, %a)", 24, 29),
           ("%fusion.11 = f32[8]{0} fusion(%p.5)", 24.5, 27),
           ("%fusion.12 = f32[8]{0} fusion(%fusion.11)", 27, 28.5)]
    ops = [(n, s * MS, e * MS) for n, s, e in ops]
    ops = ops + [(n, s + 40 * MS, e + 40 * MS) for n, s, e in ops]
    modules = [("jit_train_step", 0.0, 30 * MS),
               ("jit_train_step", 40 * MS, 70 * MS)]
    cell = types.SimpleNamespace()
    if with_text:
        cell.program_view = {"hlo_text": _HLO}
    return {"cell": cell, "raw": {"steps": 7}, "chips": 1,
            "trace": trace.Trace([trace.DeviceTimeline(0, ops, modules)], [])}


def test_owners_add_up_to_the_busy_time_and_nothing_is_counted_twice():
    ctx = _ctx()
    by = {k: v / 2 / MS for k, v in owners.seconds_by_owner(ctx).items()}
    assert by == pytest.approx({
        "RMSNorm": 10.0,         # the while AND its body: 10, not 18
        "optim": 6.0,
        "RoutedExperts": 2.5,    # fusion.11 inside the conditional
        owners.MIXED: 2.0 + 1.5 + 1.0,   # fusion.2, fusion.12, the
        # conditional's own instants (5 ms less the 4 its branch ran)
        owners.UNOWNED: 1.0 + 1.0,       # the copy; one the text lacks
        "loss": 3.0})
    busy_ms = 1e3 * trace.busy_seconds(ctx["trace"])[0] / 2
    assert sum(by.values()) == pytest.approx(busy_ms)
    assert owners.ms_per_step(ctx, "optim") == pytest.approx(6.0)
    assert owners.ms_per_step(ctx, "RMSNorm", "loss") == pytest.approx(13.0)


def test_the_two_device_readers_read_the_owners():
    ctx = _ctx()
    assert reader("optim.device_ms_per_step")(ctx) == pytest.approx(6.0)
    assert reader("step.unowned_device_ms_per_step")(ctx) == pytest.approx(
        6.5)


@pytest.mark.parametrize("metric", [
    "optim.device_ms_per_step", "step.unowned_device_ms_per_step",
    "moe.every_window_step_pct"])
def test_a_context_without_hlo_text_reads_as_nothing(metric):
    """The ResNet50 cells' drivers keep no text, and a model without a
    routed layer has no counter to divide."""
    assert reader(metric)(_ctx(with_text=False)) is None
    empty = _ctx()
    empty["trace"] = trace.Trace([], [])
    assert reader(metric)(empty) is None


def test_a_program_without_the_rule_reads_as_nothing(monkeypatch):
    """The parent commit's program has no ``owner_of``."""
    monkeypatch.setattr(owners, "program_owner_of", lambda: None)
    assert owners.seconds_by_owner(_ctx()) is None
    assert reader("optim.device_ms_per_step")(_ctx()) is None


def test_every_window_share_is_the_counter_over_layers_and_steps(
        monkeypatch):
    from deeplearning4j_tpu import obs

    class Registry:
        def __init__(self, values):
            self.values = values

        def as_dict(self):
            return {k: {"value": v} for k, v in self.values.items()}

    ctx = _ctx()
    ctx["cell"].program_view["moe"] = {"l2_ffn": {}, "l3_ffn": {}}
    read = reader("moe.every_window_step_pct")
    monkeypatch.setattr(obs, "get_registry", lambda: Registry(
        {"moe_every_window_steps_total": 6.0, "train_steps_total": 30.0}))
    assert read(ctx) == pytest.approx(10.0)      # 6 of 2 x 30
    # a program that does not count the tier (the parent's)
    monkeypatch.setattr(obs, "get_registry", lambda: Registry(
        {"train_steps_total": 30.0}))
    assert read(ctx) is None


def test_the_compile_readers_sum_the_watched_programs(monkeypatch):
    from deeplearning4j_tpu import obs

    class Registry:
        def as_dict(self):
            return {k: {"value": v} for k, v in {
                "jit_compile_trace_s_train": 4.0,
                "jit_compile_lower_s_train": 1.5,
                "jit_compile_trace_s_score": 0.5,
                "jit_compile_backend_s_train": 9.0,
                "jit_compile_cache_load_s_train": 2.0,
                "jit_compile_trace_s_unwatched": 100.0,
                "jit_compile_backend_s_unwatched": 100.0,
                "jit_compiles": 3.0}.items()}

    full = loader.resolve_cell(bench_paths.ROOT, "resnet50_train_1chip")
    ctx = {"cell": full}
    monkeypatch.setattr(obs, "get_registry", lambda: Registry())
    assert full.layer_reader("compile.trace_lower_s")(ctx) \
        == pytest.approx(6.0)
    assert full.layer_reader("compile.build_or_load_s")(ctx) \
        == pytest.approx(9.0)

    class Bare:
        def as_dict(self):
            return {"jit_compiles": {"value": 3.0}}

    monkeypatch.setattr(obs, "get_registry", lambda: Bare())
    assert full.layer_reader("compile.trace_lower_s")(ctx) is None
    assert full.layer_reader("compile.build_or_load_s")(ctx) is None


@pytest.mark.parametrize("metric,layer,unit,moves", [
    ("optim.device_ms_per_step", "fit loops", "ms", "train_items_per_s"),
    ("step.unowned_device_ms_per_step", "kernels", "ms",
     "train_items_per_s"),
    ("moe.every_window_step_pct", "routed experts", "%",
     "train_items_per_s"),
    ("compile.trace_lower_s", "compile", "s", "setup_s"),
    ("compile.build_or_load_s", "compile", "s", "setup_s")])
def test_the_readers_state_layer_unit_and_what_they_move(metric, layer, unit,
                                                         moves):
    module = loader.import_file(os.path.join(
        bench_paths.BENCH, "layer_metrics", metric + ".py"), "layer_metric")
    assert (module.LAYER, module.UNIT, module.MOVES) == (layer, unit, moves)
    manifest = loader.load_manifest(bench_paths.ROOT)
    assert moves in [m["name"] for m in manifest["end_to_end"]]


def test_the_source_line_of_an_instruction_is_read_from_the_text():
    where = owners.source_lines(_HLO)
    instructions, _ = owners.parse(_HLO)
    assert where(instructions["add.1"].line) == "update.py:43(apply_updates)"
    assert where(instructions["fusion.3"].line) == ""

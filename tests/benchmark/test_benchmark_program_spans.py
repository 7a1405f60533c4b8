"""The program's own spans out of a profiler trace
(``benchmark/harness/program_spans.py``) and the four metrics that read
them: on hand-made timelines, where every answer is known, and on a small
trace recorded on a TPU v5e with the program's spans in it
(``data/v5e_program_small.xplane.pb.gz``: a dense 512-512-10 network at
batch 256 trained through ``net.fit(DevicePrefetchIterator(batches))``,
eight steps traced with the benchmark's own profiler options after two to
compile; recorded as ``v5e_small.xplane.pb`` was, and gzipped because the
step program's HLO, which the profiler embeds, takes it to 220 KB).
``v5e_small`` itself comes from before the program had spans and has to
read as nothing."""

import gzip
import os

import pytest

import bench_paths
from harness import loader, program_spans, trace
from harness.program_spans import Span

RECORDED = os.path.join(bench_paths.DATA, "v5e_program_small.xplane.pb.gz")
BEFORE_SPANS = os.path.join(bench_paths.DATA, "v5e_small.xplane.pb")
METRICS = ("fit.host_ms_per_step", "prefetch.place_ms_per_batch",
           "device.idle_ms_per_step.loop", "device.idle_ms_per_step.input")


def reader(metric):
    return loader.import_file(os.path.join(
        bench_paths.BENCH, "layer_metrics", metric + ".py"),
        "layer_metric").read


def turn(at, step, thread="loop"):
    """One turn of a fit loop, 10 s long, starting at ``at``:
    iteration [0,10] > data_wait [0,2] > place [0.5,1.5]
                     > step_host [3,9] > stage [3,4], dispatch [4,7]
                                         (> compile at 5), post [7,8]"""
    def sp(name, s, e, **stats):
        return Span(name, at + s, at + e, thread, stats)
    return [sp("train.iteration", 0, 10, step=step),
            sp("train.data_wait", 0, 2, step=step),
            sp("prefetch.place", 0.5, 1.5, bytes=64, arrays=2),
            sp("train.step_host", 3, 9, step=step, items=16),
            sp("train.stage", 3, 4, step=step),
            sp("train.dispatch", 4, 7, step=step, program="train"),
            sp("compile", 5, 5, program="train"),
            sp("train.post", 7, 8, step=step)]


def two_turns():
    return program_spans.nest(turn(0.0, 0) + turn(10.0, 1) + [
        # another thread's span overlaps everything and nests with nothing
        Span("checkpoint.snapshot", 1.0, 19.0, "writer", {})])


def test_nesting_is_by_interval_within_a_thread():
    spans = two_turns()
    by = {(s.name, s.start): s for s in spans}
    it0, it1 = by["train.iteration", 0.0], by["train.iteration", 10.0]
    assert it0.parent is None and it1.parent is None
    assert [c.name for c in it0.children] == ["train.data_wait",
                                              "train.step_host"]
    host1 = by["train.step_host", 13.0]
    assert host1.parent is it1
    assert [c.name for c in host1.children] == [
        "train.stage", "train.dispatch", "train.post"]
    assert by["prefetch.place", 0.5].parent is by["train.data_wait", 0.0]
    assert by["compile", 15.0].parent is by["train.dispatch", 14.0]
    assert by["checkpoint.snapshot", 1.0].parent is None
    assert by["checkpoint.snapshot", 1.0].children == []
    # a span that names no step takes its nearest ancestor's
    assert by["prefetch.place", 10.5].step == 1
    assert by["compile", 5.0].step == 0
    assert by["checkpoint.snapshot", 1.0].step is None


def test_self_time_is_duration_minus_what_children_cover():
    by = {(s.name, s.start): s for s in two_turns()}
    assert by["train.iteration", 0.0].self_seconds() == pytest.approx(
        10 - 2 - 6)
    assert by["train.step_host", 3.0].self_seconds() == pytest.approx(
        6 - 1 - 3 - 1)
    assert by["train.data_wait", 0.0].self_seconds() == pytest.approx(1.0)
    assert by["train.post", 7.0].self_seconds() == pytest.approx(1.0)
    assert by["train.step_host", 3.0].child_seconds(
        "train.dispatch") == pytest.approx(3.0)


def hand_made_ctx():
    """Two turns over a device that runs [0,1] [2.5,3.5] [6,10.5] [12,13.5]
    [16.5,20]: idle 1.5 + 2.5 + 1.5 + 3 = 8.5 s in two steps."""
    ops = [("fusion", 0.0, 1.0), ("fusion", 2.5, 3.5), ("fusion", 6.0, 10.5),
           ("fusion", 12.0, 13.5), ("fusion", 16.5, 20.0)]
    mods = [("jit_train_step", 0.0, 10.5), ("jit_train_step", 12.0, 20.0),
            ("jit_split", 2.5, 2.6)]
    tr = trace.Trace([trace.DeviceTimeline(0, ops, mods)], [])
    spans = [s for s in two_turns() if s.thread == "loop"]
    return {"trace": tr, "chips": 1,
            "program_spans": program_spans.ProgramSpans(spans)}


def test_idle_is_split_by_the_innermost_program_span():
    ctx = hand_made_ctx()
    idle = ctx["program_spans"].idle_by_name(ctx["trace"])
    assert idle == {
        # [1,2.5]: place to 1.5, data_wait to 2, then the turn itself
        "prefetch.place": pytest.approx(0.5 + 1.0),   # and [10.5,11.5]
        "train.data_wait": pytest.approx(0.5 + 0.5),  # and [11.5,12]
        "train.iteration": pytest.approx(0.5),
        # [3.5,6]: stage to 4, then dispatch; [13.5,16.5]: stage [13.5,14],
        # dispatch [14,16.5]
        "train.stage": pytest.approx(0.5 + 0.5),
        "train.dispatch": pytest.approx(2.0 + 2.5),
    }
    assert sum(idle.values()) == pytest.approx(8.5)


def test_chunked_gap_splitting_equals_one_call_over_everything(monkeypatch):
    ctx = hand_made_ctx()
    spans = ctx["program_spans"].spans
    whole = dict(map(tuple, trace.idle_by_host_span(
        trace.Trace(ctx["trace"].devices,
                    sorted(((s.name, s.start, s.end) for s in spans
                            if s.end > s.start),    # not the instants
                           key=lambda sp: (sp[1], sp[2]))), n=99)))
    for chunk in (1, 2, 3, 256):
        monkeypatch.setattr(program_spans, "_CHUNK", chunk)
        got = program_spans.idle_by_span_name(ctx["trace"], spans)
        assert got == {k: pytest.approx(v) for k, v in whole.items()}, chunk


def test_the_four_readers_on_the_hand_made_timeline():
    ctx = hand_made_ctx()
    # step_host 6 s, its dispatch 3 s
    assert reader("fit.host_ms_per_step")(ctx) == pytest.approx(3000.0)
    assert reader("prefetch.place_ms_per_batch")(ctx) == pytest.approx(1000.0)
    # two executions of the step program in the slice
    assert reader("device.idle_ms_per_step.loop")(ctx) == pytest.approx(
        1000.0 * (1.0 + 4.5) / 2)
    assert reader("device.idle_ms_per_step.input")(ctx) == pytest.approx(
        1000.0 * (1.5 + 1.0) / 2)


@pytest.mark.parametrize("metric", METRICS)
def test_no_program_span_reads_as_nothing(metric):
    """A newer benchmark over an older program reports nothing, not 0."""
    ctx = {"trace": hand_made_ctx()["trace"], "chips": 1,
           "program_spans": None}
    assert reader(metric)(ctx) is None


@pytest.mark.parametrize("metric", METRICS)
def test_reader_states_layer_unit_and_what_it_moves(metric):
    mod = loader.import_file(os.path.join(
        bench_paths.BENCH, "layer_metrics", metric + ".py"), "layer_metric")
    assert mod.MOVES == "train_items_per_s"
    assert (mod.LAYER, mod.UNIT) == (
        ("device" if metric.startswith("device.") else "fit loops"), "ms")
    assert "SOURCE: program_span" in mod.__doc__


class _Cell:
    def __init__(self, root, name):
        self.root, self.name = root, name


def _ctx_of_recorded(tmp_path, recorded, name):
    """``recorded`` where ``run.py`` leaves a cell's trace, and the ctx
    ``run.py`` would hand the readers."""
    where = tmp_path / ".bench_trace" / name / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    opener = gzip.open if recorded.endswith(".gz") else open
    with opener(recorded, "rb") as f:
        (where / "vm.xplane.pb").write_bytes(f.read())
    return {"cell": _Cell(str(tmp_path), name), "chips": 1,
            "trace": trace.load(str(tmp_path / ".bench_trace" / name))}


def test_trace_from_before_the_spans_reads_as_nothing(tmp_path):
    ctx = _ctx_of_recorded(tmp_path, BEFORE_SPANS, "old")
    assert program_spans.read(BEFORE_SPANS) == []
    assert program_spans.of(ctx) is None
    assert [reader(m)(ctx) for m in METRICS] == [None] * 4


@pytest.fixture(scope="module")
def recorded_ctx(tmp_path_factory):
    return _ctx_of_recorded(tmp_path_factory.mktemp("rec"), RECORDED, "new")


def test_recorded_v5e_trace_holds_the_fit_loops_tree(recorded_ctx):
    program = program_spans.of(recorded_ctx)
    assert program is not None
    assert program_spans.of(recorded_ctx) is program       # read once
    hosts = program.named("train.step_host")
    assert len(hosts) == 8
    assert [h.step for h in hosts] == list(range(hosts[0].step,
                                                 hosts[0].step + 8))
    for h in hosts:
        assert h.parent.name == "train.iteration"
        assert h.parent.step == h.step and h.stats["items"] == 256
        assert [c.name for c in h.children] == [
            "train.stage", "train.dispatch", "train.post"]
        assert h.children[1].stats["program"] == "train"
        assert 0.0 <= h.self_seconds() < h.seconds
    places = program.named("prefetch.place")
    assert places and all(p.parent.name == "train.data_wait"
                          and p.stats["arrays"] == 2
                          and p.stats["bytes"] == 256 * (512 + 10) * 4
                          for p in places)
    assert not program.named("compile")        # compiled before the slice


def test_recorded_v5e_trace_reads_as_sane_numbers(recorded_ctx):
    program = program_spans.of(recorded_ctx)
    tr = recorded_ctx["trace"]
    busy_s, window_s = trace.busy_seconds(tr)
    assert 0.0 < busy_s < window_s
    # the program under its stable name, once a step
    modules = [n for n, _, _ in tr.devices[0].modules]
    assert sum(n.startswith("jit_train_step") for n in modules) in (7, 8, 9)
    idle = program.idle_by_name(tr)
    assert sum(idle.values()) == pytest.approx(window_s - busy_s, rel=1e-6)
    # a step of this size takes the device tens of microseconds and the
    # host several hundred: the device idles, under the loop's own work
    host_ms = reader("fit.host_ms_per_step")(recorded_ctx)
    place_ms = reader("prefetch.place_ms_per_batch")(recorded_ctx)
    loop_ms = reader("device.idle_ms_per_step.loop")(recorded_ctx)
    input_ms = reader("device.idle_ms_per_step.input")(recorded_ctx)
    assert 0.01 < host_ms < 50 and 0.01 < place_ms < 50
    assert loop_ms > 0 and input_ms >= 0
    steps = trace.steps(tr)
    assert (loop_ms + input_ms) * steps / 1000.0 <= (window_s - busy_s) * (
        1 + 1e-6)
    assert busy_s / window_s < 0.5

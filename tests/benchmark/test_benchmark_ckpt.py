"""The five ``ckpt.*`` readers over ``benchmark/harness/checkpoint_spans.py``
on hand-made spans and device intervals, where every answer is known, and
the cell they were written for, ``resnet50_train_ckpt``, which WAITS: its
file, traffic mix and driver are here, its manifest entries are not in
``BENCHMARK.json`` (six runs on the chip spread by 0.8% of the median where
half the bound is 0.5%: PERF.md sections 6 and 7) but in
``data/resnet50_train_ckpt.entries.json``, which these tests lay over a copy
of the benchmark as a ``benchmark`` PR would append them. Held here: the
window's feed, which ends on a save; the entries against the cell's file;
the guarantee that is part of ``correct``, sound and with planted faults;
and ``run.py --rehearse`` of the cell on the CPU, with the spans it leaves
on its profiler trace."""

import contextlib
import importlib.util
import io
import json
import os
import re
import types

import numpy as np
import pytest

import bench_paths
from harness import checkpoint_spans, feed, loader, program_spans, trace
from harness.program_spans import Span

CELL = "resnet50_train_ckpt"
PLAIN = "resnet50_train_1chip"
ENTRIES = os.path.join(bench_paths.DATA, CELL + ".entries.json")
CKPT = ("ckpt.stall_ms_per_save", "ckpt.device_idle_ms_per_save",
        "ckpt.enqueue_wait_ms_per_save", "ckpt.write_ms_per_save",
        "ckpt.snapshot_gb_per_s")
SPAN_READ = ("fit.host_ms_per_step", "prefetch.place_ms_per_batch",
             "device.idle_ms_per_step.loop", "device.idle_ms_per_step.input")
LOOP, WRITER = "main/299#2", "python#5"


def reader(metric):
    return loader.import_file(os.path.join(
        bench_paths.BENCH, "layer_metrics", metric + ".py"),
        "layer_metric").read


def with_the_cell(root):
    """A copy of the benchmark under ``root`` whose manifest has the
    waiting cell's entries appended: nothing that is there is edited."""
    bench_paths.copy_benchmark(root)
    manifest = loader.load_manifest(root)
    for key, entries in loader.read_json(ENTRIES).items():
        manifest[key].extend(entries)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


# ------------------------------------------------------ a hand-made timeline
def one_save(seq=3, at=0.0):
    """Two turns of a fit loop, the first with a save, and the writer's
    work on it. The device runs [0,10] [10.5,21] [33,50] [51,60]:

    * [10,10.5] lies under the first turn's ``train.step_host``;
    * [21,33] begins under the save (the drain ends at 21, the span at 30)
      and ends under the NEXT turn's ``train.dispatch``: the save's, whole;
    * [50,51] lies under the writer's open span and under no save."""
    ids = {"seq": seq, "step": 108}

    def sp(name, s, e, thread=LOOP, **stats):
        return Span(name, at + s, at + e, thread, stats)
    return [
        sp("train.iteration", 0, 30.6, step=107),
        sp("train.step_host", 0.5, 30.55, step=107),
        sp("train.dispatch", 1, 2, step=107),
        sp("checkpoint.step_end", 17.5, 30.5),
        sp("checkpoint.save", 18, 30, bytes=3_000_000_000, queued=2,
           sharded=0, **ids),
        sp("checkpoint.snapshot", 18, 27, bytes=3_000_000_000, **ids),
        sp("checkpoint.drain", 18, 21, **ids),
        sp("checkpoint.enqueue", 27, 29, queued=2, **ids),
        sp("train.iteration", 30.6, 60, step=108),
        sp("train.data_wait", 30.6, 30.8, step=108),
        sp("train.step_host", 30.8, 59, step=108),
        sp("train.dispatch", 31, 34, step=108),
        sp("checkpoint_writer.write", 29, 52, WRITER, waited_ms=12.5,
           bytes=3_000_000_123, **ids),
        sp("checkpoint_writer.serialize", 29, 35, WRITER, **ids),
        sp("checkpoint_writer.hash", 35, 40, WRITER, **ids),
        sp("checkpoint_writer.put", 40, 50, WRITER, **ids),
        sp("checkpoint_writer.journal", 50, 52, WRITER, **ids),
        sp("checkpoint.commit", 52, 52, WRITER, step=108)]


def device(last=60.0):
    ops = [("fusion", 0.0, 10.0), ("fusion", 10.5, 21.0),
           ("fusion", 33.0, 50.0), ("fusion", 51.0, last)]
    mods = [("jit_train_step", 0.0, 21.0), ("jit_train_step", 33.0, last)]
    return trace.Trace([trace.DeviceTimeline(0, ops, mods)], [])


def ctx_of(spans, tr=None):
    tr = tr or device()
    return {"trace": tr, "chips": 1,
            "checkpoint_saves": checkpoint_spans.Saves(
                program_spans.nest(spans), tr)}


def test_a_save_wholly_inside_the_slice():
    ctx = ctx_of(one_save())
    saves = ctx["checkpoint_saves"]
    assert saves.thread == LOOP and len(saves.saves) == 1
    assert reader("ckpt.stall_ms_per_save")(ctx) == pytest.approx(12000.0)
    assert reader("ckpt.enqueue_wait_ms_per_save")(ctx) == pytest.approx(
        2000.0)
    assert reader("ckpt.write_ms_per_save")(ctx) == pytest.approx(23000.0)
    # 3 GB over the snapshot's SELF time: 9 s less the 3 s of the drain
    assert reader("ckpt.snapshot_gb_per_s")(ctx) == pytest.approx(0.5)
    assert saves.drain_ms() == pytest.approx(3000.0)
    assert saves.copy_ms() == pytest.approx(6000.0)
    assert saves.write_phases_ms() == {
        "checkpoint_writer.serialize": pytest.approx(6000.0),
        "checkpoint_writer.hash": pytest.approx(5000.0),
        "checkpoint_writer.put": pytest.approx(10000.0),
        "checkpoint_writer.journal": pytest.approx(2000.0)}


def test_a_gap_that_begins_under_the_save_is_the_saves_whole():
    """[21,33] ends under the next turn's ``train.dispatch`` and counts
    from the device's last operation to its next; the gap under the open
    writer span and the step-boundary gap are kept apart."""
    ctx = ctx_of(one_save())
    assert reader("ckpt.device_idle_ms_per_save")(ctx) == pytest.approx(
        12000.0)
    assert ctx["checkpoint_saves"].device_idle() == {
        "before": pytest.approx(0.0), "under": pytest.approx(9.0),
        "after": pytest.approx(3.0), "writer": pytest.approx(1.0),
        "other": pytest.approx(0.5)}
    text = "\n".join(ctx["checkpoint_saves"].lines())
    assert "under it 9000.000 + after it 3000.000" in text
    assert "under an open writer span and no save 1000.000" in text
    assert "the remainder 500.000 (0.833% of the slice)" in text
    assert "serialize 6000.000, hash 5000.000, put 10000.000, journal " \
           "2000.000" in text and "[12.5]" in text
    assert "drain 3000.000 + copy 6000.000 + enqueue 2000.000" in text


def test_a_save_that_begins_before_the_devices_gap_counts_the_part_before():
    spans = [s for s in one_save() if s.thread == LOOP]
    tr = trace.Trace([trace.DeviceTimeline(
        0, [("fusion", 0.0, 17.0), ("fusion", 33.0, 60.0)], [])], [])
    idle = ctx_of(spans, tr)["checkpoint_saves"].device_idle()
    assert idle["before"] == pytest.approx(1.0)      # [17,18]
    assert idle["under"] == pytest.approx(12.0)
    assert idle["after"] == pytest.approx(3.0)


def test_a_save_cut_by_the_edge_of_the_slice_is_left_out():
    """A second save whose span outlasts the device's last operation: its
    aftermath was not seen, so one save counts and not two; the writer's
    span on it lies outside too."""
    late = [s for s in one_save(seq=4, at=39.5)
            if s.name.startswith(("checkpoint.", "checkpoint_writer."))]
    ctx = ctx_of(one_save() + late)
    saves = ctx["checkpoint_saves"]
    assert [s.stats["seq"] for s in saves.saves] == [3]
    assert [w.stats["seq"] for w in saves.writes] == [3]
    assert reader("ckpt.stall_ms_per_save")(ctx) == pytest.approx(12000.0)
    assert reader("ckpt.write_ms_per_save")(ctx) == pytest.approx(23000.0)
    # and with the slice long enough for both, both count
    both = ctx_of(one_save() + late, device(last=100.0))
    assert len(both["checkpoint_saves"].saves) == 2
    assert reader("ckpt.enqueue_wait_ms_per_save")(both) == pytest.approx(
        2000.0)


@pytest.mark.parametrize("metric", CKPT)
def test_a_slice_with_no_whole_save_reads_as_none_never_as_0(metric):
    loop_only = [s for s in one_save() if s.name.startswith("train.")]
    assert reader(metric)(ctx_of(loop_only)) is None
    # an older program: a snapshot span and nothing round it
    older = loop_only + [Span("checkpoint.step_end", 17.5, 30.5, LOOP, {}),
                         Span("checkpoint.snapshot", 18, 27, LOOP,
                              {"bytes": 5})]
    assert reader(metric)(ctx_of(older)) is None
    assert reader(metric)(ctx_of([])) is None
    # no device operation in the trace: no slice, so no whole save
    assert reader(metric)(ctx_of(one_save(), trace.Trace([], []))) is None
    assert "no whole checkpoint.save" in ctx_of(loop_only)[
        "checkpoint_saves"].lines()[0]


def test_only_the_training_threads_saves_count():
    """A save some other thread makes (a user's, an evaluator's) is not
    the loop's stall."""
    other = [Span(s.name, s.start, s.end, "python#9", dict(s.stats))
             for s in one_save(seq=9) if s.name.startswith("checkpoint.")]
    saves = ctx_of(one_save() + other)["checkpoint_saves"]
    assert saves.thread == LOOP
    assert [s.stats["seq"] for s in saves.saves] == [3]


def test_writer_spans_take_nothing_from_the_loops_idle_time():
    """``program_spans`` reads by prefix from every thread: it must not
    see the writer's spans, and with them filtered as it filters, the idle
    time under the loop is the training thread's alone."""
    spans = one_save()
    seen = [s for s in spans if s.name.startswith(program_spans.PREFIXES)]
    assert [s.name for s in spans if s not in seen] == [
        "checkpoint_writer.write", "checkpoint_writer.serialize",
        "checkpoint_writer.hash", "checkpoint_writer.put",
        "checkpoint_writer.journal"]
    ctx = {"trace": device(), "chips": 1,
           "program_spans": program_spans.ProgramSpans(
               program_spans.nest(seen))}
    # [10,10.5] step_host; [21,33]: snapshot 6, enqueue 2, save 1, step_end
    # 0.5, step_host 0.05 + 0.2, dispatch 2 (iteration 0.05 and data_wait
    # 0.2 are not the loop's own work); [50,51] step_host
    assert reader("device.idle_ms_per_step.loop")(ctx) == pytest.approx(
        1000.0 * (0.5 + 6 + 2 + 1 + 0.5 + 0.25 + 2 + 1.0) / 2)
    assert reader("device.idle_ms_per_step.input")(ctx) == pytest.approx(
        1000.0 * 0.2 / 2)
    # with the writer's spans among them they would have taken [29,30.6]
    # and [50,51] from the loop
    stolen = program_spans.idle_by_span_name(
        device(), program_spans.nest(spans))
    assert stolen["checkpoint_writer.serialize"] == pytest.approx(1.6)
    assert stolen["checkpoint_writer.journal"] == pytest.approx(1.0)


# ---------------------------------------------------------- the window's end
def driver():
    return loader.import_file(os.path.join(
        bench_paths.BENCH, "drivers", "fit_iterator_ckpt.py"), "driver")


@pytest.mark.parametrize("every,deadline_after,expect", [
    (100, 437, 500), (100, 400, 400), (100, 401, 500), (2, 1, 2), (3, 0, 0)])
def test_the_feed_ends_on_a_multiple_of_the_cadence(monkeypatch, every,
                                                    deadline_after, expect):
    """The deadline falls after ``deadline_after`` batches; the feed goes
    on to the next multiple of the cadence and no further."""
    now = [0.0]
    monkeypatch.setattr(feed, "clock", lambda: now[0])
    pool = list("abcdefgh")
    source = driver().CadenceFeed(pool, 3, 1.0, every)
    got = []
    while len(got) <= expect:
        if len(got) == deadline_after:
            now[0] = 2.0
        try:
            got.append(next(source))
        except StopIteration:
            break
    assert len(got) == source.handed_out == expect
    assert got[:6] == [pool[(3 + i) % 8] for i in range(min(expect, 6))]


# ------------------------------------------------------- manifest and files
def test_the_waiting_entries_and_the_cells_file_agree(tmp_path):
    real = loader.load_manifest(bench_paths.ROOT)
    waiting = loader.read_json(ENTRIES)
    # the cell waits: the benchmark as committed neither runs nor reports it
    assert CELL not in [w["name"] for w in real["workloads"]]
    assert not {m["name"] for m in waiting["per_layer"]} & {
        m["name"] for m in real["per_layer"]}
    root = with_the_cell(str(tmp_path))
    manifest = loader.load_manifest(root)
    for key in ("workloads", "per_layer"):          # appended, nothing moved
        assert manifest[key][:len(real[key])] == real[key]
    cell = loader.resolve_cell(root, CELL)
    plain = loader.resolve_cell(root, PLAIN)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": "resnet50_imagenet_bf16",
                     "traffic": "fit_prefetch_b128_ckpt100", "chips": 1,
                     "why": entry["why"]}
    assert len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in CKPT:
        assert by_name[name] == {
            "name": name, "unit": "GB/s" if name.endswith("gb_per_s")
            else "ms", "better": "higher" if name.endswith("gb_per_s")
            else "lower", "source": "program_span", "layer": "checkpoint",
            "moves": "train_items_per_s", "workloads": [CELL]}
    for name in SPAN_READ:                 # PR 24's readers: entries only
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "program_span"
    general = [m["name"] for m in manifest["per_layer"]
               if "workloads" not in m]
    assert cell.cell["per_layer"] == general + list(CKPT) + list(SPAN_READ)
    assert [m["name"] for m in cell.per_layer if "workloads" in m] \
        == list(CKPT) + list(SPAN_READ)
    # the plain cell's mix and limits: the two differ in the saves alone
    assert cell.cell["limits"] == plain.cell["limits"]
    for key in ("wrapper", "batch_per_chip", "pool_batches", "check_steps",
                "warmup_steps"):
        assert cell.traffic[key] == plain.traffic[key], key
    assert cell.config == plain.config
    assert (cell.traffic["save_every_n_steps"], cell.traffic["queue_depth"],
            cell.traffic["keep_last"]) == (100, 2, 2)
    assert cell.traffic["trace_slice_s"] == 6.5
    assert cell.driver.base.__file__ == plain.driver.__file__
    assert cell.driver.control is plain.driver.control
    for fn in ("setup", "run_window", "check", "control"):
        assert callable(getattr(cell.driver, fn))
    for m in cell.per_layer:       # what the manifest's own tests ask of it
        mod = loader.import_file(os.path.join(
            root, "benchmark", "layer_metrics", m["name"] + ".py"),
            "layer_metric")
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"],
                                                    m["moves"])
        assert m["moves"] in {x["name"] for x in cell.end_to_end}
        if m["name"] in CKPT:
            assert "SOURCE: program_span" in mod.__doc__


# ------------------------------------------------------------ the guarantee
def _tiny_session(tmp_path):
    from deeplearning4j_tpu.checkpoint import CheckpointManager
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(Adam(learning_rate=0.01)).weight_init("xavier").list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    data = [_one_batch(seed) for seed in range(4)]
    cm = CheckpointManager(str(tmp_path), save_every_n_steps=2,
                           async_write=True, queue_depth=2, keep_last=2)
    cm.save(net, wait=True)
    net.fit(data, checkpoint_manager=cm)
    cm.flush()
    return types.SimpleNamespace(
        manager=cm, net=net, directory=str(tmp_path), write_error=None,
        saves={"due": 2, "requested": 2, "committed": 2})


def test_the_guarantee_holds_and_a_planted_fault_fails_it(tmp_path):
    drv = driver()
    s = _tiny_session(tmp_path)
    said = []
    assert drv._guarantee(s, said.append) is True
    assert len(said) == 4 and all(line.endswith("ok=True") for line in said)
    # a payload that rotted on the disk: its sha256 no longer verifies, and
    # what restores is the checkpoint before it, two steps behind
    newest = s.manager.checkpoints()[-1]
    path = os.path.join(s.directory, newest["file"])
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0xFF]))
    said = []
    assert drv._guarantee(s, said.append) is False
    assert [line.endswith("ok=True") for line in said] == [
        True, True, False, False]
    # a save asked for and never made durable
    s.saves["committed"] = 1
    said = []
    assert drv._guarantee(s, said.append) is False
    assert [line.endswith("ok=True") for line in said] == [
        False, True, False, False]
    s.saves["committed"] = 2
    # a step after the last save: the checkpoint is no longer the live state
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        f.write(byte)
    assert drv._guarantee(s, lambda line: None) is True
    s.net.fit([_one_batch()])
    said = []
    assert drv._guarantee(s, said.append) is False
    assert [line.endswith("ok=True") for line in said] == [
        True, False, True, False]
    s.manager.close()


def _one_batch(seed=9):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    return DataSet(rng.standard_normal((8, 4)).astype(np.float32),
                   np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)])


# ------------------------------------------- run.py --rehearse, on the CPU
@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One traced rehearsal of the cell through a copy's ``run.py``, in
    this process: its lines, its result and where it left its trace. The
    plain cell's reference check is out of it: at the rehearsal's batch of
    4 in bfloat16 it cannot pass (nor does it in the plain cell: batch norm
    over four values), it has its own tests, and it is not what this cell
    adds; everything else of ``correct`` runs as the chip runs it."""
    root = with_the_cell(str(tmp_path_factory.mktemp("bench")))
    drv = loader.import_file(os.path.join(
        root, "benchmark", "drivers", "fit_iterator_ckpt.py"), "driver")
    spec = importlib.util.spec_from_file_location(
        "bench_run_ckpt", os.path.join(root, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def no_reference(s, say=print):
        s.net = s.trainer = s.fit = None
        return True, []
    real, drv.base.check = drv.base.check, no_reference
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            rc = run.main(["--workload", CELL, "--seed", "3000000001",
                           "--seconds", "3", "--trace", "1", "--rehearse"])
    finally:
        drv.base.check = real
    lines = buf.getvalue().strip().splitlines()
    return types.SimpleNamespace(
        rc=rc, lines=lines, last=json.loads(lines[-1]),
        trace_dir=os.path.join(root, ".bench_trace", CELL))


def test_rehearsal_is_correct_with_the_restored_parameters_bitwise_equal(
        rehearsed):
    assert rehearsed.rc == 0
    last = rehearsed.last
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearse"] is True and last["metrics"] == {}
    window = json.loads(next(l for l in rehearsed.lines
                             if l.startswith("window: "))[len("window: "):])
    # the rehearsal's own cadence: a save every 2 steps, the window ends
    # on one, and every save asked for is durable
    assert last["attempted"] == window["steps"] >= 2
    assert window["steps"] % 2 == 0
    assert window["saves_requested"] == window["saves_committed"] \
        == window["steps"] // 2
    assert window["compiles_in_window"] == 0
    checks = [l for l in rehearsed.lines if l.startswith("check guarantee")]
    assert len(checks) == 4 and all(l.endswith("ok=True") for l in checks)
    assert "bitwise the live ones: True" in checks[-1]
    # the temporary directory lay outside the checkout and is gone
    where = re.search(r"into (\S+) \(", next(
        l for l in rehearsed.lines if l.startswith("set-up's save"))).group(1)
    assert not where.startswith(bench_paths.ROOT)
    assert not os.path.exists(where)


def test_rehearsals_trace_holds_the_saves_on_the_loops_thread(rehearsed):
    """The slice (a second of the window) holds at least one whole save of
    the training thread; of the writer, whose write of 283 MB outlasts it
    here, whatever finished inside, on another thread."""
    spans = checkpoint_spans.read(rehearsed.trace_dir)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    loop = {s.thread for s in by_name["train.iteration"]}
    assert len(loop) == 1
    for name in ("checkpoint.save", "checkpoint.snapshot",
                 "checkpoint.drain", "checkpoint.enqueue"):
        assert {s.thread for s in by_name[name]} == loop, name
    for save in by_name["checkpoint.save"]:
        assert save.parent.name == "checkpoint.step_end"
        assert [c.name for c in save.children] == [
            "checkpoint.snapshot", "checkpoint.enqueue"]
        assert save.children[0].children[0].name == "checkpoint.drain"
        ids = (save.stats["seq"], save.stats["step"])
        assert ids[1] % 2 == 0
        for c in save.children + save.children[0].children:
            assert (c.stats["seq"], c.stats["step"]) == ids
        assert save.stats["bytes"] == save.children[0].stats["bytes"] > 0
    for s in spans:
        if s.name.startswith("checkpoint_writer."):
            assert s.thread not in loop
    # what program_spans reads of the same trace holds no span of the
    # writer's, so nothing of it reaches device.idle_ms_per_step.loop
    names = {s.name for s in program_spans.read(rehearsed.trace_dir)}
    assert "checkpoint.save" in names and "train.iteration" in names
    assert not [n for n in names if n.startswith("checkpoint_writer.")]
    # no device plane on the CPU: the readers have no slice, and say so
    saves = checkpoint_spans.Saves(spans, trace.load(rehearsed.trace_dir))
    assert saves.window is None and saves.saves == []
    assert [reader(m)({"checkpoint_saves": saves}) for m in CKPT] \
        == [None] * 5


def test_a_recorded_trace_tells_two_python_threads_apart(tmp_path):
    """Both the training thread and the writer are lines named ``python``
    on a profiler trace: ``checkpoint_spans.read`` keeps them apart by
    their place, nests each by itself and finds one ``seq`` on both."""
    import jax
    s = _tiny_session(tmp_path / "ck")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=options)
    try:
        s.net.fit([_one_batch(), _one_batch()], checkpoint_manager=s.manager)
        s.manager.flush()
    finally:
        jax.profiler.stop_trace()
    s.manager.close()
    spans = checkpoint_spans.read(str(tmp_path / "trace"))
    save, = [x for x in spans if x.name == "checkpoint.save"]
    write, = [x for x in spans if x.name == "checkpoint_writer.write"]
    assert save.thread != write.thread
    assert save.thread.split("#")[0] == write.thread.split("#")[0]
    assert save.parent.name == "checkpoint.step_end"
    assert write.parent is None
    assert [c.name for c in write.children] == list(checkpoint_spans.PHASES)
    assert (save.stats["seq"], save.stats["step"]) \
        == (write.stats["seq"], write.stats["step"]) == (4, 6)
    assert write.stats["waited_ms"] >= 0 and write.stats["bytes"] > 0
    # the same file through program_spans: one thread name for both lines
    assert len({x.thread for x in program_spans.read(
        str(tmp_path / "trace"))}) == 1

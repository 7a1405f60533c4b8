"""A save measured from inside (obs/trace.py has the tree): what a
``CheckpointManager.save`` costs the thread that asked for it
(``checkpoint.save`` > ``checkpoint.snapshot`` > ``checkpoint.drain``,
``checkpoint.enqueue``) and what the writer does with the snapshot
(``checkpoint_writer.write`` > ``serialize``, ``hash``, ``put``,
``journal``), all spans of one save under one ``seq``; with the tracer off,
the same spans on a profiler trace and not one clock read."""

import glob
import itertools
import queue
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.checkpoint import CheckpointManager
from deeplearning4j_tpu.checkpoint.storage import LocalFSBackend
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Adam

CALLER = ("checkpoint.save", "checkpoint.snapshot", "checkpoint.drain")
WRITER = ("checkpoint_writer.write", "checkpoint_writer.serialize",
          "checkpoint_writer.hash", "checkpoint_writer.put",
          "checkpoint_writer.journal")


@pytest.fixture(autouse=True)
def _quiet_telemetry():
    obs.configure_tracer(enabled=False)
    yield
    obs.configure_tracer(enabled=False, clock=time.perf_counter)
    obs.get_tracer().registry = None


def small_net(seed=11):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(learning_rate=0.01))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def toy_batches(n=3, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.standard_normal((batch, 4)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(n)]


def traced(run):
    """``run()`` with the global tracer on, on a clock that ticks once a
    reading (so every start and end is a count, on every thread); the
    records it sank."""
    ticks = itertools.count()
    sink = []
    obs.configure_tracer(enabled=True, clock=lambda: float(next(ticks)))
    obs.get_tracer().add_sink(sink.append)
    try:
        run()
    finally:
        obs.get_tracer().remove_sink(sink.append)
        obs.configure_tracer(enabled=False, clock=time.perf_counter)
    return sink


def spans(sink, name=None):
    return [r for r in sink if r["kind"] == "span"
            and (name is None or r["name"] == name)]


def one(sink, name, seq=None):
    found = [r for r in spans(sink, name)
             if seq is None or r["attrs"]["seq"] == seq]
    assert len(found) == 1, (name, seq, [r["name"] for r in sink])
    return found[0]


def end(record):
    return record["start"] + record["dur_ms"] / 1000.0


def parent_name(sink, record):
    by_id = {r["id"]: r for r in sink}
    return None if record["parent"] is None else by_id[record["parent"]]["name"]


def test_one_asynchronous_save_is_two_trees_under_one_seq(tmp_path):
    net = small_net()
    net.fit(toy_batches(2))
    cm = CheckpointManager(str(tmp_path), async_write=True)

    def run():
        cm.save(net)
        cm.flush()
    sink = traced(run)
    cm.close()
    names = [r["name"] for r in spans(sink)]
    assert sorted(names) == sorted(CALLER + ("checkpoint.enqueue",) + WRITER)
    save, write = one(sink, "checkpoint.save"), one(sink,
                                                    "checkpoint_writer.write")
    # the calling thread's half
    assert parent_name(sink, save) is None          # nobody's loop called
    assert parent_name(sink, one(sink, "checkpoint.snapshot")) \
        == "checkpoint.save"
    assert parent_name(sink, one(sink, "checkpoint.drain")) \
        == "checkpoint.snapshot"
    assert parent_name(sink, one(sink, "checkpoint.enqueue")) \
        == "checkpoint.save"
    here = threading.get_ident()
    for name in CALLER + ("checkpoint.enqueue",):
        assert one(sink, name)["thread"] == here
    # the writer's half, on its own thread, under names of its own
    assert parent_name(sink, write) is None
    for name in WRITER[1:]:
        assert parent_name(sink, one(sink, name)) == "checkpoint_writer.write"
    on_writer = [r for r in spans(sink) if r["thread"] != here]
    assert sorted(r["name"] for r in on_writer) == sorted(WRITER)
    assert not [r for r in on_writer if r["name"].startswith("checkpoint.")]
    # one save, one seq, one step: the journal's
    entry, = cm.checkpoints()
    for r in spans(sink):
        assert r["attrs"]["seq"] == entry["seq"] == 1, r["name"]
        assert r["attrs"]["step"] == entry["step"] == 2, r["name"]
    snap = one(sink, "checkpoint.snapshot")
    assert save["attrs"]["bytes"] == snap["attrs"]["bytes"] > 0
    assert save["attrs"]["queued"] == 0 and save["attrs"]["sharded"] == 0
    assert one(sink, "checkpoint.enqueue")["attrs"]["queued"] == 0
    assert write["attrs"]["bytes"] == entry["size"]
    assert write["attrs"]["waited_ms"] >= 0.0
    # the instant and its histogram stay, for the operators README names
    commit, = [r for r in sink if r["kind"] == "event"]
    assert commit["name"] == "checkpoint.commit"
    assert commit["attrs"]["bytes"] == entry["size"]


class _Gated(LocalFSBackend):
    """Local storage whose payload writes wait for ``gate``."""

    def __init__(self, directory, gate):
        super().__init__(directory)
        self.gate = gate

    def put(self, name, data, fsync_directory=True):
        if name.startswith("ckpt-"):
            assert self.gate.wait(timeout=60)
        return super().put(name, data, fsync_directory=fsync_directory)


def test_a_full_queue_shows_in_the_enqueue_span(tmp_path, monkeypatch):
    """queue_depth=1 and a writer held in its first ``put``: the second
    save fills the queue, the third waits in ``checkpoint.enqueue`` until
    the writer has come round, and says how many were waiting."""
    gate, blocked = threading.Event(), threading.Event()

    class Watched(queue.Queue):
        def put(self, item, *args, **kwargs):
            if self.full():
                blocked.set()               # the caller is about to wait
            super().put(item, *args, **kwargs)
    monkeypatch.setattr(queue, "Queue", Watched)
    net = small_net()
    cm = CheckpointManager(storage=_Gated(str(tmp_path), gate),
                           async_write=True, queue_depth=1)

    def release():
        assert blocked.wait(timeout=60)
        gate.set()
    helper = threading.Thread(target=release)

    def run():
        helper.start()
        for i in range(3):
            net.fit(toy_batches(1))
            cm.save(net)
            deadline = time.monotonic() + 60
            while i == 0 and not cm._q.empty():   # the writer has the first
                assert time.monotonic() < deadline
                time.sleep(0.001)
        cm.flush()
    sink = traced(run)
    helper.join(timeout=60)
    cm.close()
    assert cm.saves_committed == cm.saves_requested == 3
    waits = {seq: one(sink, "checkpoint.enqueue", seq) for seq in (1, 2, 3)}
    assert [waits[s]["attrs"]["queued"] for s in (1, 2, 3)] == [0, 0, 1]
    assert one(sink, "checkpoint.save", 3)["attrs"]["queued"] == 1
    # the third began to wait while the writer still sat in its first put,
    # and came free only after the writer had taken the second off the queue
    first_put = one(sink, "checkpoint_writer.put", 1)
    assert waits[3]["start"] < end(first_put) < end(waits[3])
    assert end(waits[3]) > one(sink, "checkpoint_writer.write", 2)["start"]
    # the first two found room at once: nothing of the writer's ran inside
    for seq in (1, 2):
        assert end(waits[seq]) < end(first_put)
    # how long a snapshot sat in the queue is on the writer's span
    assert one(sink, "checkpoint_writer.write", 3)["attrs"]["waited_ms"] >= 0


@pytest.mark.parametrize("kind", ["synchronous", "sharded"])
def test_a_synchronous_and_a_sharded_save_open_the_same_names(tmp_path, kind):
    net = small_net()
    net.fit(toy_batches(1))
    cm = CheckpointManager(str(tmp_path), async_write=False,
                           sharded=(kind == "sharded"))
    sink = traced(lambda: cm.save(net))
    assert sorted(r["name"] for r in spans(sink)) == sorted(CALLER + WRITER)
    here = threading.get_ident()
    assert {r["thread"] for r in spans(sink)} == {here}
    # no queue, so no enqueue; the writer's spans lie inside the save
    assert parent_name(sink, one(sink, "checkpoint_writer.write")) \
        == "checkpoint.save"
    for name in WRITER[1:]:
        assert parent_name(sink, one(sink, name)) == "checkpoint_writer.write"
    assert parent_name(sink, one(sink, "checkpoint.drain")) \
        == "checkpoint.snapshot"
    assert {r["attrs"]["seq"] for r in spans(sink)} == {1}
    assert {r["attrs"]["step"] for r in spans(sink)} == {1}
    assert one(sink, "checkpoint.save")["attrs"]["sharded"] \
        == int(kind == "sharded")
    assert one(sink, "checkpoint.save")["attrs"]["bytes"] > 0
    assert cm.saves_committed == 1
    restored = cm.restore_latest()
    for a, b in zip(*(map(np.asarray, _leaves(m)) for m in (net, restored))):
        np.testing.assert_array_equal(a, b)


def _leaves(model):
    import jax
    return jax.tree_util.tree_leaves((model.params, model.opt_state))


def test_a_save_under_the_fit_loop_hangs_under_its_turn(tmp_path):
    net = small_net()
    cm = CheckpointManager(str(tmp_path), save_every_n_steps=2,
                           async_write=True)

    def run():
        net.fit(toy_batches(4), checkpoint_manager=cm)
        cm.flush()
    sink = traced(run)
    cm.close()
    saves = spans(sink, "checkpoint.save")
    assert [s["attrs"]["seq"] for s in saves] == [1, 2]
    assert [s["attrs"]["step"] for s in saves] == [2, 4]
    assert {parent_name(sink, s) for s in saves} == {"checkpoint.step_end"}
    # step_end stays on every turn, save or no save
    assert len(spans(sink, "checkpoint.step_end")) == 4
    loop = saves[0]["thread"]
    assert {r["thread"] for r in spans(sink)
            if r["name"].startswith("checkpoint_writer.")} != {loop}


def test_a_disabled_tracer_reads_no_clock_and_makes_no_record_on_a_save(
        tmp_path):
    """The overhead guard of tests/test_obs.py, on the save's path: with
    the tracer off (the default) a save, its writer and a flush read the
    tracer's clock not once and hand no sink a record."""
    reads, sink = [], []

    def counting_clock():
        reads.append(1)
        return 0.0
    tracer = obs.configure_tracer(enabled=False, clock=counting_clock)
    tracer.add_sink(sink.append)
    try:
        net = small_net()
        cm = CheckpointManager(str(tmp_path), save_every_n_steps=1,
                               async_write=True)
        net.fit(toy_batches(2), checkpoint_manager=cm)
        cm.save(net, wait=True)
        cm.close()
    finally:
        tracer.remove_sink(sink.append)
    assert cm.saves_committed == 3
    assert reads == [] and sink == []
    assert tracer.current() is None and tracer._stack() == []


def test_the_spans_reach_a_profiler_trace_with_the_tracer_off(tmp_path):
    """What a ``--trace 1`` run of the benchmark reads: with nothing
    switched on, the caller's spans on one line of the host plane, the
    writer's on another, one ``seq`` between them."""
    import jax
    net = small_net()
    net.fit(toy_batches(1))
    cm = CheckpointManager(str(tmp_path / "ck"), async_write=True)
    cm.save(net, wait=True)                  # the writer thread is up
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=options)
    try:
        net.fit(toy_batches(1))
        cm.save(net, wait=True)
    finally:
        jax.profiler.stop_trace()
    cm.close()
    path, = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        # a thread is a line; both are named "python", so tell them apart
        # by their place in the plane
        for at, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("checkpoint.", "checkpoint_writer.")):
                    found.setdefault(ev.name, []).append(
                        (at, dict(ev.stats)))
    assert len(found.pop("checkpoint.commit")) == 1
    for name in CALLER + ("checkpoint.enqueue",) + WRITER:
        assert len(found.get(name, [])) == 1, (name, sorted(found))
    lines = {name: found[name][0][0] for name in found}
    assert len({lines[n] for n in CALLER + ("checkpoint.enqueue",)}) == 1
    assert len({lines[n] for n in WRITER}) == 1
    assert lines["checkpoint.save"] != lines["checkpoint_writer.write"]
    for name in CALLER + ("checkpoint.enqueue",) + WRITER:
        stats = found[name][0][1]
        assert (stats["seq"], stats["step"]) == (2, 2), (name, stats)
    assert found["checkpoint.save"][0][1]["bytes"] \
        == found["checkpoint.snapshot"][0][1]["bytes"] > 0
    assert found["checkpoint_writer.write"][0][1]["bytes"] > 0
    assert "waited_ms" in found["checkpoint_writer.write"][0][1]

"""Unified observability layer tests: registry, tracer, exporters, crash
flight recorder — plus the end-to-end chaos post-mortem the ISSUE's
acceptance names: an elastic worker SIGKILLed mid-epoch leaves a
flight-recorder dump in storage whose tail spans land in the supervisor's
``CrashRecord``, while the same run's Prometheus scrape + JSONL event log
carry the per-step phase breakdown and the membership-transition pause.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.checkpoint import CheckpointManager
from deeplearning4j_tpu.checkpoint.faults import FaultInjector, SimulatedCrash
from deeplearning4j_tpu.checkpoint.storage import (LocalFSBackend,
                                                   ObjectStoreBackend)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.obs.flight import latest_dump, read_dumps
from deeplearning4j_tpu.optimize.updaters import Sgd

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _quiet_telemetry():
    """Every test starts with tracing off and no flight recorder, and
    leaves the process the same way (the registry is process-global by
    design; tests assert deltas/presence, not exclusivity)."""
    obs.configure_tracer(enabled=False)
    obs.uninstall_flight_recorder()
    yield
    obs.configure_tracer(enabled=False, clock=time.perf_counter)
    obs.get_tracer().registry = None
    obs.uninstall_flight_recorder()


def small_net(seed=11):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(learning_rate=0.05))
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


# ================================================================ registry
class TestRegistry:
    def test_counter_gauge_histogram(self):
        r = obs.MetricsRegistry()
        c = r.counter("reqs_total", unit="requests", help="served")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = r.gauge("depth", unit="requests", help="queue depth")
        g.set(7)
        assert g.value == 7
        h = r.histogram("lat_ms", unit="ms", help="latency")
        for v in (1, 2, 3, 4, 100):
            h.observe(v)
        d = h.as_dict()
        assert d["count"] == 5 and d["max"] == 100 and d["min"] == 1
        assert 0 < d["p50"] <= d["p95"] <= d["p99"] <= 100

    def test_registration_is_idempotent_and_kind_checked(self):
        r = obs.MetricsRegistry()
        a = r.counter("x_total", unit="x", help="x")
        assert r.counter("x_total", unit="y", help="z") is a
        with pytest.raises(obs.MetricError):
            r.gauge("x_total", unit="x", help="x")

    def test_units_and_help_required(self):
        r = obs.MetricsRegistry()
        with pytest.raises(obs.MetricError):
            r.counter("a_total", unit="", help="h")
        with pytest.raises(obs.MetricError):
            r.counter("a_total", unit="u", help=" ")
        with pytest.raises(obs.MetricError):
            r.counter("Bad-Name", unit="u", help="h")

    def test_quantiles_bounded_by_observations(self):
        r = obs.MetricsRegistry()
        h = r.histogram("q_ms", unit="ms", help="h")
        for v in (10, 10, 10):
            h.observe(v)
        assert h.quantile(0.99) <= 10.0
        assert h.quantile(0.0) >= 0.0

    def test_collect_callback_absorbs_live_source(self):
        r = obs.MetricsRegistry()
        obs.absorb_compile_watch(r)  # direct absorb of the GLOBAL watch
        assert r.metric("jit_compiles") is not None
        calls = []
        r.register_callback(lambda reg: calls.append(1))
        r.as_dict()
        assert calls == [1]

    def test_absorb_training_stats(self):
        from deeplearning4j_tpu.parallel.stats import TrainingStats
        ts = TrainingStats()
        ts.record("epoch_sync", 0.25)
        ts.inc_counter("model_compiles", 3)
        ts.examples = 64
        r = obs.MetricsRegistry()
        obs.absorb_training_stats(r, ts)
        assert r.metric("train_phase_epoch_sync_total_ms").value == 250.0
        assert r.metric("train_phase_model_compiles").value == 3
        assert r.metric("train_phase_examples").value == 64

    def test_watch_training_stats_is_live_and_self_removing(self):
        from deeplearning4j_tpu.parallel.stats import TrainingStats
        ts = TrainingStats()
        r = obs.MetricsRegistry()
        obs.watch_training_stats(r, ts)
        ts.examples = 7
        assert r.as_dict()["train_phase_examples"]["value"] == 7
        ts.examples = 9  # live source: next scrape sees the new value
        assert r.as_dict()["train_phase_examples"]["value"] == 9
        del ts
        r.as_dict()  # dead weakref: the callback unregisters itself
        assert not r._callbacks

    def test_parallel_wrapper_wires_stats_into_default_registry(self):
        from deeplearning4j_tpu.parallel import ParallelWrapper
        pw = ParallelWrapper(small_net(), collect_stats=True)
        pw.stats.examples = 31
        d = obs.get_registry().as_dict()
        assert d["train_phase_examples"]["value"] == 31


# ================================================================== tracer
class TestTracer:
    def test_disabled_is_noop_by_opcount(self):
        """Overhead guard asserted by OP COUNT, not wall clock (the 9p
        bench-sensitivity note): a disabled tracer never reads the clock,
        never touches a sink, and allocates nothing but the profiler
        annotation every span also is (no record, no stack entry)."""
        clock_calls = []

        def counting_clock():
            clock_calls.append(1)
            return 0.0
        sink_calls = []
        t = obs.Tracer(enabled=False, clock=counting_clock)
        t.add_sink(sink_calls.append)
        s1 = t.span("a", step=1)
        s2 = t.span("b")
        with s1:
            pass
        t.event("c", x=1)
        import jax
        for s in (s1, s2):  # nothing but the annotation
            assert isinstance(s, jax.profiler.TraceAnnotation)
            assert vars(s) == {}
        assert t.current() is None and t._stack() == []
        assert list(t.wrap_iter([1, 2, 3], "w", turn="t",
                                step=lambda: 0)) == [1, 2, 3]
        assert clock_calls == []
        assert sink_calls == []

    def test_enabled_records_spans_and_histograms(self):
        r = obs.MetricsRegistry()
        sink = []
        t = obs.Tracer(enabled=True, registry=r)
        t.add_sink(sink.append)
        with t.span("phase.one", step=3):
            pass
        t.event("boundary", gen=2)
        kinds = [(s["kind"], s["name"]) for s in sink]
        assert kinds == [("span", "phase.one"), ("event", "boundary")]
        assert sink[0]["attrs"] == {"step": 3}
        assert r.metric("phase_one_ms").count == 1
        for rec in sink:  # what self time and a timeline need
            assert {"id", "parent", "thread", "start", "wall",
                    "dur_ms"} <= set(rec)

    def test_parents_self_time_and_step_inheritance(self):
        """A span's parent is the span open on its thread when it began;
        a child that names no step takes its parent's; another thread's
        spans hang under what it attached."""
        import threading
        ticks = iter(range(100))
        sink = []
        t = obs.Tracer(enabled=True, clock=lambda: float(next(ticks)))
        t.add_sink(sink.append)
        with t.span("outer", step=7) as outer:
            with t.span("a"):
                pass
            t.event("mark")
            assert t.current() is outer

            def other():
                with t.attach(outer), t.span("b"):
                    pass
                assert t.current() is None
            th = threading.Thread(target=other)
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()
        by = {r["name"]: r for r in sink}
        assert by["outer"]["parent"] is None
        for child in ("a", "mark", "b"):
            assert by[child]["parent"] == by["outer"]["id"]
            assert by[child]["attrs"]["step"] == 7
        assert by["b"]["thread"] != by["outer"]["thread"]
        assert len({r["id"] for r in sink}) == len(sink)
        # self time: the span's duration minus its children's
        children = sum(r["dur_ms"] for r in sink
                       if r["kind"] == "span"
                       and r["parent"] == by["outer"]["id"])
        assert by["outer"]["dur_ms"] - children > 0
        assert by["a"]["start"] >= by["outer"]["start"]

    def test_cancelled_span_leaves_no_record(self):
        sink = []
        t = obs.Tracer(enabled=True)
        t.add_sink(sink.append)
        with t.span("kept"):
            t.span("dropped").cancel()
            assert t.current().name == "kept"
        assert [r["name"] for r in sink] == ["kept"]

    def test_wrap_iter_times_each_next(self):
        sink = []
        t = obs.Tracer(enabled=True)
        t.add_sink(sink.append)
        out = list(t.wrap_iter(iter([10, 20]), "data_wait"))
        assert out == [10, 20]
        assert [s["name"] for s in sink] == ["data_wait", "data_wait"]
        # with a turn: each item's whole turn of the caller's loop is one
        # span, its first child the wait; the exhausted probe leaves none
        del sink[:]
        n = [0]
        for _ in t.wrap_iter(iter([10, 20]), "wait", turn="turn",
                             step=lambda: n[0]):
            with t.span("body"):
                n[0] += 1
        assert [s["name"] for s in sink] == ["wait", "body", "turn"] * 2
        turns = [s for s in sink if s["name"] == "turn"]
        assert [s["attrs"]["step"] for s in turns] == [0, 1]
        for s in sink:
            if s["name"] != "turn":
                assert s["parent"] in {u["id"] for u in turns}

    def test_sink_errors_never_break_the_span(self):
        t = obs.Tracer(enabled=True)
        t.add_sink(lambda rec: (_ for _ in ()).throw(RuntimeError("boom")))
        with t.span("ok"):
            pass  # must not raise

    def test_stopwatch_syncs_then_stops(self):
        import jax.numpy as jnp
        sw = obs.Stopwatch().start()
        out = jnp.arange(8) * 2
        dt = sw.stop(out)
        assert dt == sw.seconds >= 0.0
        with obs.Stopwatch() as sw2:
            pass
        assert sw2.seconds >= 0.0
        with pytest.raises(RuntimeError):
            obs.Stopwatch().stop()


# ========================================================== fit phase spans
def small_graph(seed=5):
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder, MergeVertex
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    parent = (NeuralNetConfiguration.builder().seed(seed)
              .updater(Sgd(learning_rate=0.05)).weight_init("xavier"))
    conf = (GraphBuilder(parent)
            .add_inputs("in")
            .add_layer("d1", DenseLayer(n_out=6, activation="relu"), "in")
            .add_layer("d2", DenseLayer(n_out=6, activation="tanh"), "in")
            .add_vertex("merge", MergeVertex(), "d1", "d2")
            .add_layer("out", OutputLayer(n_out=3, loss="mcxent"), "merge")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(4))
            .build())
    return ComputationGraph(conf).init()


def small_tbptt_net(seed=21):
    from deeplearning4j_tpu.nn.conf.recurrent import LSTM, RnnOutputLayer
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(learning_rate=0.05))
            .weight_init("xavier").list()
            .layer(LSTM(n_out=8, activation="tanh"))
            .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.recurrent(4))
            .backprop_type("tbptt", fwd_length=5, back_length=5)
            .build())
    return MultiLayerNetwork(conf).init()


def sequence_batch(batch=3, steps=15, seed=5):
    rng = np.random.default_rng(seed)
    return (rng.random((batch, steps, 4)).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.integers(0, 3, (batch, steps))])


class _CountingListener:
    """The least a listener is: makes ``train.listeners`` appear."""

    def __init__(self):
        self.calls = []

    def iteration_done(self, model, iteration, epoch):
        self.calls.append(iteration)

    def on_epoch_start(self, model):
        pass

    def on_epoch_end(self, model):
        pass


def _traced(run):
    """``run()`` with the global tracer on; the records it sank."""
    sink = []
    obs.configure_tracer(enabled=True)
    obs.get_tracer().add_sink(sink.append)
    try:
        run()
    finally:
        obs.get_tracer().remove_sink(sink.append)
        obs.configure_tracer(enabled=False)
    return sink


def _fit_mln():
    net = small_net()
    net.fit(toy_batches(3), num_epochs=2, prefetch=True)
    return net


def _fit_graph():
    net = small_graph()
    net.fit(toy_batches(3), num_epochs=2, prefetch=True)
    return net


def _fit_parallel_wrapper():
    import jax
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    net = small_net()
    mesh = make_mesh(dp=1, tp=1, devices=jax.devices()[:1])
    ParallelWrapper(net, mesh=mesh).fit(toy_batches(3), num_epochs=2,
                                        prefetch=True)
    return net


def _fit_tbptt_fused():
    net = small_tbptt_net()
    for _ in range(6):
        net.fit_tbptt_fused(*sequence_batch())
    return net


def _fit_cluster_local_shard():
    """ClusterTrainer.fit_local_shard as far as one process runs it: the
    batch is this process's whole shard, staged one ahead by place_fn."""
    import jax
    from deeplearning4j_tpu.parallel import ClusterTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    net = small_net()
    mesh = make_mesh(dp=1, tp=1, devices=jax.devices()[:1])
    ClusterTrainer(net, mesh=mesh).fit_local_shard(
        toy_batches(3), num_epochs=2, prefetch=True)
    return net


def _fit_solver():
    """The stack's full-batch solver loop: the three outer spans of the
    tree and nothing under ``train.step_host`` (prefetch is ignored)."""
    conf = (NeuralNetConfiguration.builder()
            .seed(11).updater(Sgd(learning_rate=0.05))
            .weight_init("xavier").list()
            .optimization_algo("lbfgs")
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.fit(toy_batches(3), num_epochs=2)
    return net


# run, optimizer steps a turn, streamed (a data wait a turn), placed (the
# prefetcher's spans under the wait), inner (the batch's own spans)
LOOPS = {"mln": (_fit_mln, 1, True, True, True),
         "graph": (_fit_graph, 1, True, True, True),
         "parallel_wrapper": (_fit_parallel_wrapper, 1, True, True, True),
         "cluster_local_shard": (_fit_cluster_local_shard, 1, True, True,
                                 True),
         "solver": (_fit_solver, 1, True, False, False),
         "tbptt_fused": (_fit_tbptt_fused, 3, False, False, True)}


class TestFitPhaseBreakdown:
    def test_mln_fit_emits_phase_spans(self):
        sink = _traced(lambda: small_net().fit(toy_batches(3), num_epochs=2))
        names = [s["name"] for s in sink]
        for name in ("train.iteration", "train.data_wait", "train.step_host",
                     "train.stage", "train.dispatch", "train.post"):
            assert names.count(name) == 6, name
        # no listener, no manager: their spans are absent, not empty
        assert "train.listeners" not in names
        assert "checkpoint.step_end" not in names
        assert not [n for n in names if "device" in n]
        host = [s for s in sink if s["name"] == "train.step_host"]
        assert [s["attrs"]["step"] for s in host] == list(range(6))
        assert all(s["attrs"]["items"] == 16 for s in host)

    @pytest.mark.parametrize("loop", sorted(LOOPS))
    def test_every_loop_emits_the_same_span_tree(self, loop):
        """Names, parents and one ``step`` a turn: the same tree out of
        MultiLayerNetwork.fit (the solver loop: its outer three spans),
        ComputationGraph.fit, ParallelWrapper.fit,
        ClusterTrainer.fit_local_shard and fit_tbptt_fused."""
        run, steps_per_turn, streamed, placed, inner_spans = LOOPS[loop]
        sink = _traced(run)
        spans = [s for s in sink if s["kind"] == "span"]
        by_id = {s["id"]: s for s in spans}
        turns = [s for s in spans if s["name"] == "train.iteration"]
        assert len(turns) == 6 and all(t["parent"] is None for t in turns)
        assert [t["attrs"]["step"] for t in turns] == [
            i * steps_per_turn for i in range(6)]

        def children(parent):
            return [s["name"] for s in sorted(
                (s for s in spans if s["parent"] == parent["id"]),
                key=lambda s: s["start"])]

        for turn in turns:
            want = ["train.step_host"]
            if streamed:
                want.insert(0, "train.data_wait")
            assert children(turn) == want
            host = next(s for s in spans if s["parent"] == turn["id"]
                        and s["name"] == "train.step_host")
            inner = children(host)
            if not inner_spans:
                assert inner == []
                continue
            # ParallelWrapper hands the batch over twice: its own sharding,
            # then the model's (no-op) asarray
            assert [n for i, n in enumerate(inner)
                    if i == 0 or n != inner[i - 1]] == [
                "train.stage", "train.dispatch", "train.post"], inner
            dispatch = next(s for s in spans if s["parent"] == host["id"]
                            and s["name"] == "train.dispatch")
            assert dispatch["attrs"].get("steps", 1) == steps_per_turn
        # every span of a turn carries that turn's step, and only that
        for s in spans:
            top = s
            while top["parent"] is not None:
                top = by_id[top["parent"]]
            assert top["name"] == "train.iteration", s
            assert s["attrs"]["step"] == top["attrs"]["step"], s
        if placed:
            # batch N+1 is placed inside turn N's wait for batch N (the
            # first wait places two, the last none)
            places = [s for s in spans if s["name"] == "prefetch.place"]
            assert len(places) == 6
            assert all(by_id[p["parent"]]["name"] == "train.data_wait"
                       and p["attrs"]["arrays"] == 2
                       and p["attrs"]["bytes"] == 16 * (4 + 3) * 4
                       for p in places)

    def test_listeners_and_checkpoint_spans_sit_under_step_host(
            self, tmp_path):
        net = small_net()
        listener = _CountingListener()
        net.set_listeners(listener)
        cm = CheckpointManager(str(tmp_path / "ck"), save_every_n_steps=2,
                               async_write=False)
        sink = _traced(lambda: net.fit(toy_batches(4),
                                       checkpoint_manager=cm))
        by_id = {s["id"]: s for s in sink}

        def parent_name(s):
            return by_id[s["parent"]]["name"]

        assert listener.calls == [0, 1, 2, 3]
        listeners = [s for s in sink if s["name"] == "train.listeners"]
        ends = [s for s in sink if s["name"] == "checkpoint.step_end"]
        saves = [s for s in sink if s["name"] == "checkpoint.save"]
        snaps = [s for s in sink if s["name"] == "checkpoint.snapshot"]
        assert len(listeners) == len(ends) == 4
        assert len(saves) == len(snaps) == 2
        assert {parent_name(s) for s in listeners + ends} == {
            "train.step_host"}
        # a save that triggers hangs under the turn's step_end (the rest of
        # its tree: tests/test_checkpoint_spans.py)
        assert {parent_name(s) for s in saves} == {"checkpoint.step_end"}
        assert {parent_name(s) for s in snaps} == {"checkpoint.save"}
        # a save's spans carry the step its checkpoint holds (the steps
        # done: the turn's own step + 1)
        assert [s["attrs"]["step"] for s in saves + snaps] == [2, 4, 2, 4]
        assert all(s["attrs"]["bytes"] > 0 for s in snaps)

    def test_per_window_tbptt_spans_carry_each_windows_step(self):
        net = small_tbptt_net()
        x, y = sequence_batch()
        sink = _traced(lambda: net.fit(DataSet(x, y)))
        dispatches = [s for s in sink if s["name"] == "train.dispatch"]
        assert [(d["attrs"]["program"], d["attrs"]["step"])
                for d in dispatches] == [("tbptt", 0), ("tbptt", 1),
                                         ("tbptt", 2)]
        assert [s["name"] for s in sink].count("train.step_host") == 1

    def test_counters_at_the_span_boundaries(self):
        reg = obs.get_registry()

        def value(name):
            m = reg.metric(name)
            return m.value if m is not None else 0.0
        before = {n: value(n) for n in ("train_steps_total",
                                        "train_items_total",
                                        "prefetch_bytes_total")}
        small_net().fit(toy_batches(3), prefetch=True)   # tracer OFF
        _fit_tbptt_fused()
        assert value("train_steps_total") - before["train_steps_total"] \
            == 3 + 6 * 3
        assert value("train_items_total") - before["train_items_total"] \
            == 3 * 16 + 6 * 3 * 3
        assert value("prefetch_bytes_total") \
            - before["prefetch_bytes_total"] == 3 * 16 * (4 + 3) * 4

    def test_tracer_on_never_syncs_the_device(self, monkeypatch):
        """The traced program is the program: a fit of 6 batches with the
        tracer on never calls ``jax.block_until_ready``."""
        import jax

        def refuse(*a, **k):
            raise AssertionError("fit synced the device for the tracer")
        nets = [small_net(), small_graph()]
        monkeypatch.setattr(jax, "block_until_ready", refuse)
        sink = _traced(lambda: [net.fit(toy_batches(6)) for net in nets])
        assert [s["name"] for s in sink].count("train.iteration") == 12

    def test_recompile_is_an_event_at_its_step(self):
        """A second batch shape mid-run: one more ``compile`` event, which
        names the step, and ``compiled=1`` on that step's dispatch."""
        net = small_net()
        data = toy_batches(2) + toy_batches(1, batch=8) + toy_batches(1)
        sink = _traced(lambda: net.fit(data))
        compiles = [s for s in sink if s["name"] == "compile"]
        assert [(c["attrs"]["program"], c["attrs"]["step"])
                for c in compiles] == [("train", 0), ("train", 2)]
        dispatches = [s for s in sink if s["name"] == "train.dispatch"]
        assert [d["attrs"].get("compiled", 0) for d in dispatches] == [
            1, 0, 1, 0]
        assert all(c["parent"] == d["id"] for c, d in zip(
            compiles, [dispatches[0], dispatches[2]]))
        assert net.compile_watch.compiles("train") == 2

    def test_disabled_tracer_changes_nothing(self):
        # identical loss, parameters and iteration with tracing off and
        # on: the spans are host-side only, never enter the traced program
        # and the loop's body is the same code either way
        import jax
        for make in (small_net, small_graph):
            a, b = make(seed=5), make(seed=5)
            data = toy_batches(3)
            a.fit(data, num_epochs=2)
            _traced(lambda: b.fit(data, num_epochs=2))
            assert a.iteration == b.iteration == 6
            assert np.asarray(a.score()).tobytes() \
                == np.asarray(b.score()).tobytes()
            for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                              jax.tree_util.tree_leaves(b.params)):
                np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    def test_spans_reach_a_profiler_trace_with_the_tracer_off(
            self, tmp_path):
        """Every span is a TraceAnnotation: a jax.profiler session sees
        the fit loop's spans on the host plane, with their ``step`` stat,
        with nothing switched on in the program."""
        import glob
        import jax
        net = small_net()
        net.fit(toy_batches(1))                      # compile outside
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            net.fit(toy_batches(3), prefetch=True)
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        found = {}
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("train.", "prefetch.")):
                        found.setdefault(ev.name, []).append(
                            dict(ev.stats))
        for name in ("prefetch.place", "train.step_host", "train.stage",
                     "train.dispatch", "train.post"):
            assert len(found.get(name, [])) == 3, (name, sorted(found))
        # an annotation cannot be taken back: the probe that found the
        # stream exhausted is a fourth turn with a wait and no step_host
        for name in ("train.iteration", "train.data_wait"):
            assert len(found.get(name, [])) == 4, (name, sorted(found))
        assert [st["step"] for st in found["train.dispatch"]] == [1, 2, 3]
        assert found["train.dispatch"][0]["program"] == "train"


class TestFusedTbptt:
    def test_window_scores_keeps_every_windows_loss(self):
        """The scan's per-window losses stay on the device; the last is
        the score, and each equals the per-window path's."""
        import jax
        x, y = sequence_batch()
        fused, seq = small_tbptt_net(), small_tbptt_net()
        assert fused.window_scores() is None
        fused.fit_tbptt_fused(x, y)
        scores = fused.window_scores()
        assert isinstance(scores, jax.Array) and scores.shape == (3,)
        assert float(scores[-1]) == float(fused.score())
        per_window = []

        class Keep(_CountingListener):
            def iteration_done(self, model, iteration, epoch):
                per_window.append(float(model.score()))
        seq.set_listeners(Keep())
        seq.fit(DataSet(x, y))
        np.testing.assert_array_equal(np.asarray(scores),
                                      np.asarray(per_window, np.float32))


# ======================================= names on the device side of a trace
class TestDeviceSideNames:
    def _conv_graph(self):
        from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
        from deeplearning4j_tpu.nn.conf.convolutional import ConvolutionLayer
        from deeplearning4j_tpu.nn.conf.normalization import (
            BatchNormalization)
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        parent = (NeuralNetConfiguration.builder().seed(3)
                  .updater(Sgd(learning_rate=0.05)).weight_init("xavier"))
        conf = (GraphBuilder(parent)
                .add_inputs("in")
                .add_layer("conv1", ConvolutionLayer(
                    n_out=4, kernel_size=(3, 3), activation="identity"),
                    "in")
                .add_layer("bn1", BatchNormalization(activation="relu"),
                           "conv1")
                .add_layer("out", OutputLayer(n_out=3, loss="mcxent"), "bn1")
                .set_outputs("out")
                .set_input_types(InputType.convolutional(8, 8, 2))
                .build())
        return ComputationGraph(conf).init()

    def test_step_hlo_names_its_layers_and_the_step_its_program(self):
        """What a device trace shows of a ResNet50-shaped step: the
        program under a stable name, every operation under its layer's
        kind and name in ``op_name``."""
        import jax.numpy as jnp
        net = self._conv_graph()
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 8, 8, 2)), jnp.float32)
        y = jnp.asarray(np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)])
        step = net._get_jitted("train")
        lowered = step.lower(net.params, net.state, net.opt_state,
                             net._rng, [x], [y], None, None)
        assert "jit_train_step" in lowered.as_text().splitlines()[0]
        hlo = lowered.compile().as_text()
        ops = [l for l in hlo.splitlines() if "op_name=" in l]
        for scope in ("ConvolutionLayer:conv1", "BatchNormalization:bn1"):
            assert any(scope in l for l in ops), scope
            # the backward pass of the layer carries the scope too
            assert any(scope in l and "transpose" in l for l in ops), scope

    @pytest.mark.parametrize("kind,name", [
        ("train", "train_step"), ("output", "output"), ("score", "score")])
    def test_jitted_programs_have_stable_names(self, kind, name):
        for net in (small_net(), small_graph()):
            fn = net._get_jitted(kind)
            assert fn.__wrapped__.__name__ == name, (type(net), kind)

    def test_scopes_do_not_change_the_numbers(self):
        """Losses with the scopes are bitwise what they are without."""
        import contextlib
        import jax
        from unittest import mock
        data = [DataSet(np.random.default_rng(i).standard_normal(
            (4, 8, 8, 2)).astype(np.float32),
            np.eye(3, dtype=np.float32)[[0, 1, 2, 0]]) for i in range(3)]

        def losses():
            net, out = self._conv_graph(), []
            for ds in data:
                net.fit(ds)
                out.append(np.asarray(net.score()).tobytes())
            return out
        scoped = losses()
        with mock.patch.object(jax, "named_scope",
                               lambda name: contextlib.nullcontext()):
            assert losses() == scoped


# ================================================ every operation has an owner
class TestOwners:
    @pytest.mark.parametrize("op_name,owner", [
        ("jit(train_step)/jvp(RoutedExperts:l2_ffn)/cond/branch_0_fun/"
         "moe.dispatch/gather", "RoutedExperts"),
        # jvp(...) / transpose(...) wrappers, the backward pass
        ("jit(train_step)/transpose(jvp(KimiDeltaAttention:l1_attn))/"
         "jvp(KimiDeltaAttention:l1_attn)/checkpoint/kda.conv/add_any",
         "KimiDeltaAttention"),
        # nested layers inside loop.body: the innermost marker
        ("jit(train_step)/jvp(LoopVertex:loop)/loop.body/while/body/"
         "LoopVertex:l0_attn/RotaryAttention:attn/rattn.attend/dot_general",
         "RotaryAttention"),
        ("LoopVertex:l3_ffn/ElementWiseVertex:add2/add", "ElementWiseVertex"),
        ("jit(train_step)/jvp(LoopVertex:loop)/loop.body/while",
         "LoopVertex"),
        # a layer name with dots, a stack's layer named by its index
        ("jit(train_step)/transpose(jvp(DenseLayer:block.0.dense))/"
         "dot_general", "DenseLayer"),
        ("jit(train_step)/jvp(OutputLayer:1)/dot_general", "OutputLayer"),
        # the scopes outside any layer
        ("jit(train_step)/optim.update/add", "optim"),
        ("jit(train_step)/optim.update/jit(_where)/select_n", "optim"),
        ("jit(train_step)/grad.compress/sign", "grad.compress"),
        ("jit(train_step)/jvp(params.cast)/convert_element_type",
         "params.cast"),
        ("jit(train_step)/transpose(jvp(params.cast))/convert_element_type",
         "params.cast"),
        ("jit(train_step)/jvp(loss.score)/loss.blocked/while", "loss"),
        ("jit(train_step)/transpose(jvp(loss.penalty))/mul", "loss"),
        ("jit(train_step)/jvp(loop.exit_head)/while/body/dot_general",
         "loss"),
        # a layer's marker wins over a scope around or inside it
        ("jit(train_step)/jvp(loss.score)/CenterLossOutputLayer:out/sub",
         "CenterLossOutputLayer"),
        # nobody's
        ("jit(train_step)/add", None),
        ("jit(train_step)/jit(searchsorted)/vmap()/while/body/gather", None),
        ("params['l4_attn']['Wq']", None), ("", None), (None, None)])
    def test_owner_of(self, op_name, owner):
        from deeplearning4j_tpu.obs.owners import owner_of
        assert owner_of(op_name) == owner

    def test_the_scopes_outside_the_layers_are_one_list(self):
        """Every constant the program opens a scope with is in
        ``NON_LAYER_SCOPES``: ``owner_of`` is the only rule."""
        from deeplearning4j_tpu.obs import owners
        for scope in (owners.OPTIM, owners.GRAD_COMPRESS, owners.PARAMS_CAST,
                      owners.LOSS_SCORE, owners.LOSS_PENALTY):
            assert owners.owner_of(f"jit(train_step)/{scope}/mul") is not None
        assert owners.layer_marker(DenseLayer(n_out=2), "d.1") \
            == "DenseLayer:d.1"

    @pytest.mark.parametrize("make", ["stack", "graph"])
    def test_the_compiled_step_names_an_owner_for_all_it_emitted(
            self, make, step_op_names):
        """Both network classes: the step holds instructions under
        ``optim.update``, and of the instructions jax emitted NONE is
        without an owner (no primitive is excused): the optimizer, the
        loss, a graph's merge vertex, an output layer's own product."""
        import collections
        import jax.numpy as jnp
        from deeplearning4j_tpu.obs.owners import owner_of
        ds = toy_batches(1)[0]
        x, y = jnp.asarray(ds.features), jnp.asarray(ds.labels)
        if make == "stack":
            names = step_op_names(small_net(), x, y)
        else:
            names = step_op_names(small_graph(), [x], [y])
        assert any("/optim.update/" in n for n in names)
        owners = collections.Counter(owner_of(n) for n in names)
        unowned = sorted({n.rsplit("/", 1)[-1] for n in names
                          if owner_of(n) is None})
        assert unowned == [], unowned
        assert {"optim", "loss", "DenseLayer", "OutputLayer"} <= set(owners)
        if make == "graph":
            assert owners["MergeVertex"] > 0

    def test_a_bfloat16_step_owns_its_cast_and_a_penalty_its_sum(
            self, step_op_names):
        import jax.numpy as jnp
        from deeplearning4j_tpu.obs.owners import owner_of
        conf = (NeuralNetConfiguration.builder().seed(3)
                .updater(Sgd(learning_rate=0.05)).weight_init("xavier")
                .l2(1e-3).dtype("bfloat16").list()
                .layer(DenseLayer(n_out=8, activation="relu"))
                .layer(OutputLayer(n_out=3, loss="mcxent"))
                .set_input_type(InputType.feed_forward(4)).build())
        ds = toy_batches(1)[0]
        names = step_op_names(MultiLayerNetwork(conf).init(),
                              jnp.asarray(ds.features),
                              jnp.asarray(ds.labels))
        assert any("params.cast" in n and "transpose(" not in n
                   for n in names)
        assert any("loss.penalty" in n for n in names)
        assert [n for n in names if owner_of(n) is None] == []

    def test_a_compressed_step_owns_its_encode_and_decode(
            self, step_op_names):
        import jax.numpy as jnp
        from deeplearning4j_tpu.obs.owners import owner_of
        from deeplearning4j_tpu.parallel.compress import (
            ThresholdCompression, enable_grad_compression,
            ensure_compress_state)
        net = small_net()
        enable_grad_compression(net, ThresholdCompression())
        ensure_compress_state(net)
        ds = toy_batches(1)[0]
        names = step_op_names(net, jnp.asarray(ds.features),
                              jnp.asarray(ds.labels),
                              extra=(net.compress_state,))
        assert {"grad.compress", "optim"} <= {owner_of(n) for n in names}
        assert [n for n in names if owner_of(n) is None] == []

    def test_the_lowered_text_names_the_scopes_without_a_compile(self):
        """What a scope test can read with no executable at all:
        ``lowered.as_text(debug_info=True)`` is never cached."""
        import jax.numpy as jnp
        net = small_graph()
        ds = toy_batches(1)[0]
        lowered = net._get_jitted("train").lower(
            net.params, net.state, net.opt_state, net._rng,
            [jnp.asarray(ds.features)], [jnp.asarray(ds.labels)], None, None)
        text = lowered.as_text(debug_info=True)
        for scope in ("optim.update", "loss.score", "MergeVertex:merge",
                      "OutputLayer:out", "DenseLayer:d1"):
            assert scope in text, scope


# ================================================ what a compile's time went on
class TestCompilePhases:
    def test_the_compile_event_says_what_the_time_went_on(self):
        """The first call of a program: a ``compile`` event with the
        seconds of tracing, lowering and the backend, and whether the
        persistent cache served it; the same on the enclosing dispatch.
        The second call records nothing."""
        net = small_net()
        data = toy_batches(2)
        sink = _traced(lambda: net.fit(data))
        compiles = [s for s in sink if s["name"] == "compile"]
        assert len(compiles) == 1
        attrs = compiles[0]["attrs"]
        assert {"trace_s", "lower_s", "backend_s", "cache_load_s",
                "cache_hit", "program", "step"} <= set(attrs)
        assert attrs["trace_s"] > 0 and attrs["lower_s"] > 0 \
            and attrs["backend_s"] > 0
        assert attrs["cache_hit"] in (0, 1)
        assert (attrs["cache_load_s"] > 0) == bool(attrs["cache_hit"])
        first, second = [s for s in sink if s["name"] == "train.dispatch"]
        assert first["attrs"]["compiled"] == 1
        for key in ("trace_s", "lower_s", "backend_s", "cache_load_s",
                    "cache_hit"):
            assert first["attrs"][key] == attrs[key]
            assert key not in second["attrs"]
        phases = net.compile_watch.compile_phases("train")
        assert phases["trace_s"] == attrs["trace_s"]
        net.fit(data)                           # warm: nothing more
        assert net.compile_watch.compile_phases("train") == phases

    def test_a_jit_traced_inside_a_jit_is_counted_once(self):
        """The inner function's trace event ends inside the outer one's:
        the call's ``trace_s`` is the outermost event's seconds."""
        from deeplearning4j_tpu.perf import compile_watch as cw
        call = cw._CompilePhases()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.02:
            pass
        call.add_seconds("trace_s", 0.005)      # inner, ended just now
        call.add_seconds("trace_s", 0.019)      # outer, holds it
        call.add_seconds("lower_s", 0.5)
        call.count("cache_hits")
        assert call.totals() == {"trace_s": 0.019, "lower_s": 0.5,
                                 "cache_hits": 1}
        # two in a row (not nested) add up
        again = cw._CompilePhases()
        again.add_seconds("backend_s", 0.0)
        again.add_seconds("backend_s", 0.0)
        assert len(again._spans["backend_s"]) == 2

    def test_thousands_of_events_in_a_call_cost_no_scan_each(self):
        """Tracing a step fires a trace event for every ``jnp`` call (each
        is a jit): 11,000 in ResNet50's step. Filing one looks at the tail
        of what was heard, never at all of it (a scan each cost 2 s of the
        cell's set-up), and the event that holds them all replaces them."""
        from deeplearning4j_tpu.perf import compile_watch as cw
        call = cw._CompilePhases()
        t0 = time.perf_counter()
        for _ in range(200_000):
            call.add_seconds("trace_s", 0.0)
        spent = time.perf_counter() - t0
        assert len(call._spans["trace_s"]) == 200_000
        assert spent < 5.0, spent           # a scan each: tens of minutes
        call.add_seconds("trace_s", spent + 1.0)
        assert call.totals() == {"trace_s": spent + 1.0}

    def test_one_listener_for_the_process_and_cache_hits_reads_it(self):
        import jax
        from jax._src import monitoring
        from deeplearning4j_tpu.perf import (cache_hits, compile_cache,
                                             compile_watch as cw)
        cw.install_listener()
        cw.install_listener()
        assert monitoring.get_event_duration_listeners().count(
            cw._on_duration) == 1
        assert monitoring.get_event_listeners().count(cw._on_event) == 1
        assert compile_cache.cache_hits is cw.cache_hits is cache_hits
        assert not hasattr(cw, "backend_compile_events")
        before = cache_hits()
        unwatched = cw.GLOBAL.compile_phases(cw.UNWATCHED)
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 1.25)
        # an event that is no compile's is not taken for one
        jax.monitoring.record_event(
            "/jax/compilation_cache/compile_requests_use_cache")
        assert cache_hits() == before + 1
        after = cw.GLOBAL.compile_phases(cw.UNWATCHED)
        assert after["backend_s"] == pytest.approx(
            unwatched.get("backend_s", 0.0) + 1.25)
        assert set(after) <= set(cw.PHASES.values()) | {
            "cache_hits", "cache_misses"}

    def test_the_scrape_has_each_program_s_phases(self):
        from deeplearning4j_tpu.perf.compile_watch import CompileWatch
        watch = CompileWatch("t")
        watch._record_phases("train", {"trace_s": 2.0, "lower_s": 0.5,
                                       "backend_s": 7.0, "cache_misses": 1})
        watch._record_phases("train", {"trace_s": 1.0})
        reg = obs.MetricsRegistry()
        obs.absorb_compile_watch(reg, watch)
        got = reg.as_dict()
        assert got["jit_compile_trace_s_train"]["value"] == 3.0
        assert got["jit_compile_backend_s_train"]["unit"] == "s"
        assert got["jit_compile_cache_misses_train"]["value"] == 1.0
        assert "jit_compile_trace_s_train" in obs.prometheus_text(reg)


class TestStepHistogram:
    @pytest.mark.parametrize("step_ms", [45.0, 310.0, 1109.0])
    def test_a_step_s_p95_is_read_within_a_bucket(self, step_ms):
        """``train_iteration_ms`` has a step's ladder (12% a bucket, 1 ms
        to 10 s): 100 turns of which the slowest ten take 1.3 times the
        usual read a p95 within 12% of the sample's; the other spans keep
        the default ladder, which cannot tell 45 ms from 50."""
        from deeplearning4j_tpu.obs.registry import (DEFAULT_BUCKETS_MS,
                                                     STEP_BUCKETS_MS)
        clock = iter(float(i) for i in range(10 ** 6))
        durations = [step_ms] * 90 + [1.3 * step_ms] * 10
        reg = obs.MetricsRegistry()
        tracer = obs.Tracer(enabled=True, registry=reg,
                            clock=lambda: next(clock))
        for dur in durations:
            for name in ("train.iteration", "train.step_host"):
                span = tracer.span(name)
                span.__enter__()
                span.__exit__(None, None, None)
        # an injected clock ticks whole seconds: observe the sample itself
        turn = reg.metric("train_iteration_ms")
        assert turn.bounds == STEP_BUCKETS_MS
        assert reg.metric("train_step_host_ms").bounds \
            == tuple(float(b) for b in DEFAULT_BUCKETS_MS)
        fresh = obs.MetricsRegistry().histogram(
            "train_iteration_ms", unit="ms", help="a turn",
            buckets=STEP_BUCKETS_MS)
        for dur in durations:
            fresh.observe(dur)
        want = float(np.quantile(durations, 0.95))
        assert abs(fresh.quantile(0.95) - want) <= 0.12 * want
        assert abs(fresh.quantile(0.50) - step_ms) <= 0.12 * step_ms
        ratios = [b / a for a, b in zip(STEP_BUCKETS_MS, STEP_BUCKETS_MS[1:])]
        assert max(ratios) < 1.1201 and STEP_BUCKETS_MS[0] == 1.0 \
            and STEP_BUCKETS_MS[-1] >= 10000.0

    def test_observe_files_a_value_under_the_first_bound_that_holds_it(self):
        h = obs.MetricsRegistry().histogram("h_ms", unit="ms", help="h",
                                            buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 4.0, 9.0):
            h.observe(v)
        assert h.bucket_counts() == [2, 1, 1, 1]


# ============================================================ serving + ckpt
class TestInstrumentedSurfaces:
    def test_parallel_inference_metrics(self):
        from deeplearning4j_tpu.parallel import ParallelInference
        reg = obs.get_registry()
        pad = reg.metric("serving_pad_waste_rows")
        before = pad.count if pad is not None else 0
        net = small_net()
        pi = ParallelInference(net, batch_limit=8, queue_timeout_ms=2)
        try:
            pi.output_batched(np.random.default_rng(0).standard_normal(
                (3, 4)).astype(np.float32))
            d = reg.as_dict()
            assert d["serving_requests"]["value"] >= 1
            assert d["serving_batches_dispatched"]["value"] >= 1
            assert "serving_hot_swap_swaps" in d
            assert reg.metric("serving_pad_waste_rows").count > before
            assert reg.metric("serving_batch_occupancy").count >= 1
        finally:
            pi.shutdown()

    def test_checkpoint_commit_and_restore_metrics(self, tmp_path):
        reg = obs.get_registry()
        net = small_net()
        cm = CheckpointManager(str(tmp_path / "ck"), async_write=False)
        commit_before = reg.metric("checkpoint_commit_ms")
        commit_before = commit_before.count if commit_before else 0
        bytes_before = reg.metric("checkpoint_bytes_written_total")
        bytes_before = bytes_before.value if bytes_before else 0
        cm.save(net)
        assert cm.restore_latest() is not None
        assert reg.metric("checkpoint_commit_ms").count == commit_before + 1
        assert reg.metric("checkpoint_bytes_written_total").value \
            > bytes_before
        assert reg.metric("checkpoint_restore_ms").count >= 1
        d = reg.as_dict()  # absorb callback pulls the manager's counters
        assert d["checkpoint_saves_committed"]["value"] >= 1


# ================================================================ exporters
class TestExporters:
    def test_prometheus_text_format(self):
        r = obs.MetricsRegistry()
        r.counter("a_total", unit="x", help="ca").inc(2)
        r.gauge("b", unit="y", help="gb").set(1.5)
        h = r.histogram("c_ms", unit="ms", help="hc", buckets=(1, 10))
        h.observe(0.5)
        h.observe(5)
        h.observe(50)
        txt = obs.prometheus_text(r)
        assert "# HELP a_total ca [unit: x]" in txt
        assert "# TYPE a_total counter" in txt and "\na_total 2\n" in txt
        assert "# TYPE b gauge" in txt
        assert 'c_ms_bucket{le="1"} 1' in txt
        assert 'c_ms_bucket{le="10"} 2' in txt
        assert 'c_ms_bucket{le="+Inf"} 3' in txt
        assert "c_ms_count 3" in txt
        # every sample line parses as `name{labels}? value`
        import re
        for line in txt.strip().splitlines():
            if line.startswith("#"):
                continue
            assert re.match(
                r'^[a-z_][a-z0-9_]*(\{le="[^"]+"\})? -?[0-9.e+natif]+$',
                line), line

    def test_prometheus_endpoint_scrape_parses(self):
        from deeplearning4j_tpu.storage import InMemoryStatsStorage
        from deeplearning4j_tpu.ui import UIServer
        srv = UIServer(port=0).attach(InMemoryStatsStorage())
        try:
            base = srv.address.rstrip("/")
            txt = urllib.request.urlopen(base + "/metrics",
                                         timeout=10).read().decode()
            assert "# TYPE jit_compiles gauge" in txt
            obs_json = json.loads(urllib.request.urlopen(
                base + "/api/obs", timeout=10).read())
            assert "jit_compiles" in obs_json
        finally:
            srv.stop()

    def test_event_log_roundtrip(self):
        store = ObjectStoreBackend()
        elog = obs.EventLog(store, name="ev.jsonl", flush_every=2)
        elog.emit({"kind": "span", "name": "a", "dur_ms": 1.0, "wall": 1.0})
        elog.emit({"kind": "event", "name": "b", "wall": 2.0})
        elog.flush()  # threshold flushes are async; sync before reading
        recs = obs.read_event_log(store, "ev.jsonl")
        assert [r["name"] for r in recs] == ["a", "b"]

    def test_tracer_to_event_log_pipeline(self):
        store = ObjectStoreBackend()
        elog = obs.EventLog(store, name="t.jsonl", flush_every=1)
        t = obs.Tracer(enabled=True)
        t.add_sink(elog)
        with t.span("x"):
            pass
        elog.flush()
        assert obs.read_event_log(store, "t.jsonl")[0]["name"] == "x"

    def test_dashboard_carries_obs_tiles(self):
        from deeplearning4j_tpu.ui import dashboard_html
        html = dashboard_html()
        assert "/api/obs" in html
        assert "elastic generation" in html
        assert "hot swaps" in html and "swap poll errors" in html

    def test_stats_listener_routes_to_registry(self):
        from deeplearning4j_tpu.storage import InMemoryStatsStorage
        from deeplearning4j_tpu.ui import StatsListener
        reg = obs.get_registry()
        net = small_net()
        net.set_listeners(StatsListener(InMemoryStatsStorage(),
                                        session_id="s", worker_id="w"))
        net.fit(toy_batches(1))
        assert reg.metric("train_score") is not None
        assert reg.metric("train_iteration") is not None


# ========================================================== flight recorder
class TestFlightRecorder:
    def test_ring_is_bounded_and_tail_summarized(self):
        fr = obs.FlightRecorder(capacity=3, worker_id="w1")
        for i in range(10):
            fr.event("e", i=i)
        tail = fr.tail()
        assert len(tail) == 3 and tail[-1]["attrs"] == {"i": 9}
        assert all("event e" in s for s in fr.tail_summary())

    def test_flush_on_fault_injector_kill(self):
        store = ObjectStoreBackend()
        obs.configure_tracer(enabled=True)
        obs.install_flight_recorder(store=store, worker_id="w2")
        net = small_net()
        net.set_listeners(FaultInjector(kill_at_step=2))
        with pytest.raises(SimulatedCrash):
            net.fit(toy_batches(4), num_epochs=3)
        dump = latest_dump(store)
        assert dump is not None and dump["worker_id"] == "w2"
        assert dump["reason"].startswith("fault injection")
        names = {e["name"] for e in dump["events"]}
        assert "train.step_host" in names  # the victim's last seconds

    def test_flush_on_watchdog_timeout(self):
        from deeplearning4j_tpu.parallel.watchdog import (
            CollectiveTimeoutError, CollectiveWatchdog)
        store = ObjectStoreBackend()
        obs.install_flight_recorder(store=store, worker_id="w3")
        with pytest.raises(CollectiveTimeoutError):
            CollectiveWatchdog(timeout_s=0.05).call(
                lambda: time.sleep(0.5), what="hung allgather")
        dump = latest_dump(store)
        assert dump is not None
        assert dump["reason"].startswith("watchdog timeout")
        assert any(e["name"] == "watchdog.timeout" for e in dump["events"])

    def test_train_until_attaches_in_process_tail(self, tmp_path):
        from deeplearning4j_tpu.checkpoint.resume import train_until
        obs.configure_tracer(enabled=True)
        obs.install_flight_recorder(worker_id="w4")  # no store: ring only
        net = small_net()
        net.set_listeners(FaultInjector(kill_at_step=2))
        cm = CheckpointManager(str(tmp_path / "ck"), save_every_n_steps=1,
                               async_write=False)
        summary = train_until(net, toy_batches(3), num_epochs=2,
                              checkpoint_manager=cm)
        assert summary.completed and summary.crashes
        tail = summary.crashes[0].flight_tail
        assert tail and any("train.step" in line for line in tail)


# ===================================================== obs_report CLI smoke
class TestObsReport:
    def _make_records(self):
        store = ObjectStoreBackend()
        elog = obs.EventLog(store, name="r.jsonl", flush_every=1)
        t = obs.Tracer(enabled=True)
        t.add_sink(elog)
        for i in range(4):
            with t.span("train.iteration", step=i):
                with t.span("train.data_wait"):
                    pass
                with t.span("train.step_host", items=16):
                    with t.span("train.dispatch", program="train"):
                        pass
        t.event("elastic.generation_start", generation=1, world=2)
        elog.flush()
        return obs.read_event_log(store, "r.jsonl")

    def test_render_report_sections(self):
        sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        records = self._make_records()
        dump = {"worker_id": "w9", "reason": "fault injection: kill",
                "time": 1.0, "events": records[-3:]}
        text = obs_report.render_report(records, [dump], top=5)
        assert "Per-step phase breakdown" in text
        for name in ("train.iteration", "train.data_wait",
                     "train.step_host", "train.dispatch"):
            assert name in text
        assert "Slowest spans" in text
        assert "Crash-ring tail — worker w9" in text
        assert "fault injection: kill" in text
        assert "elastic.generation_start" in text

    def test_cli_on_files(self, tmp_path):
        records = self._make_records()
        p = tmp_path / "run.jsonl"
        p.write_text("\n".join(json.dumps(r) for r in records) + "\n")
        dump_p = tmp_path / "flightrec-w9"
        dump_p.write_text(json.dumps(
            {"worker_id": "w9", "reason": "watchdog timeout: x",
             "time": 2.0, "events": records[:2]}))
        out = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "obs_report.py"),
             str(p), str(dump_p), "--top", "3"],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "observability report" in out.stdout
        assert "Crash-ring tail" in out.stdout


# ================================================= chaos post-mortem (E2E)
class TestChaosPostMortem:
    """ISSUE acceptance: SIGKILLed elastic worker → flight dump in storage
    whose tail spans reach the supervisor's CrashRecord; the run's
    Prometheus scrape + JSONL event log carry the per-step phase breakdown
    and the membership-transition pause."""

    def test_sigkill_postmortem_end_to_end(self, tmp_path):
        from deeplearning4j_tpu.checkpoint.supervisor import (
            train_until_process)
        store_dir = str(tmp_path / "store")
        os.makedirs(store_dir, exist_ok=True)
        worker_py = os.path.join(REPO_ROOT, "tests", "obs_worker.py")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO_ROOT)

        def argv_for(index, attempt):
            return [sys.executable, worker_py, store_dir, "w00",
                    str(attempt), "2", "2"]

        cm_reader = CheckpointManager(storage=LocalFSBackend(store_dir))
        summary = train_until_process(
            argv_for, num_workers=1, respawn_preempted=True,
            checkpoint_manager=cm_reader,
            attempt_timeout_s=240.0, overall_timeout_s=480.0,
            poll_s=0.1, env=env,
            log_dir=str(tmp_path / "logs"))
        assert summary.completed, summary

        # --- the SIGKILL left a crash record with the victim's last
        #     seconds, read back across the process boundary
        pre = [c for c in summary.crashes if c.error_type == "Preempted"]
        assert pre, summary.crashes
        tail = pre[0].flight_tail
        assert tail, "supervisor attached no flight tail"
        assert any("fault injection" in line for line in tail)
        assert any("train.step" in line for line in tail)

        # --- the flight dump itself is durable in the store
        backend = LocalFSBackend(store_dir)
        dumps = read_dumps(backend)
        assert dumps and dumps[-1]["worker_id"] == "w00"
        dump_names = {e["name"] for e in dumps[-1]["events"]}
        assert "train.step_host" in dump_names
        assert "elastic.generation_start" in dump_names

        # --- the JSONL event log carries the phase breakdown AND the
        #     membership-transition pause of the respawned generation
        records = []
        for name in backend.list(prefix="events-"):
            # span ids are a process's own, and every attempt writes a log
            # of its own: an id means something beside its log's name only
            records.extend(dict(r, log=name)
                           for r in obs.read_event_log(backend, name))
        names = {r["name"] for r in records}
        assert {"train.iteration", "train.data_wait", "train.step_host",
                "train.stage", "train.dispatch", "train.post",
                "train.listeners", "checkpoint.step_end"} <= names
        # the worker trains under a watchdog, on its worker thread: the
        # step still hangs under its turn
        by_id = {(r["log"], r["id"]): r for r in records if "id" in r}
        hosts = [r for r in records if r["name"] == "train.step_host"]
        assert hosts and all(
            by_id[h["log"], h["parent"]]["name"] == "train.iteration"
            and by_id[h["log"], h["parent"]]["thread"] != h["thread"]
            for h in hosts if (h["log"], h["parent"]) in by_id)
        pauses = [r for r in records
                  if r["name"] == "elastic.transition_pause"]
        assert pauses and pauses[0]["attrs"]["generation"] == 2
        assert pauses[0]["attrs"]["pause_ms"] > 0

        # --- the same run's Prometheus scrape (through the real /metrics
        #     endpoint inside the worker) has both as metrics
        scrapes = backend.list(prefix="prom-")
        assert scrapes, "worker saved no /metrics scrape"
        txt = backend.get(scrapes[-1]).decode()
        assert "train_step_host_ms_bucket" in txt
        assert "train_iteration_ms_count" in txt
        assert "\ntrain_steps_total " in txt
        assert "elastic_transition_pause_ms_count 1" in txt
        assert "\nelastic_generation 2" in txt

        # --- and the report CLI renders the whole post-mortem
        sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        text = obs_report.render_report(records, dumps)
        assert "Per-step phase breakdown" in text
        assert "Crash-ring tail — worker w00" in text

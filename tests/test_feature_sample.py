"""The listeners' feature sample (``model._last_features``) is taken only on
the iterations a registered listener says it reads it
(``TrainingListener.reads_features``): every fit path, with no listener, a
reading one, one that reads nothing, and a duck-typed one."""

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.convolutional import ConvolutionLayer
from deeplearning4j_tpu.nn.conf.layers import OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import (
    ConvolutionalIterationListener, TrainingListener, any_reads_features)
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.storage import InMemoryStatsStorage
from deeplearning4j_tpu.ui.stats import StatsListener


@pytest.fixture(autouse=True)
def _tracer_off():
    obs.configure_tracer(enabled=False)
    yield
    obs.configure_tracer(enabled=False)


# ------------------------------------------------------------------ models
def _builder(seed):
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Sgd(learning_rate=0.05)).weight_init("relu"))


def conv_net(seed=3):
    conf = (_builder(seed).list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                    convolution_mode="same",
                                    activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())
    return MultiLayerNetwork(conf).init()


def conv_graph(seed=3):
    from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (GraphBuilder(_builder(seed))
            .add_inputs("in")
            .add_layer("conv", ConvolutionLayer(
                n_out=4, kernel_size=(3, 3), convolution_mode="same",
                activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "conv")
            .set_outputs("out")
            .set_input_types(InputType.convolutional(8, 8, 1))
            .build())
    return ComputationGraph(conf).init()


def tbptt_net(seed=3):
    from deeplearning4j_tpu.nn.conf.recurrent import LSTM, RnnOutputLayer
    conf = (_builder(seed).weight_init("xavier").list()
            .layer(LSTM(n_out=6, activation="tanh"))
            .layer(RnnOutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.recurrent(4))
            .backprop_type("tbptt", fwd_length=5, back_length=5)
            .build())
    return MultiLayerNetwork(conf).init()


def image_batches(n, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.standard_normal((batch, 8, 8, 1)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(n)]


def sequence(batch=3, steps=15, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.random((batch, steps, 4)).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.integers(0, 3, (batch, steps))])


# --------------------------------------------------------------- fit paths
# Each path makes FOUR turns of its loop with ``listeners`` set and returns
# (model, told, rows): the iteration number each turn hands to
# ``iteration_done`` and the one-row sample that turn would keep.
def _mln_fit(listeners):
    net, data = conv_net(), image_batches(4)
    net.set_listeners(*listeners)
    net.fit(data)
    return net, [0, 1, 2, 3], [ds.features[:1] for ds in data]


def _fit_fused(listeners):
    net, data = conv_net(), image_batches(12)
    net.set_listeners(*listeners)
    for g in range(4):                  # groups of 3: told 2, 5, 8, 11
        net.fit_fused(data[3 * g:3 * g + 3])
    return net, [2, 5, 8, 11], [data[3 * g + 2].features[:1]
                                for g in range(4)]


def _fit_tbptt_fused(listeners):
    net = tbptt_net()
    net.set_listeners(*listeners)
    seqs = [sequence(seed=s) for s in range(4)]
    for x, y in seqs:                   # 3 windows a call: told 2, 5, 8, 11
        net.fit_tbptt_fused(x, y)
    return net, [2, 5, 8, 11], [x[:1] for x, _ in seqs]


def _tbptt_windows(listeners):
    net = tbptt_net()
    net.set_listeners(*listeners)
    x, y = sequence(steps=20)           # 4 windows of 5: one turn each
    net.fit(DataSet(x, y))
    return net, [0, 1, 2, 3], [x[:1, s:s + 5] for s in range(0, 20, 5)]


def _graph_fit(listeners):
    net, data = conv_graph(), image_batches(4)
    net.set_listeners(*listeners)
    net.fit(data)
    return net, [0, 1, 2, 3], [ds.features[:1] for ds in data]


def _parallel_wrapper(listeners):
    import jax
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    assert len(jax.devices()) == 8
    net, data = conv_net(), image_batches(4)
    net.set_listeners(*listeners)
    ParallelWrapper(net, mesh=make_mesh()).fit(data)   # batch sharded 8-way
    return net, [0, 1, 2, 3], [ds.features[:1] for ds in data]


def _cluster_local_shard(listeners):
    """ClusterTrainer.fit_local_shard in one process: the same one-batch
    method under the epoch loop's third caller."""
    from deeplearning4j_tpu.parallel import ClusterTrainer
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    net, data = conv_graph(), image_batches(4)
    net.set_listeners(*listeners)
    ClusterTrainer(net, mesh=make_mesh()).fit_local_shard(data)
    return net, [0, 1, 2, 3], [ds.features[:1] for ds in data]


PATHS = {"mln_fit": _mln_fit, "fit_fused": _fit_fused,
         "cluster_local_shard": _cluster_local_shard,
         "fit_tbptt_fused": _fit_tbptt_fused,
         "tbptt_windows": _tbptt_windows, "graph_fit": _graph_fit,
         "parallel_wrapper": _parallel_wrapper}


# --------------------------------------------------------------- listeners
class _Recording(ConvolutionalIterationListener):
    """The package's reader, keeping also what it drew from the model."""

    def __init__(self, frequency):
        super().__init__(InMemoryStatsStorage(), frequency=frequency,
                         session_id="s")
        self.drawn = []

    def _conv_activations(self, model):
        x = model._last_features
        self.drawn.append(None if x is None else [
            np.asarray(f) for f in (x if isinstance(x, list) else [x])])
        return super()._conv_activations(model)

    def stored(self):
        return [(r["iteration"], r["layers"]) for r in
                self.storage.get_all_updates("s", "ActivationsListener")]


class _ReadsEveryStep(TrainingListener):
    """Beside another listener this restores the parent's rule: the sample
    is taken on every step."""

    def reads_features(self, iteration):
        return True


class _Duck:
    """A listener from outside the package: no base class, no
    ``reads_features``."""

    def __init__(self):
        self.calls = []

    def iteration_done(self, model, iteration, epoch):
        self.calls.append(iteration)

    def on_epoch_start(self, model):
        pass

    def on_epoch_end(self, model):
        pass


def _observed(path, listeners):
    """Run ``path`` with the tracer on: (model, told, rows, the counter's
    movement, ``sampled`` of each ``train.post`` record)."""
    def samples():
        m = obs.get_registry().metric("train_feature_samples_total")
        return 0.0 if m is None else m.value
    sink = []
    before = samples()
    obs.configure_tracer(enabled=True)
    obs.get_tracer().add_sink(sink.append)
    try:
        net, told, rows = PATHS[path](listeners)
    finally:
        obs.get_tracer().remove_sink(sink.append)
        obs.configure_tracer(enabled=False)
    posts = [s["attrs"]["sampled"] for s in sink if s["name"] == "train.post"]
    return net, told, rows, samples() - before, posts


def _check_no_listener(path):
    net, _, _, moved, posts = _observed(path, [])
    assert net._last_features is None
    assert moved == 0 and posts == [0, 0, 0, 0]
    assert obs.get_registry().metric(
        "train_feature_samples_total") is not None   # reads 0, not absent


def _check_conv_every_second(path):
    lis = _Recording(frequency=2)
    net, told, rows, moved, posts = _observed(path, [lis])
    assert moved == 2 and posts == [1, 0, 1, 0]
    # the last turn was none of the listener's: nothing stale, nothing held
    assert net._last_features is None
    # what it drew is the row of ITS turn's batch, bit for bit
    assert len(lis.drawn) == 2
    for drawn, row in zip(lis.drawn, (rows[0], rows[2])):
        assert len(drawn) == 1 and np.array_equal(drawn[0], row)
    # ... and what it stored is what the parent's rule (a sample on every
    # step) has it store at the same seed
    old = _Recording(frequency=2)
    _, _, _, moved_old, posts_old = _observed(path, [old, _ReadsEveryStep()])
    assert moved_old == 4 and posts_old == [1, 1, 1, 1]
    assert all(np.array_equal(a[0], b[0])
               for a, b in zip(lis.drawn, old.drawn))
    assert lis.stored() == old.stored()
    conv = path not in ("fit_tbptt_fused", "tbptt_windows")
    assert [i for i, _ in lis.stored()] == (
        [told[0], told[2]] if conv else [])   # an LSTM has no (H, W, C)


def _check_stats_without_activations(path):
    lis = StatsListener(InMemoryStatsStorage(), session_id="s",
                        collect_activations=False)
    net, told, _, moved, posts = _observed(path, [lis])
    assert moved == 0 and posts == [0, 0, 0, 0]
    assert net._last_features is None
    recs = lis.storage.get_all_updates("s", "StatsListener")
    assert [r["iteration"] for r in recs] == told
    assert not any("activations" in r for r in recs)


def _check_stats_every_second(path):
    lis = StatsListener(InMemoryStatsStorage(), session_id="s", frequency=2)
    net, told, _, moved, posts = _observed(path, [lis])
    assert moved == 2 and posts == [1, 0, 1, 0]
    recs = lis.storage.get_all_updates("s", "StatsListener")
    assert [r["iteration"] for r in recs] == [told[0], told[2]]
    if hasattr(net, "feed_forward"):
        assert all(r["activations"] for r in recs)


def _check_duck_typed(path):
    lis = _Duck()
    net, told, _, moved, posts = _observed(path, [lis])
    assert lis.calls == told
    assert moved == 0 and posts == [0, 0, 0, 0]
    assert net._last_features is None


CASES = {"no_listener": _check_no_listener,
         "conv_every_second": _check_conv_every_second,
         "stats_without_activations": _check_stats_without_activations,
         "stats_every_second": _check_stats_every_second,
         "duck_typed": _check_duck_typed}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("path", sorted(PATHS))
def test_feature_sample_only_when_read(path, case):
    CASES[case](path)


def test_a_listener_is_asked_through_the_epoch_hook_proxy():
    """ParallelWrapper's proxy for non-standard fits forwards the question,
    and a proxied duck-typed listener still counts as reading nothing."""
    from deeplearning4j_tpu.parallel.trainer import _EpochHooksSuppressed
    reader = ConvolutionalIterationListener(InMemoryStatsStorage(),
                                            frequency=3)
    proxies = [_EpochHooksSuppressed(_Duck()), _EpochHooksSuppressed(reader)]
    assert [any_reads_features(proxies, i) for i in range(4)] == [
        True, False, False, True]
    assert not any_reads_features(proxies[:1], 0)
    assert not any_reads_features([TrainingListener()], 0)

"""The seam between ``nn/engine.py`` and its two fronts: the same
three-layer stack, built as a ``MultiLayerNetwork`` and as a single-chain
``ComputationGraph``, takes the same steps; and every caller of
``run_epochs`` gets its epoch hooks once an epoch."""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.checkpoint import CheckpointManager
from deeplearning4j_tpu.datasets.augment import ImageAugmentation
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.convolutional import ConvolutionLayer
from deeplearning4j_tpu.nn.conf.graph import GraphBuilder
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.engine import Network
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.optimize.updaters import Adam
from deeplearning4j_tpu.parallel.compress import (ThresholdCompression,
                                                  enable_grad_compression)


def _layers(penalties):
    return [("conv", ConvolutionLayer(n_out=3, kernel_size=(3, 3),
                                      activation="relu", **penalties)),
            ("dense", DenseLayer(n_out=8, activation="tanh", **penalties)),
            ("out", OutputLayer(n_out=3, activation="softmax",
                                loss="mcxent", **penalties))]


def _parent(seed):
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(0.01)).weight_init("xavier"))


def _stack(seed=11, algo="stochastic_gradient_descent", **penalties):
    b = _parent(seed).list().optimization_algo(algo)
    for _, layer in _layers(penalties):
        b = b.layer(layer)
    return MultiLayerNetwork(
        b.set_input_type(InputType.convolutional(6, 6, 2)).build()).init()


def _graph(seed=11, **penalties):
    g, below = GraphBuilder(_parent(seed)).add_inputs("in"), "in"
    for name, layer in _layers(penalties):
        g, below = g.add_layer(name, layer, below), name
    return ComputationGraph(
        g.set_outputs("out")
        .set_input_types(InputType.convolutional(6, 6, 2)).build()).init()


def _batches(n=3, rows=4):
    rng = np.random.default_rng(3)
    return [DataSet(rng.standard_normal((rows, 6, 6, 2)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, rows)])
            for _ in range(n)]


class _Losses:
    def __init__(self):
        self.seen = []

    def iteration_done(self, model, iteration, epoch):
        self.seen.append(float(model.score()))

    def on_epoch_start(self, model):
        pass

    def on_epoch_end(self, model):
        pass


def _plain(net, tmp_path):
    net.fit(_batches())


def _compressed(net, tmp_path):
    enable_grad_compression(net, ThresholdCompression())
    net.fit(_batches())
    assert net.compress_state is not None


def _augmented(net, tmp_path):
    # no crop, no flip: the one thing the fronts do differently with the
    # augmentation's key (a graph folds the input's index in) draws nothing
    net.set_augmentation(ImageAugmentation(mean=(0.1, -0.2), std=(1.5, 0.5)))
    net.fit(_batches())


def _resumed(net, tmp_path):
    """Two steps, a checkpoint at the second, and a restored network
    takes the third."""
    where = tmp_path / type(net).__name__
    cm = CheckpointManager(where, save_every_n_steps=2, async_write=False)
    net.fit(_batches()[:2], checkpoint_manager=cm)
    cm.close()
    restored = CheckpointManager(where).restore_latest()
    assert restored._resume_state.step == 2
    assert type(restored) is type(net)
    restored.set_listeners(*net.listeners)
    restored.fit(_batches())
    assert restored._resume_state is None
    return restored


CASES = {
    "l1_l2_weights_and_biases": (_plain, dict(l1=1e-3, l2=1e-2, l1_bias=1e-3,
                                              l2_bias=1e-2)),
    "threshold_compression": (_compressed, {}),
    "augmentation": (_augmented, {}),
    "checkpoint_resume_at_step_2": (_resumed, {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_stack_and_chain_graph_take_the_same_steps(case, tmp_path):
    run, penalties = CASES[case]
    ended = {}
    for build in (_stack, _graph):
        net, losses = build(**penalties), _Losses()
        assert isinstance(net, Network)
        net.set_listeners(losses)
        net = run(net, tmp_path) or net
        assert net.iteration == 3 and len(losses.seen) == 3
        ended[build] = (net, losses.seen)
    (stack, stack_losses), (graph, graph_losses) = ended[_stack], ended[_graph]
    np.testing.assert_allclose(stack_losses, graph_losses, rtol=1e-6)
    if penalties:
        assert float(stack._regularization(stack.params)) > 0
    for i, name in enumerate(n for n, _ in _layers({})):
        for leaf in stack.params[i]:
            np.testing.assert_allclose(
                np.asarray(stack.params[i][leaf]),
                np.asarray(graph.params[name][leaf]), rtol=1e-5, atol=1e-7,
                err_msg=f"{name}/{leaf}")
    # the fronts keep their containers: a list by index, a dict by name
    assert isinstance(stack.params, list) and isinstance(stack.opt_state, list)
    assert isinstance(graph.params, dict) and isinstance(graph.opt_state, dict)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(stack._rng)),
        np.asarray(jax.random.key_data(graph._rng)))


# ------------------------------------------------- run_epochs' four callers
class _Hooks:
    """Everything ``run_epochs`` tells a listener and a manager."""

    def __init__(self):
        self.calls = []

    def on_epoch_start(self, model):
        self.calls.append(("start", model.epoch))

    def on_epoch_end(self, model):
        self.calls.append(("end", model.epoch))

    def iteration_done(self, model, iteration, epoch):
        self.calls.append(("step", iteration))

    # the manager's side
    def step_end(self, model, batch_in_epoch):
        self.calls.append(("step_end", batch_in_epoch))

    def epoch_end(self, model):
        self.calls.append(("epoch_end", model.epoch))


def _one_device_mesh():
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    return make_mesh(dp=1, tp=1, devices=jax.devices()[:1])


def _call_stack(net, data, hooks):
    net.fit(data, num_epochs=2, checkpoint_manager=hooks)


def _solver_stack():
    return _stack(algo="lbfgs")


def _call_wrapper(net, data, hooks):
    from deeplearning4j_tpu.parallel import ParallelWrapper
    ParallelWrapper(net, mesh=_one_device_mesh()).fit(
        data, num_epochs=2, checkpoint_manager=hooks)


def _call_cluster(net, data, hooks):
    from deeplearning4j_tpu.parallel import ClusterTrainer
    ClusterTrainer(net, mesh=_one_device_mesh()).fit_local_shard(
        data, num_epochs=2, checkpoint_manager=hooks)


CALLERS = {"stack_fit": (_stack, _call_stack),
           "stack_solver_fit": (_solver_stack, _call_stack),
           "graph_fit": (_graph, _call_stack),
           "parallel_wrapper_fit": (_stack, _call_wrapper),
           "cluster_fit_local_shard": (_graph, _call_cluster)}


@pytest.mark.parametrize("caller", sorted(CALLERS))
def test_run_epochs_fires_the_epoch_hooks_once_an_epoch(caller):
    build, call = CALLERS[caller]
    net, hooks = build(), _Hooks()
    net.set_listeners(hooks)
    call(net, _batches(n=2), hooks)
    assert hooks.calls == [
        ("start", 0), ("step", 0), ("step_end", 1), ("step", 1),
        ("step_end", 2), ("end", 0), ("epoch_end", 1),
        ("start", 1), ("step", 2), ("step_end", 1), ("step", 3),
        ("step_end", 2), ("end", 1), ("epoch_end", 2)]
    assert (net.iteration, net.epoch) == (4, 2)

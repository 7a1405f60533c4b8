"""Parallelism tests on the 8-device virtual CPU mesh.

Mirrors the reference's run-distributed-without-a-cluster strategy
(ParallelWrapperTest on CPU, BaseSparkTest local[N] — SURVEY §4.3/§4.4):
same code paths as real multi-chip, worker count > physical devices.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration, InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Adam, Sgd
from deeplearning4j_tpu.datasets import IrisDataSetIterator
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.parallel import ParallelWrapper, ParallelInference, ClusterTrainer
from deeplearning4j_tpu.parallel.mesh import make_mesh, tp_shardings, DATA_AXIS, MODEL_AXIS
from deeplearning4j_tpu.parallel.ring_attention import (
    reference_attention, ring_self_attention,
)


def _net(seed=42, lr=0.05):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(learning_rate=lr)).weight_init("xavier").list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _iris_batch(n=144):
    ds = next(iter(IrisDataSetIterator(batch=150)))
    return DataSet(ds.features[:n], ds.labels[:n])


def test_mesh_construction(devices):
    mesh = make_mesh()
    assert mesh.shape[DATA_AXIS] == 8 and mesh.shape[MODEL_AXIS] == 1
    mesh2 = make_mesh(tp=2)
    assert mesh2.shape[DATA_AXIS] == 4 and mesh2.shape[MODEL_AXIS] == 2
    with pytest.raises(ValueError):
        make_mesh(dp=5, tp=2)


def test_data_parallel_matches_single_device(devices):
    """DP training over the mesh must produce the SAME params as single-device
    training on the same global batch (exact per-step averaging — the
    semantics ParallelWrapper.averagingFrequency=1 only approximates)."""
    ds = _iris_batch(144)
    single = _net(seed=7)
    single.fit(ds, num_epochs=5)

    dp = _net(seed=7)
    pw = ParallelWrapper(dp, mesh=make_mesh())
    pw.fit(ds, num_epochs=5)

    for a, b in zip(jax.tree_util.tree_leaves(single.params),
                    jax.tree_util.tree_leaves(dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6)


def test_data_parallel_batch_is_sharded(devices):
    ds = _iris_batch(144)
    net = _net()
    pw = ParallelWrapper(net, mesh=make_mesh())
    sharded = pw._shard_dataset(ds)
    assert len(sharded.features.sharding.device_set) == 8


def test_data_parallel_rejects_ragged_batch(devices):
    net = _net()
    pw = ParallelWrapper(net, mesh=make_mesh())
    with pytest.raises(ValueError, match="divisible"):
        pw.fit(_iris_batch(150))  # 150 % 8 != 0


def test_tensor_parallel_trains_and_shards_params(devices):
    conf = (NeuralNetConfiguration.builder()
            .seed(3).updater(Adam(0.02)).weight_init("xavier").list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=4, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    net = MultiLayerNetwork(conf).init()
    mesh = make_mesh(tp=2)  # dp=4, tp=2
    pw = ParallelWrapper(net, mesh=mesh, tensor_parallel=True)
    rng = np.random.default_rng(0)
    x = rng.random((16, 4), np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)]
    ds = DataSet(x, y)
    s0 = net.score_dataset(ds)
    pw.fit(ds, num_epochs=30)
    # the (4,32) kernel is actually sharded over 'model'
    spec = net.params[0]["W"].sharding.spec
    assert MODEL_AXIS in str(spec)
    with pw.mesh:
        assert net.score_dataset(pw._shard_dataset(ds)) < s0 * 0.7


def test_tp_matches_replicated_numerics(devices):
    """Tensor-parallel step == replicated step (GSPMD is semantics-preserving)."""
    rng = np.random.default_rng(1)
    x = rng.random((8, 4), np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
    ds = DataSet(x, y)
    a = _net(seed=11)
    b = _net(seed=11)
    ParallelWrapper(a, mesh=make_mesh()).fit(ds, num_epochs=3)
    ParallelWrapper(b, mesh=make_mesh(tp=4), tensor_parallel=True).fit(ds, num_epochs=3)
    for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                      jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=2e-4, atol=1e-5)


def test_parallel_inference_pads_ragged(devices):
    net = _net()
    pi = ParallelInference(net, mesh=make_mesh())
    x = np.random.default_rng(0).random((13, 4), np.float32)  # 13 % 8 != 0
    out = pi.output(x)
    assert out.shape == (13, 3)
    np.testing.assert_allclose(out, net.output(x), rtol=1e-5, atol=1e-6)


def test_parallel_inference_batched_queue(devices):
    net = _net()
    pi = ParallelInference(net, mesh=make_mesh())
    x = np.random.default_rng(1).random((4, 4), np.float32)
    out = pi.output_batched(x)
    assert out.shape == (4, 3)


def test_cluster_trainer_single_process(devices):
    ClusterTrainer.initialize(num_processes=1)  # no-op path
    net = _net(seed=13)
    ct = ClusterTrainer(net, mesh=make_mesh())
    ds = _iris_batch(144)
    s0 = net.score_dataset(ds)
    ct.fit_local_shard(ds, num_epochs=10)
    with ct.mesh:
        assert net.score_dataset(ct._shard_dataset(ds)) < s0


# ------------------------------------------------------------- ring attention
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(devices, causal):
    mesh = make_mesh()  # 8-way sequence sharding on 'data'
    rng = np.random.default_rng(5)
    b, h, t, d = 2, 3, 32, 8  # t=32 -> 4 timesteps per device
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    expected = reference_attention(q, k, v, causal=causal)
    got = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_differentiable(devices):
    mesh = make_mesh()
    rng = np.random.default_rng(6)
    b, h, t, d = 1, 2, 16, 4
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)

    def loss_ring(q, k, v):
        return jnp.sum(ring_self_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-5)


def test_ring_attention_jit_compiles(devices):
    mesh = make_mesh()
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((1, 1, 64, 8)), jnp.float32)

    @jax.jit
    def f(q):
        return ring_self_attention(q, q, q, mesh, causal=True)

    out = f(q)
    assert out.shape == (1, 1, 64, 8)


def test_flash_self_attention_fallback_matches_reference(devices):
    # CPU backend: routes to reference_attention — same numbers by definition,
    # but the wrapper's shape/scale contract is what this pins
    from deeplearning4j_tpu.parallel import flash_self_attention
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((2, 3, 16, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 3, 16, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 3, 16, 8)), jnp.float32)
    for causal in (False, True):
        got = flash_self_attention(q, k, v, causal=causal)
        want = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-2 if jax.default_backend() == "tpu"
                                   else 1e-6)


def test_collective_watchdog():
    """Watchdog (SURVEY §5): fast syncs pass through; an over-deadline wait
    raises a diagnostic CollectiveTimeoutError instead of hanging."""
    import time as _time

    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.watchdog import (
        CollectiveTimeoutError, CollectiveWatchdog,
    )

    wd = CollectiveWatchdog(timeout_s=30.0)
    x = jnp.arange(8.0) * 2
    assert wd.sync(x, what="small add") is x  # completes well in deadline

    msgs = []
    wd2 = CollectiveWatchdog(timeout_s=0.2, on_timeout=msgs.append)
    with pytest.raises(CollectiveTimeoutError) as ei:
        with wd2.guard("deliberately slow host section"):
            _time.sleep(0.6)
    assert "did not complete" in str(ei.value)
    assert msgs and "deliberately slow" in msgs[0]


def test_collective_watchdog_guard_paths():
    """guard()'s full contract: an in-time body passes untouched (timer
    cancelled, no callback); an expired body raises on exit EVEN IF it
    eventually completed (the hang was real — finishing late must not mask
    it); a body that raises its own error keeps that error (the guard
    never shadows a real exception with its timeout)."""
    import time as _time

    from deeplearning4j_tpu.parallel.watchdog import (
        CollectiveTimeoutError, CollectiveWatchdog,
    )

    # in-time: no raise, no on_timeout, value side effects intact
    msgs = []
    wd = CollectiveWatchdog(timeout_s=5.0, on_timeout=msgs.append)
    ran = []
    with wd.guard("fast section"):
        ran.append(1)
    assert ran == [1] and msgs == []

    # expired-but-completed: the timer fired mid-body; the body then
    # finished fine — exit must STILL raise (and must have delivered the
    # diagnostic callback at fire time, not exit time)
    wd2 = CollectiveWatchdog(timeout_s=0.15, on_timeout=msgs.append)
    with pytest.raises(CollectiveTimeoutError) as ei:
        with wd2.guard("slow but eventually fine"):
            _time.sleep(0.5)
            ran.append(2)
    assert ran == [1, 2]  # body DID complete; the guard raised anyway
    assert "slow but eventually fine" in str(ei.value)
    assert len(msgs) == 1 and "slow but eventually fine" in msgs[0]

    # body exception wins over a fired timer: never mask the real error
    with pytest.raises(ValueError, match="real failure"):
        with wd2.guard("failing section"):
            _time.sleep(0.5)
            raise ValueError("real failure")


def test_collective_watchdog_call_on_timeout_delivery():
    """call() paths: on_timeout fires with the diagnostic on expiry; a
    worker-side exception is re-raised on the caller thread; the in-time
    path returns the value with no callback."""
    import time as _time

    from deeplearning4j_tpu.parallel.watchdog import (
        CollectiveTimeoutError, CollectiveWatchdog,
    )

    msgs = []
    wd = CollectiveWatchdog(timeout_s=0.15, on_timeout=msgs.append)
    with pytest.raises(CollectiveTimeoutError):
        wd.call(lambda: _time.sleep(0.6), what="stuck dispatch")
    assert msgs and "stuck dispatch" in msgs[0]
    assert "process" in msgs[0]  # diagnostic includes process/device info

    wd_ok = CollectiveWatchdog(timeout_s=5.0, on_timeout=msgs.append)
    assert wd_ok.call(lambda: 41 + 1, what="quick") == 42

    with pytest.raises(KeyError):  # body errors surface, not timeouts
        wd_ok.call(lambda: {}[0], what="raising body")
    assert len(msgs) == 1  # no extra callbacks from the healthy calls


def test_cluster_trainer_watchdog_smoke():
    """fit_local_shard with an armed watchdog trains normally when healthy."""
    net = _net(seed=44)
    trainer = ClusterTrainer(net)
    ds = _iris_batch(48)
    trainer.fit_local_shard(ds, num_epochs=2, collective_timeout_s=60.0,
                            watchdog_every=1)
    assert net.score() is not None


def test_parallel_inference_dynamic_batching():
    """BatchedInferenceObservable contract (reference
    ParallelInference.java:97-134): concurrent submits coalesce into shared
    device dispatches, every caller gets ITS slice, latency stays bounded."""
    import threading
    import time as _time

    net = _net(seed=9)
    ds = _iris_batch(96)
    net.fit(ds)
    pi = ParallelInference(net, batch_limit=16, queue_timeout_ms=30)

    want = np.asarray(pi.output(ds.features))
    n_threads, per = 12, 4
    outs = [None] * n_threads
    lat = [0.0] * n_threads

    def worker(i):
        x = ds.features[i * per:(i + 1) * per]
        t0 = _time.perf_counter()
        outs[i] = pi.output_batched(x)
        lat[i] = _time.perf_counter() - t0

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    for i in range(n_threads):
        np.testing.assert_allclose(outs[i], want[i * per:(i + 1) * per],
                                   rtol=1e-5, atol=1e-6)
    assert pi.requests_served == n_threads
    # coalescing happened: fewer dispatches than requests
    assert pi.batches_dispatched < n_threads, pi.batch_sizes
    assert max(pi.batch_sizes) > 1
    assert max(lat) < 20.0  # bounded latency even under contention
    pi.shutdown()

    # observable API: async submit, late get
    obs = pi.submit(ds.features[:3])
    out = obs.get(timeout=10)
    assert out.shape == (3, 3) and obs.is_done()
    pi.shutdown()

    # sequential mode parity
    pi_seq = ParallelInference(net, inference_mode="sequential")
    np.testing.assert_allclose(pi_seq.output_batched(ds.features[:5]),
                               want[:5], rtol=1e-5, atol=1e-6)
    assert pi_seq.batches_dispatched == 0  # no worker involved


def _stalled_inference(seed=21, queue_depth=3, queue_put_timeout_ms=30):
    """A ParallelInference whose model forward is HELD at a gate — the
    stalled-worker scenario the bounded queue exists for. Returns
    (pi, gate, entered): set `gate` to release, wait `entered` to know
    the worker is wedged inside a dispatch."""
    import threading as _threading

    net = _net(seed=seed)
    gate = _threading.Event()
    entered = _threading.Event()
    orig_output = net.output

    def gated_output(arr):
        entered.set()
        assert gate.wait(30), "test gate leaked shut"
        return orig_output(arr)

    net.output = gated_output  # instance attribute shadows the method
    pi = ParallelInference(net, queue_depth=queue_depth,
                           queue_put_timeout_ms=queue_put_timeout_ms)
    return pi, gate, entered


def test_parallel_inference_bounded_queue_sheds_when_stalled():
    """Regression for the unbounded-queue bug: a stalled worker cannot
    grow the queue past queue_depth — the overflow submit raises typed
    QueueFullError within the put timeout (block-with-timeout semantics),
    and the rejection is surfaced in stats()."""
    import time as _time

    from deeplearning4j_tpu.parallel import QueueFullError

    pi, gate, entered = _stalled_inference(queue_depth=3)
    try:
        x = np.zeros((1, 4), np.float32)
        first = pi.submit(x)
        assert entered.wait(10)  # worker is wedged inside the dispatch
        queued = [pi.submit(x) for _ in range(3)]  # exactly fills the bound
        t0 = _time.perf_counter()
        with pytest.raises(QueueFullError, match="queue_depth=3"):
            pi.submit(x)
        assert _time.perf_counter() - t0 < 5.0  # shed fast, not hung
        assert pi._q.qsize() == 3  # the queue never grew past its bound
        st = pi.stats()
        assert st["queue"] == {"depth": 3, "size": 3,
                               "rejected": 1, "expired": 0}
        gate.set()  # drain: everything accepted is served
        assert first.get(timeout=30).shape == (1, 3)
        for obs in queued:
            assert obs.get(timeout=30).shape == (1, 3)
        assert pi.stats()["queue"]["size"] == 0
    finally:
        gate.set()
        pi.shutdown()


def test_parallel_inference_deadline_evicted_before_dispatch():
    """submit(deadline=...) contract: a request whose deadline expires
    while queued behind a stalled batch is failed at batch formation
    (DeadlineExpiredError) and never dispatched."""
    import time as _time

    from deeplearning4j_tpu.parallel import DeadlineExpiredError

    pi, gate, entered = _stalled_inference(queue_depth=8)
    try:
        x = np.zeros((2, 4), np.float32)
        patient = pi.submit(x)
        assert entered.wait(10)
        doomed = pi.submit(x, deadline=_time.monotonic() + 0.05)
        _time.sleep(0.25)  # the deadline passes while it sits queued
        gate.set()
        assert patient.get(timeout=30).shape == (2, 3)
        with pytest.raises(DeadlineExpiredError):
            doomed.get(timeout=30)
        st = pi.stats()
        assert st["queue"]["expired"] == 1
        assert st["batches_dispatched"] == 1  # the doomed one never ran
    finally:
        gate.set()
        pi.shutdown()


# --------------------------------------------------- all-to-all (Ulysses) SP
@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(devices, causal):
    from deeplearning4j_tpu.parallel import ulysses_self_attention

    mesh = make_mesh()  # 8-way sequence sharding on 'data'
    rng = np.random.default_rng(6)
    b, h, t, d = 2, 8, 32, 8  # h=8 heads over 8 devices, t=32 sharded
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    expected = reference_attention(q, k, v, causal=causal)
    got = ulysses_self_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_matches_ring_and_validates_heads(devices):
    from deeplearning4j_tpu.parallel import ulysses_self_attention
    from deeplearning4j_tpu.parallel.ring_attention import ring_self_attention

    mesh = make_mesh()
    rng = np.random.default_rng(7)
    b, h, t, d = 1, 16, 64, 4
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    ring = jax.jit(lambda q, k, v: ring_self_attention(
        q, k, v, mesh, causal=True))(q, k, v)
    uly = jax.jit(lambda q, k, v: ulysses_self_attention(
        q, k, v, mesh, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(uly), np.asarray(ring),
                               rtol=2e-4, atol=2e-5)
    # differentiable under jit
    import jax as _jax

    @_jax.jit
    def loss(qq):
        return jnp.sum(ulysses_self_attention(qq, k, v, mesh) ** 2)
    g = _jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
    # the classic constraint: heads must divide the axis size
    with pytest.raises(ValueError, match="divisible"):
        ulysses_self_attention(q[:, :3], k[:, :3], v[:, :3], mesh)

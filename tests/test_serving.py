"""serving/ tier: overload-safe HTTP serving over ParallelInference.

Covers the tentpole contract end to end: continuous batching over HTTP,
bounded admission with 429 shedding, per-request deadlines evicted before
dispatch (504), circuit breaker fast-503s with half-open probing,
graceful drain (zero dropped in-flight), warmup-gated readiness, and the
chaos acceptance test — burst > capacity with a checkpoint hot-swap and
drain riding through it, all asserted against a live /metrics scrape.

HTTP goes over loopback sockets like the kNN/UI server tests; every
server is closed in finally blocks so a failing assertion can't leak a
listener into later tests.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.datasets import IrisDataSetIterator
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.serving import (CircuitBreaker, ModelEndpoint,
                                        ModelServer)


def _net(seed=42, n_out=3, n_in=4):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(learning_rate=0.05))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=n_out, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


class GatedNet:
    """Delegating model wrapper whose forward can be HELD at a gate,
    slowed, or scripted to fail — the chaos lever for overload tests.
    Param/state access delegates so checkpoint hot-swap works through it."""

    def __init__(self, inner, delay_s: float = 0.0):
        self._inner = inner
        self.delay_s = delay_s
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()  # a dispatch reached the gate
        self.fail_next = 0
        self.dispatches = 0
        self._lock = threading.Lock()

    @property
    def params(self):
        return self._inner.params

    @params.setter
    def params(self, v):
        self._inner.params = v

    @property
    def state(self):
        return self._inner.state

    @state.setter
    def state(self, v):
        self._inner.state = v

    def init(self):
        self._inner.init()
        return self

    def output(self, arr):
        self.entered.set()
        assert self.gate.wait(30), "test gate leaked shut"
        if self.delay_s:
            time.sleep(self.delay_s)
        with self._lock:
            self.dispatches += 1
            if self.fail_next > 0:
                self.fail_next -= 1
                raise RuntimeError("scripted model fault")
        return self._inner.output(arr)

    def __getattr__(self, name):  # _restored_from, compile_watch, ...
        return getattr(self.__dict__["_inner"], name)


def _post(base, model, inputs, deadline_ms=None, timeout=30):
    """POST a predict; returns (status, parsed body, headers)."""
    body = {"inputs": np.asarray(inputs).tolist()}
    if deadline_ms is not None:
        body["deadline_ms"] = deadline_ms
    req = urllib.request.Request(
        f"{base}/v1/models/{model}:predict", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(base, path, timeout=10):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# ---------------------------------------------------------------- routing
def test_predict_roundtrip_and_multi_model_routing(devices):
    """Several nets behind one server, each with its own
    ParallelInference; predictions match the models' own output()."""
    iris = _net(seed=7, n_out=3, n_in=4)
    wide = _net(seed=8, n_out=5, n_in=6)
    srv = ModelServer({"iris": iris}).start(warmup=False)
    srv.add_model("wide", wide)
    try:
        base = srv.address
        xi = np.random.default_rng(0).random((5, 4)).astype(np.float32)
        xw = np.random.default_rng(1).random((3, 6)).astype(np.float32)
        code, out, _ = _post(base, "iris", xi)
        assert code == 200 and out["model"] == "iris"
        np.testing.assert_allclose(np.asarray(out["outputs"], np.float32),
                                   np.asarray(iris.output(xi)),
                                   rtol=1e-4, atol=1e-5)
        code, out, _ = _post(base, "wide", xw)
        assert code == 200
        assert np.asarray(out["outputs"]).shape == (3, 5)
        np.testing.assert_allclose(np.asarray(out["outputs"], np.float32),
                                   np.asarray(wide.output(xw)),
                                   rtol=1e-4, atol=1e-5)
        # model listing + detail
        code, body = _get(base, "/v1/models")
        listing = json.loads(body)["models"]
        assert set(listing) == {"iris", "wide"}
        assert listing["iris"]["breaker"]["state"] == "closed"
        code, body = _get(base, "/v1/models/wide")
        assert code == 200 and json.loads(body)["model"] == "wide"
        # unknown model and malformed bodies are structured errors
        code, err, _ = _post(base, "nope", xi)
        assert code == 404 and err["reason"] == "unknown_model"
        code, err, _ = _post(base, "iris", np.zeros((2, 9)))
        assert code == 400 and "shape" in err["error"]
        code, body = _get(base, "/healthz")
        assert code == 200 and json.loads(body)["ok"] is True
    finally:
        srv.stop(drain=False)


def test_malformed_and_oversized_bodies(devices):
    srv = ModelServer({"m": _net()}, max_body_bytes=512).start(warmup=False)
    try:
        base = srv.address
        req = urllib.request.Request(f"{base}/v1/models/m:predict",
                                     data=b"this is not json")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
        assert "error" in json.loads(ei.value.read())
        big = json.dumps({"inputs": [[0.0] * 4] * 1000}).encode()
        req = urllib.request.Request(f"{base}/v1/models/m:predict", data=big)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 413
        assert json.loads(ei.value.read())["reason"] == "body_too_large"
        # no leading batch axis
        code, err, _ = _post(base, "m", np.zeros((4,)))
        assert code == 400 and err["reason"] == "bad_request"
    finally:
        srv.stop(drain=False)


# -------------------------------------------------------------- readiness
def test_readyz_gates_on_warmup_ladder(devices):
    """/readyz stays 503 until the endpoint's bucket ladder compiled — no
    live request ever pays a multi-second XLA compile."""
    srv = ModelServer()
    ep = srv.add_model("m", _net(),
                       warmup_example=np.zeros((1, 4), np.float32))
    srv.start(warmup=False)  # deliberately not warmed yet
    try:
        base = srv.address
        code, body = _get(base, "/readyz")
        assert code == 503
        assert any("warmup" in r for r in json.loads(body)["reasons"])
        srv.warmup()
        assert ep.warmed and ep.pi.stats()["warmed_buckets"]
        code, body = _get(base, "/readyz")
        assert code == 200 and json.loads(body)["ready"] is True
        # warmed traffic compiles nothing new (request fits the ladder)
        st0 = ep.pi.stats()
        code, _, _ = _post(base, "m", np.zeros((2, 4), np.float32))
        assert code == 200
        st = ep.pi.stats()
        assert st["model_compiles"] == st0["model_compiles"]
        assert st["unwarmed_dispatches"] == 0
    finally:
        srv.stop(drain=False)


def test_wrong_shape_never_reaches_dispatch(devices):
    """A wrong-shaped request is a CLIENT error: 400 from the feature
    guard, zero model dispatches, nothing counted against the breaker."""
    gated = GatedNet(_net())
    srv = ModelServer()
    ep = srv.add_model("m", gated,
                       warmup_example=np.zeros((1, 4), np.float32))
    srv.start(warmup=False)
    try:
        code, err, _ = _post(srv.address, "m", np.zeros((2, 7)))
        assert code == 400 and "shape" in err["error"]
        assert gated.dispatches == 0
        assert ep.breaker.as_dict()["window_failures"] == 0
    finally:
        srv.stop(drain=False)


# -------------------------------------------------- admission / shedding
def test_queue_full_sheds_429_with_retry_after(devices):
    """Over capacity ⇒ immediate 429 + Retry-After while the queue stays
    at its bound; releasing the stall serves everything accepted."""
    gated = GatedNet(_net())
    srv = ModelServer()
    ep = srv.add_model("m", gated, queue_depth=2, batch_limit=1,
                       default_deadline_ms=30_000)
    srv.start(warmup=False)
    gated.gate.clear()  # stall the worker inside dispatch
    results = []
    lock = threading.Lock()
    try:
        base = srv.address
        x = np.zeros((1, 4), np.float32)

        def client():
            r = _post(base, "m", x)
            with lock:
                results.append(r)

        threads = [threading.Thread(target=client) for _ in range(8)]
        # first client gets dequeued into the stalled dispatch; then fill
        threads[0].start()
        assert gated.entered.wait(10)
        for t in threads[1:]:
            t.start()
        # the shed answers arrive while the worker is still stalled
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with lock:
                if sum(1 for c, _, _ in results if c == 429) >= 5:
                    break
            time.sleep(0.01)
        assert ep.pi._q.qsize() <= 2  # the bound held during the burst
        gated.gate.set()
        for t in threads:
            t.join(timeout=30)
        codes = sorted(c for c, _, _ in results)
        assert codes.count(429) == 5, codes  # 1 in dispatch + 2 queued
        assert codes.count(200) == 3, codes
        shed = next(r for r in results if r[0] == 429)
        assert shed[1]["reason"] == "shed"
        assert int(shed[2]["Retry-After"]) >= 1
        assert ep.pi.stats()["queue"]["rejected"] == 5
    finally:
        gated.gate.set()
        srv.stop(drain=False)


# --------------------------------------------------------------- deadlines
def test_expired_deadline_evicted_before_dispatch_504(devices):
    """A request whose deadline passes while it waits behind a slow batch
    is answered 504 at batch formation and never occupies a device batch
    slot; the patient request ahead of it completes normally."""
    gated = GatedNet(_net())
    srv = ModelServer()
    ep = srv.add_model("m", gated)
    srv.start(warmup=False)
    gated.gate.clear()  # the in-flight batch is held on the "device"
    done1, done2 = [], []
    try:
        base = srv.address
        x = np.zeros((1, 4), np.float32)
        t1 = threading.Thread(target=lambda: done1.append(
            _post(base, "m", x, deadline_ms=30_000)))
        t1.start()
        # wait until the worker PULLED t1 into the stalled dispatch, so
        # t2 lands in the queue behind it rather than in the same batch
        assert gated.entered.wait(10)
        t2 = threading.Thread(target=lambda: done2.append(
            _post(base, "m", x, deadline_ms=150)))
        t2.start()
        time.sleep(0.4)  # t2's deadline expires while it sits queued
        gated.gate.set()
        t1.join(timeout=30)
        t2.join(timeout=30)
        assert done1[0][0] == 200
        code, err, _ = done2[0]
        assert code == 504 and err["reason"] == "deadline_expired"
        assert gated.dispatches == 1  # t1's batch only: t2 never dispatched
        assert ep.pi.stats()["queue"]["expired"] == 1
    finally:
        gated.gate.set()
        srv.stop(drain=False)


def test_late_completion_is_504_not_stale_200(devices):
    """A request already ON the device when its deadline passes must not
    come back as a late 200 — a 200 always means the deadline was met."""
    gated = GatedNet(_net())
    srv = ModelServer({"m": gated}).start(warmup=False)
    gated.gate.clear()
    done = []
    try:
        t = threading.Thread(target=lambda: done.append(
            _post(srv.address, "m", np.zeros((1, 4), np.float32),
                  deadline_ms=100)))
        t.start()
        assert gated.entered.wait(10)  # request is IN the held dispatch
        time.sleep(0.4)  # deadline passes mid-dispatch
        gated.gate.set()
        t.join(timeout=30)
        code, err, _ = done[0]
        assert code == 504 and err["reason"] == "deadline_expired"
        assert "after the deadline" in err["error"]
    finally:
        gated.gate.set()
        srv.stop(drain=False)


# ----------------------------------------------------------- circuit breaker
def test_breaker_unit_state_machine():
    now = [0.0]
    br = CircuitBreaker(failure_threshold=3, window_s=10.0, cooldown_s=5.0,
                        probe_timeout_s=20.0, clock=lambda: now[0])
    assert br.state == "closed" and br.allow()
    for _ in range(3):
        br.record_failure()
    assert br.state == "open"
    assert not br.allow() and br.rejections == 1
    assert 0 < br.retry_after() <= 5.0
    now[0] = 5.1  # cooldown over: exactly one half-open probe
    assert br.allow() and br.state == "half_open"
    assert not br.allow()  # second caller rejected while probe in flight
    br.record_failure()  # probe failed: full cooldown again
    assert br.state == "open" and br.opens == 2
    now[0] = 10.3
    assert br.allow()
    br.record_success()  # probe succeeded: closed, window reset
    assert br.state == "closed" and br.as_dict()["window_failures"] == 0
    # an abandoned probe (caller died) is reclaimed after probe_timeout_s
    for _ in range(3):
        br.record_failure()
    now[0] = 20.0
    assert br.allow()  # the probe that will be abandoned
    assert not br.allow()
    now[0] = 41.0  # probe_timeout_s elapsed: a new probe may claim
    assert br.allow()


def test_breaker_opens_on_error_burst_and_recovers(devices):
    """A model-fault burst opens the breaker (fast 503 + Retry-After, no
    dispatch), and a successful half-open probe closes it again."""
    now = [0.0]
    breaker = CircuitBreaker(failure_threshold=3, window_s=30.0,
                             cooldown_s=5.0, clock=lambda: now[0])
    gated = GatedNet(_net())
    srv = ModelServer()
    srv.add_model("m", gated, breaker=breaker)
    srv.start(warmup=False)
    try:
        base = srv.address
        x = np.zeros((2, 4), np.float32)
        gated.fail_next = 3
        for _ in range(3):
            code, err, _ = _post(base, "m", x)
            assert code == 500 and err["reason"] == "dispatch_failed"
        assert breaker.state == "open"
        d0 = gated.dispatches
        code, err, hdrs = _post(base, "m", x)
        assert code == 503 and err["reason"] == "breaker_open"
        assert int(hdrs["Retry-After"]) >= 1
        assert gated.dispatches == d0  # fast fail: nothing dispatched
        now[0] = 6.0  # cooldown elapsed: next request is the probe
        code, out, _ = _post(base, "m", x)
        assert code == 200
        assert breaker.state == "closed"
        code, _, _ = _post(base, "m", x)
        assert code == 200
    finally:
        srv.stop(drain=False)


# ----------------------------------------------------------------- drain
def test_graceful_drain_completes_inflight_and_sheds_new(devices):
    """drain(): every in-flight request completes (zero dropped), new
    arrivals are shed with 503, undrain() restores service."""
    gated = GatedNet(_net())
    srv = ModelServer({"m": gated}).start(warmup=False)
    results = []
    lock = threading.Lock()
    gated.gate.clear()  # all six get stuck inside the server
    try:
        base = srv.address
        x = np.zeros((1, 4), np.float32)

        def client():
            r = _post(base, "m", x, deadline_ms=30_000)
            with lock:
                results.append(r)

        threads = [threading.Thread(target=client) for _ in range(6)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 10
        while srv.inflight < 6 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert srv.inflight == 6
        # drain blocks until in-flight hits zero: run it alongside
        drained = []
        dr = threading.Thread(
            target=lambda: drained.append(srv.drain(timeout_s=30)))
        dr.start()
        deadline = time.monotonic() + 10
        while not srv.draining and time.monotonic() < deadline:
            time.sleep(0.005)
        code, err, _ = _post(base, "m", x)  # a new arrival is shed
        assert code == 503 and err["reason"] == "draining"
        code, body = _get(base, "/readyz")
        assert code == 503 and "draining" in json.loads(body)["reasons"]
        gated.gate.set()  # let the in-flight six complete
        dr.join(timeout=30)
        assert drained == [True]
        for t in threads:
            t.join(timeout=30)
        assert [c for c, _, _ in results].count(200) == 6  # zero dropped
        srv.undrain()
        code, _, _ = _post(base, "m", x)
        assert code == 200
    finally:
        gated.gate.set()
        srv.stop(drain=False)


def test_slow_client_does_not_wedge_the_server(devices):
    """A client that stalls mid-request holds one handler thread at most;
    other clients keep being served (threaded server + socket timeout)."""
    srv = ModelServer({"m": _net()}).start(warmup=False)
    sock = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
    try:
        sock.sendall(b"POST /v1/models/m:predict HTTP/1.1\r\n"
                     b"Content-Length: 100000\r\n\r\n")  # ...then stall
        time.sleep(0.1)
        code, _, _ = _post(srv.address, "m", np.zeros((2, 4), np.float32))
        assert code == 200  # served while the slow client dangles
    finally:
        sock.close()
        srv.stop(drain=False)


# --------------------------------------------------------- wire format
def test_binary_wire_format_shrinks_the_request_body():
    """What serving/wire.py promises of its encodings, from the shapes
    alone: over a mix of request sizes a raw float32 body is a third of
    the JSON float lists or less, an int8 body a tenth."""
    from deeplearning4j_tpu.serving.wire import decode_array, encode_array
    rng = np.random.default_rng(77)
    as_json = as_f32 = as_int8 = 0
    for rows in (1, 2, 4, 8):
        x = rng.standard_normal((rows, 784)).astype(np.float32)
        as_json += len(json.dumps({"inputs": x.tolist()}))
        body = encode_array(x)
        np.testing.assert_array_equal(decode_array(body), x)
        as_f32 += len(json.dumps(body))
        as_int8 += len(json.dumps(encode_array(x.astype(np.int8))))
    assert as_json >= 3.0 * as_f32
    assert as_json >= 10.0 * as_int8


# ------------------------------------------------------------- metrics
def test_metrics_scrape_carries_serving_instruments(devices):
    from deeplearning4j_tpu.obs.registry import get_registry
    srv = ModelServer({"m": _net()}).start(warmup=False)
    try:
        base = srv.address
        for _ in range(3):
            code, _, _ = _post(base, "m", np.zeros((2, 4), np.float32))
            assert code == 200
        code, body = _get(base, "/metrics")
        assert code == 200
        text = body.decode()
        for name in ("serving_http_requests", "serving_requests_shed",
                     "serving_requests_expired", "serving_breaker_rejected",
                     "serving_request_ms_bucket", "serving_request_ms_count",
                     "serving_inflight_requests", "serving_models",
                     "serving_queue_bound", "serving_ready"):
            assert name in text, f"{name} missing from /metrics"
        hist = get_registry().metric("serving_request_ms")
        assert hist.count >= 3 and hist.quantile(0.5) > 0
    finally:
        srv.stop(drain=False)


# ------------------------------------------------------- chaos acceptance
class TestChaosAcceptance:
    """The ISSUE's acceptance scenario: a burst at far above sustainable
    offered load, a checkpoint hot-swap and a graceful drain all riding
    through it — shedding bounded, deadlines honored, zero dropped."""

    def _serving_stack(self, store, gated_delay_s):
        from deeplearning4j_tpu.checkpoint import (CheckpointManager,
                                                   ObjectStoreBackend)
        ds = next(iter(IrisDataSetIterator(batch=150)))
        batches = [DataSet(ds.features[i * 48:(i + 1) * 48],
                           ds.labels[i * 48:(i + 1) * 48]) for i in range(3)]
        trainer_cm = CheckpointManager(storage=ObjectStoreBackend(store),
                                       async_write=False)
        trainer_net = _net(seed=7)
        trainer_net.fit(batches, num_epochs=1)
        trainer_cm.save(trainer_net)
        serve_cm = CheckpointManager(storage=ObjectStoreBackend(store))
        served = serve_cm.restore_latest(load_updater=False)
        gated = GatedNet(served, delay_s=gated_delay_s)
        return batches, trainer_cm, trainer_net, serve_cm, gated

    def test_burst_swap_drain_with_metrics(self, devices):
        from deeplearning4j_tpu.obs.registry import get_registry
        store = {}
        batches, trainer_cm, trainer_net, serve_cm, gated = \
            self._serving_stack(store, gated_delay_s=0.0)
        srv = ModelServer()
        ep = srv.add_model("iris", gated, queue_depth=8, batch_limit=8,
                           warmup_example=np.zeros((1, 4), np.float32),
                           default_deadline_ms=30_000)
        ep.pi.start_hot_swap(serve_cm)  # manual polls: deterministic
        srv.start(warmup=False, warmup_async=False)
        srv.warmup()
        reg = get_registry()
        shed0 = reg.metric("serving_requests_shed").value
        exp0 = reg.metric("serving_requests_expired").value
        lat_hist = reg.metric("serving_request_ms")
        results = []
        lock = threading.Lock()
        try:
            base = srv.address
            code, _ = _get(base, "/readyz")
            assert code == 200
            x = np.asarray(batches[0].features[:2])

            def client(i, dl):
                t0 = time.perf_counter()
                code, bod, hdr = _post(base, "iris", x, deadline_ms=dl)
                with lock:
                    results.append((i, dl, code,
                                    time.perf_counter() - t0))

            # the burst front is held at the (gated) device so every
            # phase is deterministic: capacity = 1 dispatching + 8 queued
            # = 9; everything else MUST shed. 48 arrivals ≈ 5x capacity.
            gated.gate.clear()
            gated.entered.clear()  # warmup dispatches set it already
            gated.dispatches = 0   # count burst-era dispatches only
            threads = []

            def spawn(i, dl):
                t = threading.Thread(target=client, args=(i, dl))
                t.start()
                threads.append(t)

            # 1 — a request the gate holds ON the device past its
            # deadline: must come back 504, never a stale 200
            spawn(0, 120)
            assert gated.entered.wait(10)
            # 2 — two requests whose deadlines expire while QUEUED: must
            # be evicted at batch formation, before any dispatch
            spawn(1, 250)
            spawn(2, 250)
            deadline = time.monotonic() + 10
            while ep.pi._q.qsize() < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            assert ep.pi._q.qsize() == 2
            # 3 — the flood: 45 patient requests against 6 free slots
            for i in range(3, 48):
                spawn(i, 30_000)
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                with lock:
                    if sum(1 for *_, c, _ in results if c == 429) >= 39:
                        break
                time.sleep(0.005)
            with lock:
                assert sum(1 for *_, c, _ in results if c == 429) == 39
            assert ep.pi._q.qsize() == 8  # the admission bound HELD

            # a newer checkpoint commits MID-BURST; the short deadlines
            # expire in the queue while the gate still holds
            trainer_net.fit(batches, num_epochs=2)
            trainer_cm.save(trainer_net)
            time.sleep(0.3)
            gated.gate.set()
            assert ep.pi.poll_checkpoint() is True  # hot-swap under load

            # graceful drain while the accepted tail is still in flight
            assert srv.drain(timeout_s=60) is True
            for t in threads:
                t.join(timeout=60)
            srv.undrain()

            by_code = {}
            for *_, c, _ in results:
                by_code[c] = by_code.get(c, 0) + 1
            # every request got a TERMINAL answer (zero dropped/hung),
            # and the burst resolved exactly as capacity dictates
            assert len(results) == 48
            assert by_code == {429: 39, 504: 3, 200: 6}, by_code
            # accepted requests met their deadlines — 200 means ON TIME
            for i, dl, code, lat in results:
                if code == 200:
                    assert lat <= dl / 1000.0, (i, dl, lat)
            # the expired ones never wasted a device batch slot: only the
            # held batch (request 0) and the post-release batch dispatched
            assert gated.dispatches == 2
            st = ep.pi.stats()
            assert st["queue"]["rejected"] == 39
            assert st["queue"]["expired"] == 2  # the two queue evictions

            # the swap landed mid-burst and is being served
            assert st["hot_swap"]["swaps"] == 1
            assert st["hot_swap"]["current_checkpoint_step"] == 9
            code, out, _ = _post(base, "iris", x)
            assert code == 200
            np.testing.assert_allclose(
                np.asarray(out["outputs"], np.float32),
                np.asarray(trainer_net.output(x)),
                rtol=1e-4, atol=1e-5)

            # live /metrics scrape: shed/expired/swap counters and the
            # request-latency quantiles all visible to a scraper
            code, body = _get(base, "/metrics")
            text = body.decode()
            scraped = {}
            for line in text.splitlines():
                if line and not line.startswith("#"):
                    k, _, v = line.rpartition(" ")
                    scraped[k] = float(v)
            assert scraped["serving_requests_shed"] - shed0 == 39
            assert scraped["serving_requests_expired"] - exp0 == 3
            assert scraped["serving_hot_swap_swaps"] == 1
            assert scraped["serving_queue_rejected"] == 39
            assert scraped["serving_deadline_evictions"] == 2
            assert scraped["serving_request_ms_count"] == lat_hist.count
            assert lat_hist.quantile(0.5) > 0
            assert lat_hist.quantile(0.99) >= lat_hist.quantile(0.5)
        finally:
            gated.gate.set()
            srv.stop(drain=False)
            trainer_cm.close()
            serve_cm.close()

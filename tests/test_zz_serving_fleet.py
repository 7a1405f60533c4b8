"""fleet/ tier: lease-backed replica set, health-aware router, autoscaler.

Covers the tentpole contract with in-process backends (tier-1 lean per
the ROADMAP budget caution): the factored LeaseBoard prefix/payload
protocol, replica membership lifecycle over the SAME lease idiom the
elastic trainer uses, placement-aware routing for models AND indexes,
the never-route-to-cold + instant-start (zero steady-state compiles)
guarantee, the retry taxonomy (transient → different replica;
post-send + non-idempotent → never), and SLO-driven autoscale decisions
with placement-safe victims.

The multi-process chaos acceptance (scale 1→3→2 under open-loop Poisson
load with a SIGKILL mid-burst and zero non-200s on admitted work) is
``slow``-marked with hard deadlines; a tier-1 guard asserts the marking
(house pattern from test_resilience.py).
"""

import inspect
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.checkpoint.storage import ObjectStoreBackend
from deeplearning4j_tpu.fleet import (Autoscaler, AutoscalerPolicy,
                                      FleetRouter, FleetView,
                                      ReplicaAnnouncer, ServingReplica,
                                      parse_prometheus)
from deeplearning4j_tpu.fleet.autoscaler import histogram_quantile
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.parallel.leases import LeaseBoard
from deeplearning4j_tpu.serving import ModelServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _conf(seed=42, n_hidden=8):
    return (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(learning_rate=0.05))
            .weight_init("xavier").list()
            .layer(DenseLayer(n_out=n_hidden, activation="tanh"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())


def _net(seed=42):
    return MultiLayerNetwork(_conf(seed)).init()


def _post(base, path, obj, timeout=30):
    req = urllib.request.Request(
        base + path, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _predict(base, model, inputs, timeout=30):
    return _post(base, f"/v1/models/{model}:predict",
                 {"inputs": np.asarray(inputs).tolist()}, timeout=timeout)


def _get(base, path, timeout=10):
    try:
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ------------------------------------------------------- lease board factoring
def test_lease_board_prefix_and_payload_protocol():
    """The factored LeaseBoard: a prefixed fleet lease and a
    default-prefix trainer lease share one store without colliding;
    static payload + per-write sampler ride every record; a sampler that
    raises is counted, never fatal to the beat."""
    store = ObjectStoreBackend()
    calls = {"n": 0}

    def sampler():
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("stats hook died")
        return {"load": {"inflight": calls["n"]}}

    rb = LeaseBoard(store, "r0", ttl_s=5.0, prefix="replica-",
                    payload_fn=sampler)
    rb.set_payload(address="http://127.0.0.1:1", models=["m"])
    rb.write()
    tb = LeaseBoard(store, "w0", ttl_s=5.0)  # elastic trainer, "lease-"
    tb.write()
    assert set(rb.read_all()) == {"r0"}
    assert set(tb.read_all()) == {"w0"}

    rec = rb.read_all()["r0"]
    assert rec["address"] == "http://127.0.0.1:1"
    assert rec["models"] == ["m"]
    assert rec["load"] == {"inflight": 1}
    assert rec["incarnation"] and rec["seq"] == 1

    rb.write()  # sampler raises this time: write still lands
    assert rb.payload_errors == 1
    assert rb.read_all()["r0"]["seq"] == 2
    rb.write()
    assert rb.read_all()["r0"]["load"] == {"inflight": 3}

    # the elastic module re-exports the factored class (one protocol)
    from deeplearning4j_tpu.parallel.elastic import LeaseBoard as Elastic
    assert Elastic is LeaseBoard


def test_replica_membership_lifecycle():
    """Announce cold → warm → draining → TTL-expire → withdraw, all
    through FleetView with an injected observer clock."""
    store = ObjectStoreBackend()
    t = {"now": 1000.0}
    ann = ReplicaAnnouncer(store, "rep0", address="http://127.0.0.1:1234",
                           models=["iris"], indexes=["docs"], ttl_s=5.0,
                           heartbeat_s=999.0, clock=lambda: t["now"])
    ann.announce()
    view = FleetView(store, ttl_s=5.0, clock=lambda: t["now"])

    rs = view.replicas()
    assert list(rs) == ["rep0"]
    r = rs["rep0"]
    assert not r.ready and not r.warmed
    assert r.hosts_model("iris") and r.hosts_index("docs")
    assert r.host_port == ("127.0.0.1", 1234)
    # cold replicas are visible but never placement candidates
    assert view.for_model("iris") == []
    assert [x.replica_id
            for x in view.for_model("iris", ready_only=False)] == ["rep0"]

    ann.set_warmed(True)
    assert view.for_model("iris")[0].ready
    assert view.for_index("docs")[0].replica_id == "rep0"
    snap = view.snapshot()
    json.dumps(snap)  # JSON-safe (the router's /v1/fleet)
    assert snap["ready"] == ["rep0"]

    ann.set_draining(True)
    assert view.replicas() and view.ready() == {}
    ann.set_draining(False)
    assert view.ready()

    t["now"] += 5.1  # observer clock passes the TTL: silent death
    assert view.replicas() == {}
    ann.set_warmed(True)  # a fresh heartbeat write revives it
    assert view.ready()

    ann.withdraw()  # clean exit: gone immediately, no TTL wait
    assert view.replicas() == {}


# ----------------------------------------------------- routing and placement
def test_router_placement_models_and_indexes(devices):
    """Two replicas, disjoint placement (one hosts a model, the other a
    different model plus an index): the router routes each name only to
    its host, aggregates placement maps, and relays the upstream
    taxonomy untouched."""
    store = ObjectStoreBackend()
    rng = np.random.default_rng(0)
    V = rng.standard_normal((32, 8)).astype(np.float32)

    srv_a = ModelServer()
    srv_a.add_model("small", _net(0),
                    warmup_example=np.zeros((1, 4), np.float32))
    srv_b = ModelServer()
    srv_b.add_model("big", _net(1),
                    warmup_example=np.zeros((1, 4), np.float32))
    from deeplearning4j_tpu.retrieval import BruteForceIndex
    srv_b.add_index("vecs", BruteForceIndex(V), k_default=3,
                    warmup_queries=8)

    rep_a = ServingReplica(srv_a, store, "repA", heartbeat_s=0.5).start()
    rep_b = ServingReplica(srv_b, store, "repB", heartbeat_s=0.5).start()
    router = None
    try:
        assert rep_a.wait_ready(120) and rep_b.wait_ready(120)
        router = FleetRouter(FleetView(store), refresh_s=0.1,
                             seed=0).start()
        base = router.address

        code, body = _get(base, "/v1/models")
        assert code == 200 and body["models"] == ["big", "small"]
        assert body["placement"] == {"small": ["repA"], "big": ["repB"]}
        code, body = _get(base, "/v1/indexes")
        assert body["placement"] == {"vecs": ["repB"]}

        x = rng.random((3, 4)).astype(np.float32)
        code, out = _predict(base, "small", x)
        assert code == 200 and np.asarray(out["outputs"]).shape == (3, 3)
        code, out = _predict(base, "big", x)
        assert code == 200 and out["model"] == "big"
        code, out = _post(base, "/v1/indexes/vecs:query",
                          {"queries": V[:2].tolist(), "k": 3})
        assert code == 200 and np.asarray(out["indices"]).shape == (2, 3)
        # nearest neighbour of a stored vector is itself
        assert out["indices"][0][0] == 0 and out["indices"][1][0] == 1

        # upstream 400 relayed untouched (shape guard fires on the host)
        code, err = _predict(base, "small", np.zeros((2, 9), np.float32))
        assert code == 400 and "shape" in err["error"]
        # a live fleet with no host for the name: retryable 503, typed
        code, err = _predict(base, "nope", x)
        assert code == 503 and err["reason"] == "no_replica"

        code, body = _get(base, "/readyz")
        assert code == 200 and body["replicas"] == ["repA", "repB"]
        code, body = _get(base, "/v1/fleet")
        assert code == 200 and sorted(body["replicas"]) == ["repA", "repB"]
    finally:
        if router is not None:
            router.stop()
        rep_a.stop(drain_timeout_s=5.0)
        rep_b.stop(drain_timeout_s=5.0)


def test_instant_start_never_cold_routed_zero_steady_compiles(
        devices, tmp_path):
    """The instant-start acceptance, in-process: a replica restoring a
    checkpoint that carries a TuningRecord (1) is announced but NEVER
    routed to while its lease says cold, and (2) once warmed serves its
    first admitted request with ZERO new compiles — the ladder the
    record warmed at registration is the serving ladder."""
    from deeplearning4j_tpu.checkpoint import CheckpointManager
    from deeplearning4j_tpu.perf.autotune import autotune, build_network

    conf = _conf(seed=3)
    rec = autotune(conf, batch_sizes=(4,), top_k=1, reps=1)
    net = build_network(conf, rec).init()
    ckpt = str(tmp_path / "ckpt")
    CheckpointManager(ckpt).save(net, wait=True)

    restored = CheckpointManager(ckpt).restore_latest(load_updater=False)
    assert restored._tuning_record == rec  # the ladder rode the checkpoint

    store = ObjectStoreBackend()
    srv = ModelServer()
    ep = srv.add_model("m", restored)  # tuned ladder warms at registration
    rep = ServingReplica(srv, store, "cold0", heartbeat_s=0.5)
    rep.start(warm=False)  # announced, lease says warmed=False
    # start() seeds the shape guard from the conf, so a FRESH replica
    # (no successful request yet) 400s wrong shapes pre-dispatch
    assert ep.feature_shape == (4,)
    router = FleetRouter(FleetView(store), refresh_s=0.05, seed=0).start()
    try:
        x = np.zeros((4, 4), np.float32)
        # the server itself could answer — but the lease is cold, so the
        # router must not route to it
        code, err = _predict(router.address, "m", x)
        assert code == 503 and err["reason"] == "no_replica"

        srv.warmup()  # no-op pass: the record's buckets already compiled
        st0 = ep.pi.stats()
        rep.mark_ready()
        deadline = time.monotonic() + 15.0
        code = None
        while time.monotonic() < deadline:
            code, out = _predict(router.address, "m", x)
            if code == 200:
                break
            time.sleep(0.05)
        assert code == 200
        st = ep.pi.stats()
        assert st["model_compiles"] == st0["model_compiles"]
        assert st["unwarmed_dispatches"] == 0
        # wrong-shape now relays the replica's pre-dispatch 400
        code, err = _predict(router.address, "m",
                             np.zeros((2, 9), np.float32))
        assert code == 400 and "shape" in err["error"]
    finally:
        router.stop()
        rep.stop(drain_timeout_s=5.0)


def test_router_retries_transient_against_different_replica(devices):
    """A lease pointing at a dead port (connect refused = provably never
    admitted) never surfaces to clients: the router retries against the
    OTHER healthy replica and every request answers 200."""
    store = ObjectStoreBackend()
    srv = ModelServer()
    srv.add_model("m", _net(2), warmup_example=np.zeros((1, 4), np.float32))
    rep = ServingReplica(srv, store, "live0", heartbeat_s=0.5).start()
    router = None
    try:
        assert rep.wait_ready(120)
        # reserve a port nobody listens on, then advertise it as warmed
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        dead = ReplicaAnnouncer(store, "dead0",
                                address=f"http://127.0.0.1:{dead_port}",
                                models=["m"], heartbeat_s=999.0)
        dead.announce()
        dead.set_warmed(True)

        router = FleetRouter(FleetView(store), refresh_s=0.05,
                             quarantine_s=0.0, backoff_base_s=0.0,
                             backoff_cap_s=0.001, seed=0).start()
        retries0 = router._m_retries.value
        x = np.zeros((2, 4), np.float32)
        for _ in range(8):
            code, _ = _predict(router.address, "m", x)
            assert code == 200
        # with 2 candidates and 8 weighted picks the dead one was chosen
        # at least once — and the retry landed elsewhere, invisibly
        assert router._m_retries.value > retries0
    finally:
        if router is not None:
            router.stop()
        rep.stop(drain_timeout_s=5.0)


def _half_open_sink():
    """A fake replica that accepts, reads the request, then closes with
    no response — a failure strictly AFTER the request was fully sent
    (the admission-ambiguous case)."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    hits = []

    def loop():
        while True:
            try:
                c, _ = srv.accept()
            except OSError:
                return
            hits.append(1)
            try:
                c.settimeout(2.0)
                c.recv(65536)
            except OSError:
                pass
            finally:
                c.close()

    threading.Thread(target=loop, daemon=True).start()
    return srv, srv.getsockname()[1], hits


def test_post_send_failure_never_retries_non_idempotent():
    """Post-send transport failure: the replica MAY have admitted the
    work. Non-idempotent routes answer 502 after exactly ONE attempt
    (no double execution); idempotent routes retry every candidate."""
    sink_a, port_a, hits_a = _half_open_sink()
    sink_b, port_b, hits_b = _half_open_sink()
    store = ObjectStoreBackend()
    try:
        for rid, port in (("a", port_a), ("b", port_b)):
            ann = ReplicaAnnouncer(store, rid,
                                   address=f"http://127.0.0.1:{port}",
                                   models=["m"], heartbeat_s=999.0)
            ann.announce()
            ann.set_warmed(True)
        router = FleetRouter(FleetView(store), quarantine_s=0.0,
                             backoff_base_s=0.0, backoff_cap_s=0.001,
                             request_timeout_s=5.0, seed=0)  # not started

        up = router._forward("model", "m", "POST",
                             "/v1/models/m:predict", b"{}",
                             "application/json", idempotent=False)
        assert up.status == 502
        assert json.loads(up.body)["reason"] == "upstream_failed"
        assert len(hits_a) + len(hits_b) == 1  # one attempt, no retry

        up = router._forward("model", "m", "POST",
                             "/v1/models/m:predict", b"{}",
                             "application/json", idempotent=True)
        assert up.status == 503  # both candidates tried, both failed
        assert len(hits_a) + len(hits_b) == 3
        assert hits_a and hits_b  # the retry targeted a DIFFERENT replica
    finally:
        sink_a.close()
        sink_b.close()


# ------------------------------------------------------------- autoscaler
def _prom(shed, served, inflight, buckets):
    """Prometheus text a replica's /metrics would carry, minimal form."""
    lines = ["# fake scrape",
             f"serving_requests_shed {shed}",
             f"serving_http_requests {served}",
             f"serving_inflight_requests {inflight}"]
    total = 0
    for le, cum in buckets:
        lines.append(f'serving_request_ms_bucket{{le="{le}"}} {cum}')
        total = cum
    lines.append(f'serving_request_ms_bucket{{le="+Inf"}} {total}')
    lines.append(f"serving_request_ms_sum {float(total)}")
    lines.append(f"serving_request_ms_count {total}")
    return "\n".join(lines)


def test_parse_prometheus_and_histogram_quantile():
    got = parse_prometheus(_prom(2, 10, 3, [(10, 5), (50, 9)]))
    assert got["serving_requests_shed"] == 2.0
    assert got["serving_inflight_requests"] == 3.0
    h = got["serving_request_ms"]
    assert h["buckets"] == [(10.0, 5.0), (50.0, 9.0), (float("inf"), 9.0)]
    assert h["count"] == 9 and h["sum"] == 9.0
    # interpolated: rank 4.5 inside the first bucket
    assert histogram_quantile(h["buckets"], 0.5) == pytest.approx(9.0)
    # rank 8.991 interpolates near the top of the (10, 50] bucket
    assert histogram_quantile(h["buckets"], 0.999) == pytest.approx(49.91)
    # rank lands in the +Inf bucket: best lower bound is the last finite le
    inf_heavy = [(10.0, 5.0), (50.0, 9.0), (float("inf"), 12.0)]
    assert histogram_quantile(inf_heavy, 0.99) == pytest.approx(50.0)
    assert histogram_quantile([], 0.5) == 0.0


def test_autoscaler_slo_decisions_and_cooldowns():
    """shed-rate breach scales up, cooldown holds, idle scales down with
    a placement-covered victim, below-min always launches."""
    store = ObjectStoreBackend()
    t = {"now": 0.0}
    metrics = {}

    class Launcher:
        def __init__(self):
            self.started, self.stopped = 0, []

        def start_replica(self):
            self.started += 1
            return f"new{self.started}"

        def stop_replica(self, rid):
            self.stopped.append(rid)

    def announce(rid, port, inflight):
        ann = ReplicaAnnouncer(store, rid,
                               address=f"http://127.0.0.1:{port}",
                               models=["m"], heartbeat_s=999.0,
                               load_fn=lambda: {"inflight": inflight})
        ann.announce()
        ann.set_warmed(True)
        return ann

    launcher = Launcher()
    pol = AutoscalerPolicy(min_replicas=1, max_replicas=3,
                           scale_up_cooldown_s=10.0,
                           scale_down_cooldown_s=30.0)
    view = FleetView(store, ttl_s=1e9)
    scaler = Autoscaler(view, launcher, pol,
                        fetch=lambda addr: metrics[addr],
                        clock=lambda: t["now"])

    # empty fleet: below min ⇒ launch regardless of signals
    assert scaler.step()["action"] == "up"
    assert launcher.started == 1

    a0 = "http://127.0.0.1:1"
    announce("rep0", 1, inflight=3)
    metrics[a0] = _prom(0, 100, 1.0, [(10, 100), (1000, 100)])
    t["now"] = 12.0  # past the up-cooldown the launch above started
    assert scaler.step()["action"] == "hold"  # baseline scrape, within SLO

    # shed burst: Δshed=30 of Δ90 ⇒ rate ≫ 1% ⇒ up
    t["now"] = 24.0
    metrics[a0] = _prom(30, 160, 1.0, [(10, 160), (1000, 160)])
    d = scaler.step()
    assert (d["action"], d["reason"]) == ("up", "slo breach: shed")
    assert d["shed_rate"] == pytest.approx(30 / 90)
    assert launcher.started == 2

    # still shedding inside the cooldown ⇒ hold, reason says so
    t["now"] = 26.0
    metrics[a0] = _prom(40, 180, 1.0, [(10, 180), (1000, 180)])
    d = scaler.step()
    assert d["action"] == "hold" and "cooldown" in d["reason"]

    # p99 breach drives up too: the new 220 requests all land in the
    # 1 s bucket, an interval p99 far past the 250 ms target
    announce("rep1", 2, inflight=0)
    metrics["http://127.0.0.1:2"] = _prom(0, 0, 0.0, [(10, 0), (1000, 0)])
    t["now"] = 41.0
    metrics[a0] = _prom(40, 400, 1.0, [(10, 180), (1000, 400)])
    d = scaler.step()
    assert (d["action"], d["reason"]) == ("up", "slo breach: p99")
    assert d["p99_ms"] > pol.target_p99_ms

    # idle fleet of 2 ⇒ down; victim is the least-loaded (placement is
    # covered either way: both host "m")
    t["now"] = 120.0
    d = scaler.step()
    assert (d["action"], d["victim"]) == ("down", "rep1")
    assert launcher.stopped == ["rep1"]

    # a second idle step inside the down-cooldown holds
    t["now"] = 125.0
    d = scaler.step()
    assert d["action"] == "hold" and "cooldown" in d["reason"]


def test_scale_down_victim_is_placement_safe():
    """The least-loaded replica is skipped when it is the SOLE host of a
    model or index — scale-down never opens a placement hole."""
    from deeplearning4j_tpu.fleet.membership import ReplicaInfo

    def info(rid, models, indexes, inflight):
        return ReplicaInfo(replica_id=rid, address="http://x:1",
                           warmed=True, draining=False,
                           models=tuple(models), indexes=tuple(indexes),
                           incarnation="i", load={"inflight": inflight},
                           time=0.0)

    scaler = Autoscaler(FleetView(ObjectStoreBackend()), launcher=None,
                        fetch=lambda a: "")
    # both replicas host the same set: the least-loaded one goes
    ready = {"lo": info("lo", ["a"], [], inflight=0),
             "hi": info("hi", ["a"], [], inflight=9)}
    assert scaler._victim(ready) == "lo"
    # the least-loaded replica is the SOLE host of "b": despite its
    # load advantage it is skipped, the coverage-preserving peer goes
    ready = {"lo": info("lo", ["a", "b"], [], inflight=0),
             "hi": info("hi", ["a"], [], inflight=9)}
    assert scaler._victim(ready) == "hi"
    # sole-host check applies to indexes exactly like models
    ready = {"lo": info("lo", ["a"], ["vecs"], inflight=0),
             "hi": info("hi", ["a"], [], inflight=9)}
    assert scaler._victim(ready) == "hi"
    # a 1-replica fleet has no safe victim at all
    assert scaler._victim({"lo": info("lo", ["a"], [], 0)}) is None


# ------------------------------------------------------------------- CLI
def test_fleet_cli_parser_and_model_spec():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fleet_cli", os.path.join(REPO, "tools", "fleet.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    args = cli.build_parser().parse_args(
        ["up", "--store", "/tmp/s", "--replicas", "3",
         "--model", "iris=/ckpts/iris", "--model", "big=/ckpts/big"])
    assert args.replicas == 3
    assert args.model == [("iris", "/ckpts/iris"), ("big", "/ckpts/big")]
    with pytest.raises(SystemExit):
        cli.build_parser().parse_args(
            ["up", "--store", "/tmp/s", "--model", "no-equals-sign"])


# ------------------------------------------------- multi-process chaos (slow)
def _spawn_replica(store, ckpt, rid, ttl_s=3.0):
    """One tools/fleet.py replica subprocess (the SIGKILL target)."""
    cmd = [sys.executable, os.path.join(REPO, "tools", "fleet.py"),
           "replica", "--store", store, "--model", f"m={ckpt}",
           "--replica-id", rid, "--ttl-s", str(ttl_s),
           "--drain-timeout-s", "30"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)


def _reap(procs, timeout=30.0):
    """Hard deadline on child exit: TERM, bounded wait, then kill."""
    for p in procs.values():
        if p.poll() is None:
            p.terminate()
    outs = {}
    for rid, p in procs.items():
        try:
            outs[rid] = p.communicate(timeout=timeout)[0]
        except subprocess.TimeoutExpired:
            p.kill()
            outs[rid] = p.communicate(timeout=10)[0]
    return outs


@pytest.mark.slow
def test_chaos_scale_1_3_2_sigkill_midburst_zero_non200_admitted(tmp_path):
    """The chaos acceptance: open-loop Poisson load against the router
    while the fleet scales 1→3 (fresh replicas restore the checkpoint,
    inherit the TuningRecord, warm off-path), one replica is SIGKILLed
    mid-burst and another SIGTERM-drains (3→2). Every response the
    router hands a client is a 200 or a typed shed (429/503) — zero
    non-200s on admitted work, zero transport errors surfaced."""
    from deeplearning4j_tpu.checkpoint import CheckpointManager
    from deeplearning4j_tpu.perf.autotune import autotune, build_network

    conf = _conf(seed=11)
    rec = autotune(conf, batch_sizes=(4,), top_k=1, reps=1)
    net = build_network(conf, rec).init()
    ckpt = str(tmp_path / "ckpt")
    CheckpointManager(ckpt).save(net, wait=True)
    store = str(tmp_path / "store")
    os.makedirs(store)

    procs = {"rep0": _spawn_replica(store, ckpt, "rep0")}
    router = FleetRouter(FleetView(store, ttl_s=3.0), refresh_s=0.1,
                         seed=0).start()
    statuses, stop_evt = [], threading.Event()
    rng = np.random.default_rng(0)

    def load_loop():
        body = json.dumps({"inputs": [[5.1, 3.5, 1.4, 0.2]]}).encode()
        url = router.address + "/v1/models/m:predict"
        while not stop_evt.is_set():
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=20) as r:
                    statuses.append(r.status)
            except urllib.error.HTTPError as e:
                statuses.append(e.code)
            except Exception as e:  # transport error surfaced = failure
                statuses.append(type(e).__name__)
            time.sleep(float(rng.exponential(0.05)))  # open-loop Poisson

    loader = threading.Thread(target=load_loop, daemon=True)
    try:
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline:
            if _get(router.address, "/readyz", timeout=5)[0] == 200:
                break
            assert procs["rep0"].poll() is None, \
                _reap(procs, timeout=10)["rep0"][-2000:]
            time.sleep(0.5)
        else:
            pytest.fail("rep0 never became ready")

        loader.start()
        time.sleep(1.5)  # burst against the 1-replica fleet

        # scale 1→3 under load; the cold replicas must not be routed to
        # until their leases flip warmed
        procs["rep1"] = _spawn_replica(store, ckpt, "rep1")
        procs["rep2"] = _spawn_replica(store, ckpt, "rep2")
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline:
            if len(router.table()) == 3:
                break
            time.sleep(0.5)
        else:
            pytest.fail(f"fleet never reached 3 ready: {_reap(procs)}")
        time.sleep(2.0)  # burst across all 3

        procs["rep1"].kill()  # SIGKILL mid-burst: lease times out (3 s)
        time.sleep(4.5)  # ride through the TTL window on retries

        procs["rep2"].send_signal(signal.SIGTERM)  # graceful drain 3→2
        out2 = procs.pop("rep2")
        drained = out2.communicate(timeout=60)[0]
        assert out2.returncode == 0, drained[-2000:]
        assert "drained and stopped" in drained
        time.sleep(1.5)  # burst against the survivor
    finally:
        stop_evt.set()
        loader.join(timeout=30)
        outs = _reap(procs, timeout=60.0)
        router.stop()

    ok = statuses.count(200)
    bad = [s for s in statuses if s not in (200, 429, 503)]
    assert ok >= 50, (ok, statuses[:50], outs.get("rep0", "")[-2000:])
    # the acceptance bar: nothing admitted ever failed — no 5xx other
    # than typed sheds, no 504s, no raw transport errors
    assert bad == [], (bad, outs)


def test_fleet_chaos_tests_are_slow_marked_and_bounded():
    """Tier-1 guard (house pattern from test_resilience.py): the
    multi-process fleet chaos test can never hang tier-1 — it is
    slow-marked AND every wait carries a finite deadline that kills
    children on expiry."""
    fn = test_chaos_scale_1_3_2_sigkill_midburst_zero_non200_admitted
    marks = [m.name for m in getattr(fn, "pytestmark", [])]
    assert "slow" in marks, f"{fn.__name__} must be slow-marked"
    src = inspect.getsource(fn)
    assert "timeout=" in src, f"{fn.__name__} must pass a deadline"
    assert "communicate(timeout=" in src
    reap = inspect.getsource(_reap)
    assert "communicate(timeout=" in reap and ".kill()" in reap

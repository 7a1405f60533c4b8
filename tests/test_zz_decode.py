"""Generative decode tier (ISSUE 19): device-resident session-slot
ladder, continuous session batching, token streaming over HTTP.

Core contracts under test:

- sessions join/leave the live batch at token boundaries with ZERO
  steady-state compiles (CompileWatch-asserted) and no per-token host
  sync in the jitted step (trace_check-asserted: host transfers stay
  O(dispatches), never O(sessions x tokens));
- greedy decode through the engine matches the sequential stateful
  ``rnn_time_step`` loop token for token, chunked prefill included;
- ``POST /v1/models/<name>:generate`` extends the PR 8 429/503/504
  taxonomy to streams — a stream that misses a token deadline
  terminates with a typed event, never a silent stall;
- sessions survive a checkpoint hot-swap (or re-prefill cleanly);
- the persisted compilation cache makes the SECOND cold start replay
  executables from disk (subprocess-measured).

The chaos run (hundreds of concurrent streams + mid-generation swap)
is slow-marked; tier-1 keeps the lean core per the ROADMAP cap note.
"""

import http.client
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.textgenlstm import TextGenerationLSTM
from deeplearning4j_tpu.serving.decode import (DecodeEngine,
                                               EngineStoppedError,
                                               SessionLimitError)
from deeplearning4j_tpu.serving.server import ModelServer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = list("abcdefghij")


def _make_net(seed=7):
    return TextGenerationLSTM(total_unique_characters=len(VOCAB),
                              units=16, seed=seed).init()


def _sequential_greedy(net, prompt, n_tokens):
    """Reference decode: the stateful host-API loop, one token at a time."""
    def one_hot(tok):
        x = np.zeros((1, len(VOCAB)), np.float32)
        x[0, tok] = 1.0
        return x

    net.rnn_clear_previous_state()
    for tok in prompt:
        out = net.rnn_time_step(one_hot(tok))
    toks = [int(out[0].argmax())]
    for _ in range(n_tokens - 1):
        out = net.rnn_time_step(one_hot(toks[-1]))
        toks.append(int(out[0].argmax()))
    net.rnn_clear_previous_state()
    return toks


@pytest.fixture(scope="module")
def engine():
    """One warmed engine for the whole module: small ladder (2->4->8),
    small prefill buckets so a 23-token prompt exercises chunking."""
    eng = DecodeEngine(_make_net(), max_sessions=8, min_slots=2,
                       prefill_buckets=(4, 8), seed=1)
    eng.warmup()
    yield eng
    eng.stop()


class TestRnnTimeStepLowering:
    """Satellite 1: rnn_time_step rides the jitted single-step program."""

    def test_single_step_parity_with_full_forward(self):
        net = _make_net()
        seq = [3, 1, 4, 1, 5, 9, 2, 6]
        x_full = np.zeros((1, len(seq), len(VOCAB)), np.float32)
        for t, tok in enumerate(seq):
            x_full[0, t, tok] = 1.0
        full = np.asarray(net.output(x_full))
        net.rnn_clear_previous_state()
        steps = []
        for tok in seq:
            x = np.zeros((1, len(VOCAB)), np.float32)
            x[0, tok] = 1.0
            steps.append(net.rnn_time_step(x))
        stepped = np.stack([s[0] for s in steps])[None]
        # (1, T, v) both ways; stateful stepping == one full pass
        assert np.allclose(full, stepped, atol=1e-5), \
            np.abs(full - stepped).max()

    def test_no_per_call_tracing(self):
        net = _make_net()
        x = np.zeros((1, len(VOCAB)), np.float32)
        x[0, 2] = 1.0
        net.rnn_clear_previous_state()
        net.rnn_time_step(x)
        compiled = net.compile_watch.compiles("rnn_single_step")
        for _ in range(25):
            net.rnn_time_step(x)
        assert net.compile_watch.compiles("rnn_single_step") == compiled

    def test_batch_mismatch_still_raises(self):
        net = _make_net()
        net.rnn_clear_previous_state()
        net.rnn_time_step(np.zeros((2, len(VOCAB)), np.float32))
        with pytest.raises(ValueError, match="batch size"):
            net.rnn_time_step(np.zeros((3, len(VOCAB)), np.float32))


class TestDecodeEngine:
    def test_greedy_parity_including_chunked_prefill(self, engine):
        # 23-token prompt >> top prefill bucket (8): exercises chunking
        rng = np.random.default_rng(3)
        for prompt in ([0, 1, 2],
                       [int(t) for t in rng.integers(0, len(VOCAB), 23)]):
            sess = engine.open_session(prompt, max_tokens=10,
                                       temperature=0.0)
            got = [ev["id"] for ev in sess.events(30.0)
                   if ev["type"] == "token"]
            want = _sequential_greedy(_make_net(), prompt, 10)
            assert got == want, (prompt, got, want)

    def test_zero_steady_state_compiles_and_bounded_syncs(self, engine):
        from deeplearning4j_tpu.analysis import trace_check

        before = dict(engine.stats()["compiles"])
        n_sessions, n_tokens = 4, 12
        with trace_check(check_constants=False) as rep:
            sessions = [engine.open_session([i, i + 1], max_tokens=n_tokens,
                                            temperature=1.0, top_k=3)
                        for i in range(n_sessions)]
            done = [list(s.events(30.0)) for s in sessions]
        for evs in done:
            assert evs[-1]["type"] == "done"
            assert sum(e["type"] == "token" for e in evs) == n_tokens
        # continuous batching joins/leaves at token boundaries: nothing
        # compiles once the ladder is warmed
        assert dict(engine.stats()["compiles"]) == before
        # ONE bulk host read per dispatch (+ admission bookkeeping), not
        # one per session-token: far fewer syncs than tokens delivered
        syncs = sum(h.count for h in rep.sync_points)
        assert syncs < n_sessions * n_tokens, \
            f"{syncs} host syncs for {n_sessions * n_tokens} tokens"

    def test_full_wave_of_sessions_compiles_nothing(self, engine):
        """Eight sessions opened at once fill the warmed ladder's top
        rung (2 -> 4 -> 8 slots): each one runs to its end and the wave
        compiles nothing."""
        before = dict(engine.stats()["compiles"])
        n_sessions, n_tokens = 8, 16
        rng = np.random.default_rng(0)
        sessions = [engine.open_session(
            [int(t) for t in rng.integers(0, len(VOCAB), 3)],
            max_tokens=n_tokens, temperature=0.0)
            for _ in range(n_sessions)]
        for evs in [list(s.events(30.0)) for s in sessions]:
            assert evs[-1]["type"] == "done"
            assert sum(e["type"] == "token" for e in evs) == n_tokens
        assert dict(engine.stats()["compiles"]) == before

    def test_admission_taxonomy(self, engine):
        with pytest.raises(ValueError):
            engine.open_session([], max_tokens=4)
        with pytest.raises(ValueError):
            engine.open_session([999], max_tokens=4)
        with pytest.raises(ValueError):
            engine.open_session([1], max_tokens=0)
        held = [engine.open_session([0], max_tokens=1_000_000)
                for _ in range(engine.max_sessions)]
        try:
            with pytest.raises(SessionLimitError):
                engine.open_session([1], max_tokens=4)
        finally:
            for s in held:
                s.cancel()
        deadline = time.monotonic() + 10
        while engine.stats()["active"] and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine.stats()["active"] == 0

    def test_eos_retires_at_boundary(self, engine):
        # greedy from this prompt emits token 0 first: eos on it
        want = _sequential_greedy(_make_net(), [0, 1, 2], 1)
        sess = engine.open_session([0, 1, 2], max_tokens=50,
                                   temperature=0.0, eos_id=want[0])
        evs = list(sess.events(30.0))
        assert evs[-1] == {"type": "done", "reason": "eos", "tokens": 1}

    def test_stopped_engine_refuses(self):
        eng = DecodeEngine(_make_net(), max_sessions=2, min_slots=2,
                           prefill_buckets=(4,), seed=0)
        eng.start()
        eng.stop()
        with pytest.raises(EngineStoppedError):
            eng.open_session([1], max_tokens=4)


class TestHotSwap:
    def test_sessions_survive_swap_and_reprefill(self, tmp_path):
        from deeplearning4j_tpu.checkpoint import CheckpointManager

        eng = DecodeEngine(_make_net(), max_sessions=2, min_slots=2,
                           prefill_buckets=(4,), seed=0)
        eng.warmup()
        cm = CheckpointManager(str(tmp_path / "ckpt"))
        try:
            # huge poll interval: the poller thread stays idle and the
            # test drives poll_checkpoint() deterministically
            eng.start_hot_swap(cm, poll_secs=3600.0, policy="reprefill")
            # long-lived stream so it is still mid-generation when the
            # staged swap lands at a step boundary
            sess = eng.open_session([1, 2, 3], max_tokens=1_000_000,
                                    temperature=1.0)
            while len(sess.generated) < 5:
                time.sleep(0.005)
            newer = _make_net(seed=99)
            newer.training_step = 100
            cm.save(newer)
            cm.flush()  # save() commits async: flush before the poll
            assert eng.poll_checkpoint() is True
            deadline = time.monotonic() + 20
            while (eng.stats()["hot_swaps"] == 0
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert eng.stats()["hot_swaps"] == 1, \
                "staged swap never applied at a step boundary"
            # the session SURVIVED: tokens keep flowing under new params
            n0 = len(sess.generated)
            deadline = time.monotonic() + 20
            while (len(sess.generated) < n0 + 10
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert len(sess.generated) >= n0 + 10
            assert not sess.finished
            sess.cancel()
            # no-newer poll is a no-op
            assert eng.poll_checkpoint() is False
        finally:
            eng.stop()
            cm.close()


@pytest.fixture(scope="module")
def server():
    srv = ModelServer()
    srv.add_generator("char", DecodeEngine(
        _make_net(), max_sessions=4, min_slots=2, prefill_buckets=(4, 8),
        seed=1, vocab=VOCAB), default_deadline_ms=10_000.0)
    srv.start(warmup=True, warmup_async=False)
    yield srv
    srv.stop(drain=True, drain_timeout_s=10.0)


def _post(srv, path, body, timeout=30.0):
    c = http.client.HTTPConnection(srv.bind_address, srv.port,
                                   timeout=timeout)
    c.request("POST", path, body=json.dumps(body).encode())
    r = c.getresponse()
    data = r.read()
    headers = dict(r.getheaders())
    c.close()
    return r.status, headers, data


def _sse_events(raw: str):
    out = []
    for block in raw.strip().split("\n\n"):
        lines = dict(ln.split(": ", 1) for ln in block.split("\n"))
        out.append((lines["event"], json.loads(lines["data"])))
    return out


class TestGenerateRoute:
    def test_stream_and_json_agree_with_sequential(self, server):
        want = _sequential_greedy(_make_net(), [0, 1, 2], 6)
        st, _, data = _post(server, "/v1/models/char:generate",
                            {"prompt": "abc", "max_tokens": 6,
                             "temperature": 0.0, "stream": False})
        out = json.loads(data)
        assert st == 200 and out["token_ids"] == want
        assert out["text"] == "".join(VOCAB[t] for t in want)
        assert out["reason"] == "max_tokens"

        c = http.client.HTTPConnection(server.bind_address, server.port,
                                       timeout=30.0)
        c.request("POST", "/v1/models/char:generate", body=json.dumps(
            {"prompt_ids": [0, 1, 2], "max_tokens": 6,
             "temperature": 0.0}).encode())
        r = c.getresponse()
        assert r.status == 200
        assert r.getheader("Content-Type") == "text/event-stream"
        events = _sse_events(r.read().decode())  # http.client de-chunks
        c.close()
        kinds = [k for k, _ in events]
        assert kinds[0] == "meta" and kinds[-1] == "done"
        assert [d["id"] for k, d in events if k == "token"] == want

    def test_error_taxonomy(self, server):
        gep = server.generators["char"]
        st, _, _ = _post(server, "/v1/models/nope:generate",
                         {"prompt_ids": [1]})
        assert st == 404
        st, _, _ = _post(server, "/v1/models/char:generate", {})
        assert st == 400
        st, _, data = _post(server, "/v1/models/char:generate",
                            {"prompt": "a!z", "max_tokens": 4})
        assert st == 400 and b"vocab" in data
        # 429 shed + Retry-After when every session slot is held
        held = [gep.engine.open_session([0], max_tokens=1_000_000)
                for _ in range(gep.engine.max_sessions)]
        try:
            st, headers, _ = _post(server, "/v1/models/char:generate",
                                   {"prompt_ids": [1], "max_tokens": 4})
            assert st == 429 and "Retry-After" in headers
        finally:
            for s in held:
                s.cancel()
        deadline = time.monotonic() + 10
        while gep.engine.stats()["active"] and time.monotonic() < deadline:
            time.sleep(0.01)
        # 504 when the FIRST token misses the deadline (nothing sent yet)
        st, _, data = _post(server, "/v1/models/char:generate",
                            {"prompt_ids": [1], "max_tokens": 4,
                             "deadline_ms": 0.001, "stream": False})
        assert st == 504 and b"deadline_expired" in data
        # draining: typed 503 shed
        server.drain(timeout_s=5.0)
        try:
            st, _, data = _post(server, "/v1/models/char:generate",
                                {"prompt_ids": [1], "max_tokens": 4})
            assert st == 503 and b"draining" in data
        finally:
            server.undrain()

    def test_token_deadline_terminates_stream_typed(self, server):
        # after streaming starts the status is already 200: a missed
        # token deadline must surface as a typed in-band error event
        c = http.client.HTTPConnection(server.bind_address, server.port,
                                       timeout=30.0)
        c.request("POST", "/v1/models/char:generate", body=json.dumps(
            {"prompt_ids": [1], "max_tokens": 200, "deadline_ms": 10_000,
             "token_deadline_ms": 0.0001}).encode())
        r = c.getresponse()
        assert r.status == 200
        events = _sse_events(r.read().decode())
        c.close()
        kind, detail = events[-1]
        assert kind == "error"
        assert detail["error"] == "token_deadline_expired"

    def test_readiness_and_stats_surface(self, server):
        ready, reasons = server.readiness()
        assert ready, reasons
        c = http.client.HTTPConnection(server.bind_address, server.port,
                                       timeout=10.0)
        c.request("GET", "/v1/models/char")
        r = c.getresponse()
        stats = json.loads(r.read())
        c.close()
        assert stats["warmed"] and stats["capacity"] >= 1
        assert set(stats["compiles"]) == {"step", "join", "clear", "grow",
                                          "prefill"}
        c = http.client.HTTPConnection(server.bind_address, server.port,
                                       timeout=10.0)
        c.request("GET", "/healthz")
        r = c.getresponse()
        health = json.loads(r.read())
        c.close()
        assert health["generators"] == ["char"]


class TestCompileCache:
    SCRIPT = """
import sys
from deeplearning4j_tpu.serving.server import ModelServer
srv = ModelServer(compile_cache_dir=sys.argv[1])  # wires the cache
import jax, jax.numpy as jnp
f = jax.jit(lambda x: (x * 2 + 1).sum())
f(jnp.arange(128.0)).block_until_ready()
from deeplearning4j_tpu.perf.compile_cache import cache_hits
print("HITS=%d" % cache_hits())
"""

    def test_second_cold_start_hits_cache(self, tmp_path):
        cache = str(tmp_path / "xla-cache")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO_ROOT)
        runs = []
        for _ in range(2):
            p = subprocess.run([sys.executable, "-c", self.SCRIPT, cache],
                               capture_output=True, text=True, timeout=120,
                               env=env, cwd=REPO_ROOT)
            assert p.returncode == 0, p.stderr
            runs.append(int(p.stdout.strip().split("HITS=")[1]))
        assert runs[0] == 0  # first cold start populates
        assert runs[1] > 0, "second cold start never hit the disk cache"
        assert os.listdir(cache)


@pytest.mark.slow
def test_chaos_many_streams_with_hot_swap(tmp_path):
    """Hundreds of concurrent streaming sessions under open-loop load
    with a mid-generation checkpoint hot-swap: every ADMITTED stream
    (HTTP 200) ends in a terminal done event with its full token count —
    zero non-200 outcomes on admitted streams, zero silent stalls.
    Sheds (429) are allowed and retried; hard timeout bounds the run."""
    from deeplearning4j_tpu.checkpoint import CheckpointManager

    cm = CheckpointManager(str(tmp_path / "ckpt"))
    srv = ModelServer()
    srv.add_generator("char", DecodeEngine(
        _make_net(), max_sessions=32, min_slots=8,
        prefill_buckets=(4, 8), seed=1, vocab=VOCAB),
        checkpoint_manager=cm, checkpoint_poll_secs=0.2,
        hot_swap_policy="reprefill", default_deadline_ms=60_000.0)
    srv.start(warmup=True, warmup_async=False)

    n_streams, n_tokens = 300, 20
    results, failures = [], []
    lock = threading.Lock()
    deadline = time.monotonic() + 240.0

    def run_stream(i):
        rng = np.random.default_rng(i)
        prompt = [int(t) for t in rng.integers(0, len(VOCAB),
                                               1 + i % 11)]
        while time.monotonic() < deadline:
            try:
                c = http.client.HTTPConnection(srv.bind_address, srv.port,
                                               timeout=60.0)
                c.request("POST", "/v1/models/char:generate",
                          body=json.dumps({
                              "prompt_ids": prompt,
                              "max_tokens": n_tokens,
                              "temperature": 1.0, "top_k": 4,
                              "token_deadline_ms": 60_000.0}).encode())
                r = c.getresponse()
                if r.status == 429:  # shed under load: back off, retry
                    r.read()
                    c.close()
                    time.sleep(0.02 * (1 + i % 5))
                    continue
                body = r.read().decode()
                c.close()
                with lock:
                    if r.status != 200:
                        failures.append((i, r.status, body[:200]))
                        return
                    events = _sse_events(body)
                    kinds = [k for k, _ in events]
                    ok = (kinds[-1] == "done"
                          and kinds.count("token") == n_tokens)
                    (results if ok else failures).append(
                        (i, r.status, kinds[-3:]))
                return
            except Exception as e:  # noqa: BLE001 - recorded as failure
                with lock:
                    failures.append((i, "exc", repr(e)))
                return
        with lock:
            failures.append((i, "timeout", "never admitted"))

    threads = [threading.Thread(target=run_stream, args=(i,), daemon=True)
               for i in range(n_streams)]
    t0 = time.monotonic()
    for j, th in enumerate(threads):
        th.start()
        if j % 25 == 24:
            time.sleep(0.05)  # open-loop ramp
        if j == n_streams // 3:
            newer = _make_net(seed=99)
            newer.training_step = 100
            cm.save(newer)
            cm.flush()  # hot-swap lands mid-generation via the poller
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()) + 30.0)
    elapsed = time.monotonic() - t0

    try:
        assert not failures, failures[:10]
        assert len(results) == n_streams
        assert srv.generators["char"].engine.stats()["hot_swaps"] >= 1, \
            "checkpoint hot-swap never applied during the chaos run"
    finally:
        srv.stop(drain=True, drain_timeout_s=15.0)
        cm.close()
    print(f"chaos: {len(results)} streams x {n_tokens} tokens in "
          f"{elapsed:.1f}s")

"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: every phase below
    python chip_smoke.py --chips 4   # four chips: ParallelWrapper only
    python chip_smoke.py --rehearse  # any backend, tiny sizes: control flow

ONE process (a chip belongs to one process: servers run in-process and
are driven over loopback by threads), through the entry points a user
calls, weights and data made from ``--seed``. Each phase prints one JSON
line — ``phase``, ``ok``, ``seconds``, ``compile_seconds`` apart from
``run_seconds``, and what it ``checked`` — and the first failure exits
non-zero. Phases, one chip:

- ``device``  versions, ``jax.devices()``; not a TPU -> fail, no CPU retry
- ``train``   ResNet50 224x224 / 1000 classes / bf16 / batch 128 through
              ``ComputationGraph.fit`` over a ``DevicePrefetchIterator``
- ``serve``   that network behind ``serving.ModelServer`` (``:predict``
              over HTTP) and the zoo text-generation LSTM behind
              ``DecodeEngine`` (``:generate``), zero compiles after warm-up
- ``legacy``  LeNet ``fit``/``fit_fused``, char-RNN ``fit_tbptt_fused``,
              Word2Vec SGNS ``device_corpus=True``
- ``kernels`` every Pallas family the default selection resolves to on
              this device, flash attention and the Word2Vec scatter:
              compiled, not interpreted, against the XLA/jnp reference

``--chips 4`` runs only ``device`` and ``multichip``: ResNet50 at global
batch 128 on one device, then from the same seed through
``ParallelWrapper`` at dp=4 and at dp=2 x tp=2 on four real chips.

The last line of stdout is the result, and is printed only when every
phase passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``
with the device as JAX reports it. ``--rehearse`` lifts the platform
check and shrinks every size; its last line names the platform it really
ran on, so it can never pass for a chip run.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import traceback
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.obs import Stopwatch
from deeplearning4j_tpu.perf.compile_cache import (cache_hits,
                                                   enable_compilation_cache)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What ``--rehearse`` shrinks. Widths of the full run are the
    models' own; nothing else differs between the two modes but the
    platform check."""
    side: int = 224             # ResNet50 input
    classes: int = 1000
    batch: int = 128
    serve_rows: tuple = (3, 12)  # rows a request carries: two buckets
    lstm_units: int = 256       # zoo width
    gen_tokens: int = 24
    tbptt: int = 50
    w2v_sentences: int = 25_000
    w2v_vocab: int = 10_000
    w2v_batch: int = 8192
    table_rows: int = 1 << 20   # retrieval tables
    index_rows: int = 20_000
    attn_seq: int = 1024
    scatter_rows: int = 20_000
    mc_side: int = 224          # --chips 4: ResNet50 input, global batch
    mc_batch: int = 128


REHEARSAL = Sizes(side=32, classes=10, batch=8, lstm_units=16, gen_tokens=6, tbptt=8, w2v_sentences=300,
                  w2v_vocab=200, w2v_batch=256, table_rows=2048,
                  index_rows=600, attn_seq=128, scatter_rows=64,
                  # (smaller than this, 1x1 feature maps under a batch of 8
                  # make BatchNorm amplify rounding into the whole loss)
                  mc_side=64, mc_batch=32)


class Ctx:
    def __init__(self, args):
        self.rehearse = args.rehearse
        self.seed = args.seed
        self.size = REHEARSAL if args.rehearse else Sizes()
        self.device = None      # set by the device phase
        self.resnet = None      # trained net, handed from train to serve
        self.lenet = None       # handed from legacy to kernels

    def rng(self, salt: int):
        return np.random.default_rng([self.seed, salt])


def check(cond, what: str):
    if not cond:
        raise AssertionError(what)


def lap(sw, sync=None) -> float:
    """Seconds on ``sw`` (an ``obs.Stopwatch``) once ``sync`` is ready —
    every other use ends in a value fetch — and start the next lap."""
    dt = round(sw.stop(sync), 3)
    sw.start()
    return dt


# ------------------------------------------------------------------ device
def phase_device(ctx: Ctx) -> dict:
    import jaxlib

    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.perf import compile_cache
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    devices = jax.devices()
    ctx.device = devices[0]
    info = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "devices": [str(d) for d in devices],
            "platform": ctx.device.platform,
            "device_kind": ctx.device.device_kind,
            "compile_cache_dir": compile_cache.cache_dir(),
            "csv_reader": "native" if native.native_available()
                          else "python"}
    if not ctx.rehearse:
        check(ctx.device.platform == "tpu",
              f"not a TPU: jax.devices() = {info['devices']}")
    return info


# ------------------------------------------------------------------- train
def _resnet50(ctx: Ctx, side: int):
    """The north-star model as the benchmark configures it: bf16 compute."""
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = dataclasses.replace(
        ResNet50(num_classes=ctx.size.classes, input_shape=(side, side, 3),
                 seed=ctx.seed).conf(), dtype="bfloat16")
    return ComputationGraph(conf).init()


def _image_batches(ctx: Ctx, n: int, batch: int, side: int, salt: int):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    classes, rng = ctx.size.classes, ctx.rng(salt)
    out = []
    for _ in range(n):
        x = rng.standard_normal((batch, side, side, 3), np.float32)
        y = np.eye(classes, dtype=np.float32)[
            rng.integers(0, classes, batch)]
        out.append(DataSet(x, y))
    return out


def _on_device(tree, device) -> bool:
    return all(leaf.devices() == {device}
               for leaf in jax.tree_util.tree_leaves(tree)
               if isinstance(leaf, jax.Array))


def _share_changed(before, after) -> float:
    a = jax.tree_util.tree_leaves(before)
    b = [np.asarray(x) for x in jax.tree_util.tree_leaves(after)]
    return sum(not np.array_equal(x, y) for x, y in zip(a, b)) / len(a)


def phase_train(ctx: Ctx) -> dict:
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import \
        CollectScoresIterationListener
    from deeplearning4j_tpu.perf import DevicePrefetchIterator

    s = ctx.size
    net = _resnet50(ctx, s.side)
    scores = CollectScoresIterationListener()
    net.set_listeners(scores)
    before = jax.tree_util.tree_map(np.asarray, net.params)
    data = _image_batches(ctx, 4, s.batch, s.side, salt=1)
    t = Stopwatch().start()
    # the first batch pays the one compile of the step
    net.fit(DevicePrefetchIterator(ListDataSetIterator(data[:1], s.batch)))
    compile_s = lap(t, net.params)
    compiles = net.compile_watch.compiles("train")
    net.fit(DevicePrefetchIterator(ListDataSetIterator(data[1:], s.batch)))
    run_s = lap(t, net.params)
    losses = [v for _, v in scores.scores]
    check(len(losses) == 4 and np.all(np.isfinite(losses)),
          f"losses not finite: {losses}")
    check(len(set(losses)) == len(losses), f"loss not changing: {losses}")
    changed = _share_changed(before, net.params)
    # (a conv bias in front of a BatchNorm has a zero gradient)
    check(changed >= 0.9, f"only {changed:.0%} of parameter leaves changed")
    check(_on_device((net.params, net.state, net.opt_state), ctx.device),
          f"a parameter/state/updater leaf is not on {ctx.device}")
    check(compiles == 1 and net.compile_watch.compiles("train") == 1,
          f"train step compiled {compiles} then "
          f"{net.compile_watch.compiles('train')} times, expected 1 and 1")
    net.set_listeners()
    ctx.resnet = net
    return {"compile_seconds": compile_s, "run_seconds": run_s,
            "model": f"ResNet50 {s.side}x{s.side}x3 -> {s.classes}, "
                     f"bfloat16, batch {s.batch}",
            "steps": len(losses), "losses": [round(v, 4) for v in losses],
            "param_leaves_changed": round(changed, 3),
            "train_step_compiles": compiles,
            "dispatches": net.compile_watch.dispatches("train")}


# ------------------------------------------------------------------- serve
def _http(url: str, body=None, timeout: float = 300.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _in_threads(fns):
    """Run the client calls concurrently; a failure is re-raised here."""
    with ThreadPoolExecutor(len(fns)) as pool:
        return [f.result(600.0) for f in [pool.submit(fn) for fn in fns]]


def _lstm(ctx: Ctx):
    from deeplearning4j_tpu.models import TextGenerationLSTM
    return TextGenerationLSTM(units=ctx.size.lstm_units, seed=ctx.seed,
                              tbptt_length=ctx.size.tbptt).init()


def _greedy_vs_host_api(net, prompt, tokens):
    """Hold a served greedy stream to the stateful host API
    (``rnn_time_step``, one token at a time), teacher-forced along the
    served tokens: each must be the reference's argmax or tie with it.
    Returns (tokens that are the exact argmax, the worst relative margin
    by which one was not). The engine's batched slot step and the batch-1
    host step are different programs, and at the TPU's default matmul
    precision they round differently: with seeded random weights the
    distribution is near-uniform and a top-2 gap can sit inside that."""
    vocab = net.conf.layers[-1].n_out

    def step(tok):
        x = np.zeros((1, vocab), np.float32)
        x[0, tok] = 1.0
        return np.asarray(net.rnn_time_step(x)[0], np.float64)
    net.rnn_clear_previous_state()
    for tok in prompt:
        p = step(tok)
    exact, worst = 0, 0.0
    for tok in tokens:
        exact += int(tok == int(np.argmax(p)))
        worst = max(worst, float(1.0 - p[tok] / np.max(p)))
        p = step(tok)
    net.rnn_clear_previous_state()
    return exact, worst


def phase_serve(ctx: Ctx) -> dict:
    from deeplearning4j_tpu.perf import BucketPolicy
    from deeplearning4j_tpu.serving import ModelServer
    from deeplearning4j_tpu.serving.wire import encode_array

    s, rng = ctx.size, ctx.rng(2)
    net = ctx.resnet
    lstm = _lstm(ctx)
    # the two rungs of the serving ladder these requests pad to. Each
    # ResNet50 program is a long compile, so only these two are warmed,
    # and batch_limit=1 makes a dispatch one request: concurrent clients
    # cannot coalesce into a bucket that was not
    buckets = sorted({BucketPolicy().bucket(r) for r in s.serve_rows})
    check(len(buckets) == 2, f"requests {s.serve_rows} share a bucket")
    srv = ModelServer(port=0, max_body_bytes=32 << 20)
    srv.add_model("resnet50", net, batch_limit=1,
                  warmup_example=np.zeros((1, s.side, s.side, 3), np.float32),
                  warmup_buckets=buckets, default_deadline_ms=120_000.0)
    gen = srv.add_generator("char", lstm, max_sessions=8, min_slots=8,
                            prefill_buckets=(16,), seed=ctx.seed,
                            default_deadline_ms=120_000.0)
    t = Stopwatch().start()
    srv.start(warmup=True, warmup_async=False)
    try:
        compile_s = lap(t)
        base = srv.address
        status, ready = _http(base + "/readyz")
        check(status == 200, f"/readyz {status}: {ready}")
        pi = srv.endpoints["resnet50"].pi
        net_compiles = net.compile_watch.compiles()
        gen_compiles = dict(gen.engine.stats()["compiles"])

        # :predict — two of each size, from concurrent clients
        xs = [rng.standard_normal((b, s.side, s.side, 3)).astype(np.float32)
              for b in s.serve_rows for _ in range(2)]
        answers = _in_threads([
            (lambda x=x: _http(base + "/v1/models/resnet50:predict",
                               encode_array(x))) for x in xs])
        stats = pi.stats()
        check(net.compile_watch.compiles() == net_compiles
              and stats["unwarmed_dispatches"] == 0,
              f"ResNet50 compiled after warm-up: {net_compiles} -> "
              f"{net.compile_watch.compiles()}, unwarmed dispatches "
              f"{stats['unwarmed_dispatches']}")
        # the reference is the network's own output at the request's own,
        # unpadded shape: another program than the one that served it
        worst = 0.0
        for x, (status, body) in zip(xs, answers):
            check(status == 200, f":predict {status}: {body}")
            got = np.asarray(body["outputs"], np.float32)
            want = np.asarray(net.output_single(x), np.float32)
            check(got.shape == want.shape == (len(x), s.classes),
                  f":predict shape {got.shape} vs {want.shape}")
            check(np.all(np.isfinite(got)), ":predict output not finite")
            worst = max(worst, float(np.max(np.abs(got - want))))
        check(worst <= 1e-2, f":predict differs from net.output by {worst}")

        # :generate — concurrent sessions, greedy, against the host API
        prompts = [[int(v) for v in rng.integers(0, 47, n)]
                   for n in (3, 5, 9, 20)]
        answers = _in_threads([
            (lambda p=p: _http(base + "/v1/models/char:generate",
                               {"prompt_ids": p, "max_tokens": s.gen_tokens,
                                "temperature": 0.0, "stream": False}))
            for p in prompts])
        ref = _lstm(ctx)    # same seed, its own host-side rnn state
        exact, worst_tie = 0, 0.0
        for p, (status, body) in zip(prompts, answers):
            check(status == 200, f":generate {status}: {body}")
            ids = body["token_ids"]
            check(len(ids) == s.gen_tokens, f":generate sent {len(ids)} ids")
            n, margin = _greedy_vs_host_api(ref, p, ids)
            exact, worst_tie = exact + n, max(worst_tie, margin)
        # bf16 tolerance on a probability
        check(worst_tie <= 1e-2,
              f":generate chose a token {worst_tie:.3g} (relative) below "
              f"the argmax of sequential rnn_time_step")
        check(dict(gen.engine.stats()["compiles"]) == gen_compiles,
              f"decode engine compiled after warm-up: {gen_compiles} -> "
              f"{gen.engine.stats()['compiles']}")
        run_s = lap(t)
    finally:
        srv.stop(drain=True, drain_timeout_s=30.0)
    return {"compile_seconds": compile_s, "run_seconds": run_s,
            "predict_requests": len(xs),
            "predict_rows": list(s.serve_rows), "warmed_buckets": buckets,
            "predict_max_abs_diff_vs_net_output": worst,
            "generate_sessions": len(prompts),
            "generate_tokens_each": s.gen_tokens,
            "generate_tokens_exact_argmax": exact,
            "generate_worst_tie_margin": worst_tie,
            "lstm": f"TextGenerationLSTM 2x{s.lstm_units}, vocab 47",
            "compiles_after_warmup": 0,
            "decode_programs": gen_compiles}


# ------------------------------------------------------------------ legacy
def phase_legacy(ctx: Ctx) -> dict:
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.fetchers import synthetic_mnist
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models import LeNet
    from deeplearning4j_tpu.nlp import Word2Vec

    s, rng = ctx.size, ctx.rng(3)
    out, t = {}, Stopwatch().start()

    # LeNet: per-batch fit (prefetched) and the scan-fused multi-batch step
    batch, group = 64, 4
    x_np, y_np = synthetic_mnist(batch * group, seed=ctx.seed)
    sets = [DataSet(x_np[i * batch:(i + 1) * batch],
                    y_np[i * batch:(i + 1) * batch]) for i in range(group)]
    net = LeNet(num_classes=10, seed=ctx.seed).init()
    net.fit(ListDataSetIterator(sets, batch), num_epochs=2, prefetch=True)
    fit_score = float(net.score())
    check(np.isfinite(fit_score), f"LeNet fit score {fit_score}")
    check(net.compile_watch.compiles("train") == 1,
          f"LeNet fit compiled {net.compile_watch.compiles('train')} steps")
    xs = jnp.stack([jnp.asarray(d.features) for d in sets])
    ys = jnp.stack([jnp.asarray(d.labels) for d in sets])
    fused = LeNet(num_classes=10, seed=ctx.seed).init()
    for _ in range(3):      # donated buffers must survive re-dispatch
        fused.fit_fused((xs, ys))
    fused_score = float(fused.score())
    check(np.isfinite(fused_score), f"LeNet fit_fused score {fused_score}")
    check(fused.iteration == 3 * group,
          f"fit_fused advanced {fused.iteration} iterations")
    ctx.lenet = net
    out["lenet"] = {"fit_score": round(fit_score, 4),
                    "fit_fused_score": round(fused_score, 4),
                    "seconds": lap(t)}

    # GravesLSTM char-RNN: all tBPTT windows of a batch in one dispatch
    lstm = _lstm(ctx)
    windows, cb = 4, (8 if ctx.rehearse else 64)
    ids = rng.integers(0, 47, (cb, s.tbptt * windows + 1))
    eye = np.eye(47, dtype=np.float32)
    x, y = jnp.asarray(eye[ids[:, :-1]]), jnp.asarray(eye[ids[:, 1:]])
    scores = []
    for _ in range(3):
        lstm.fit_tbptt_fused(x, y)
        scores.append(float(lstm.score()))
    check(np.all(np.isfinite(scores)), f"char-RNN scores {scores}")
    check(scores[-1] != scores[0], f"char-RNN score not changing: {scores}")
    out["char_rnn"] = {"fit_tbptt_fused_scores":
                       [round(v, 4) for v in scores], "seconds": lap(t)}

    # Word2Vec SGNS, corpus resident on the device
    ranks = np.arange(1, s.w2v_vocab + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    words = np.array([f"w{i}" for i in range(s.w2v_vocab)])
    sents = [" ".join(words[row]) for row in
             rng.choice(s.w2v_vocab, (s.w2v_sentences, 20), p=probs)]
    w2v = Word2Vec(layer_size=128, window_size=5, negative=5, epochs=2,
                   batch_size=s.w2v_batch, min_word_frequency=1,
                   seed=ctx.seed, device_corpus=True)
    w2v.fit(sents)
    check(len(w2v.loss_history) == 2
          and np.all(np.isfinite(w2v.loss_history)),
          f"Word2Vec losses {w2v.loss_history}")
    vec = np.asarray(w2v.word_vector("w0"))
    check(vec.shape == (128,) and np.all(np.isfinite(vec)),
          "Word2Vec vector not finite")
    check(w2v.compile_watch.compiles("sgns_corpus_macro") == 1,
          f"sgns_corpus_macro compiled "
          f"{w2v.compile_watch.compiles('sgns_corpus_macro')} times")
    check(_on_device((w2v.syn0, w2v.syn1), ctx.device),
          "Word2Vec tables not on the device")
    # syn1 starts at zero: any entry away from it is a trained update
    check(float(np.max(np.abs(np.asarray(w2v.syn1)))) > 0,
          "Word2Vec output table never moved")
    out["word2vec"] = {"losses": [round(v, 6) for v in w2v.loss_history],
                       "words": int(w2v.vocab.total_word_occurrences),
                       "seconds": lap(t)}
    return out


# ----------------------------------------------------------------- kernels
def _has_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _ids_agree(a, b) -> float:
    return float(np.mean(np.asarray(a) == np.asarray(b)))


def _relative_gaps(got, want, what: str) -> list:
    """Leaf by leaf, |got - want| / |want| by norms; ``got`` has to be
    finite."""
    gaps = []
    for a, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, w = (np.asarray(x, np.float64) for x in (a, w))
        check(np.all(np.isfinite(a)), f"{what}: not finite")
        gaps.append(float(np.linalg.norm(a - w)
                          / max(np.linalg.norm(w), 1e-30)))
    return gaps


def phase_kernels(ctx: Ctx) -> dict:
    from deeplearning4j_tpu.nlp.pallas_scatter import scatter_add_pallas
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.conf.convolutional import fused_bn_act_train
    from deeplearning4j_tpu.nn.conf.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.perf import pallas as pk
    from deeplearning4j_tpu.perf.pallas import adc
    from deeplearning4j_tpu.quant import calibrate, quantize
    from deeplearning4j_tpu.retrieval import (BruteForceIndex, PQIndex,
                                              synthetic_corpus)
    from deeplearning4j_tpu.retrieval.index import _score_brute_int4
    from deeplearning4j_tpu.retrieval.pq import _score_pq

    s, rng, t = ctx.size, ctx.rng(4), Stopwatch().start()
    chip = not ctx.rehearse
    snapshot = pk.selection_snapshot()
    out = {"selection": snapshot, "interpret": pk.interpret()}
    if chip:
        check(not pk.interpret(), "Pallas would interpret on the chip")
        check({f for f, v in snapshot.items() if v == "pallas"}
              == set(pk.TPU_AUTO_FAMILIES),
              f"default selection {snapshot} is not TPU_AUTO_FAMILIES")
    # the default selection on the chip; forced (interpreted) in rehearsal
    auto = pk.override() if chip else pk.override(enabled=True)
    n, b, k = s.table_rows, 64, 16

    with auto:
        # ---- adc_pq: the kernel at table scale, then the index call site
        q = jnp.asarray(rng.standard_normal((b, 64)), jnp.float32)
        books = jnp.asarray(rng.standard_normal((8, 256, 8)), jnp.float32)
        codes = jnp.asarray(rng.integers(0, 256, (n, 8)), jnp.uint8)
        check(pk.take("adc_pq", adc.pq_supported(q, books, codes)),
              "adc_pq not selected at the table shape")
        d_k, i_k = adc.score_pq(q, books, codes, k=k)
        d_r, i_r = _score_pq(q, books, codes, k=k)
        check(np.allclose(d_k, d_r, rtol=1e-5, atol=1e-5),
              "adc_pq distances differ from the XLA reference")
        check(_ids_agree(i_k, i_r) >= 0.99, "adc_pq ids differ")
        if chip:
            check(_has_kernel(lambda *a: adc.score_pq(*a, k=k), q, books,
                              codes), "adc_pq: no Mosaic kernel compiled")
        V, Q = synthetic_corpus(s.index_rows, 64, n_clusters=32,
                                seed=ctx.seed, queries=b)
        ix = PQIndex(V, M=8, ksub=256 if chip else 16, seed=ctx.seed)
        got = ix.search(Q, 10)
        with pk.override(enabled=False):
            want = ix.search(Q, 10)
        counts = ix.compile_watch.counters("kernel.")
        check(counts.get("kernel.pallas_adc_pq", 0) >= 1
              and counts.get("kernel.xla_adc_pq", 0) >= 1,
              f"PQIndex kernel counters {counts}")
        check(_ids_agree(got[1], want[1]) >= 0.99
              and np.allclose(got[0], want[0], rtol=1e-5, atol=1e-5),
              "PQIndex answers differ between the Pallas and XLA arms")
        out["adc_pq"] = {"table": [n, 8], "queries": b,
                         "ids_agree": _ids_agree(i_k, i_r),
                         "index_counters": counts, "seconds": lap(t)}

        # ---- int4_dot: table scorer, index call site, int4 weights
        packed = jnp.asarray(rng.integers(-128, 128, (n, 32)), jnp.int8)
        vn = jnp.asarray(rng.random(n) * 10 + 1, jnp.float32)
        sv = jnp.asarray(rng.random(n) * 0.1 + 0.01, jnp.float32)
        check(pk.take("int4_dot",
                      adc.brute_int4_supported(q, packed)),
              "int4_dot not selected at the table shape")
        d_k, i_k = adc.score_brute_int4(q, packed, vn, sv, k=k,
                                        metric="euclidean")
        d_r, i_r = _score_brute_int4(q, packed, vn, sv, k=k,
                                     metric="euclidean")
        check(np.allclose(d_k, d_r, rtol=1e-5, atol=1e-5),
              "int4_dot distances differ from the XLA reference")
        check(_ids_agree(i_k, i_r) >= 0.99, "int4_dot ids differ")
        if chip:
            check(_has_kernel(lambda *a: adc.score_brute_int4(
                *a, k=k, metric="euclidean"), q, packed, vn, sv),
                "int4_dot: no Mosaic kernel compiled")
        bx = BruteForceIndex(V, int4=True)
        got = bx.search(Q, 10)
        with pk.override(enabled=False):
            want = bx.search(Q, 10)
        counts = bx.compile_watch.counters("kernel.")
        check(counts.get("kernel.pallas_int4_dot", 0) >= 1,
              f"BruteForceIndex(int4) kernel counters {counts}")
        check(_ids_agree(got[1], want[1]) >= 0.99,
              "int4 index answers differ between the arms")
        # int4 weights: the LeNet the legacy phase trained, both arms
        x_img = np.asarray(rng.random((32, 28, 28, 1)), np.float32)
        rec = calibrate(ctx.lenet, [x_img])
        q4 = quantize(ctx.lenet, rec, weight_bits=4)
        y_k = np.asarray(q4.output(x_img))
        with pk.override(enabled=False):
            y_r = np.asarray(quantize(ctx.lenet, rec,
                                      weight_bits=4).output(x_img))
        wcounts = q4.compile_watch.counters("kernel.")
        check(wcounts.get("kernel.pallas_int4_dot", 0) >= 1,
              f"int4-weight kernel counters {wcounts}")
        check(np.allclose(y_k, y_r, rtol=1e-5, atol=1e-6),
              "int4-weight outputs differ between the arms")
        out["int4_dot"] = {"table": [n, 32], "queries": b,
                           "ids_agree": _ids_agree(i_k, i_r),
                           "index_counters": counts,
                           "weight_counters": wcounts, "seconds": lap(t)}

        # ---- kda_scan and blocked_attention: each family's kernels, forward
        # and backward, against the jax.numpy form of the same call
        from deeplearning4j_tpu.nn.conf.attention import (
            blocked_causal_attention)
        from deeplearning4j_tpu.nn.conf.linear_attention import chunked_kda
        from deeplearning4j_tpu.perf.compile_watch import GLOBAL
        from deeplearning4j_tpu.perf.pallas import attention as attn_kernels
        from deeplearning4j_tpu.perf.pallas import kda
        cdt = jnp.bfloat16 if chip else jnp.float32

        def both_arms(name, family, fn, args, **report):
            """``fn(*args)``'s output and every gradient with the family's
            kernels and as plain jax.numpy: one call counted on each arm,
            and the arms agree to their own rounding (both round their
            default-precision products, p and ds to bfloat16 on the chip:
            they agree to that, not to float32's)."""
            def loss(*a):
                o = fn(*a).astype(jnp.float32)
                return jnp.sum(jnp.sin(o)), o
            grad = jax.value_and_grad(loss, range(len(args)), has_aux=True)
            before = dict(GLOBAL.as_dict().get("counters", {}))
            got = grad(*args)
            with pk.override(enabled=False):
                want = grad(*args)
            counts = {key: val - before.get(key, 0) for key, val in
                      GLOBAL.as_dict()["counters"].items() if family in key}
            check(counts == {f"kernel.pallas_{family}": 1,
                             f"kernel.xla_{family}": 1},
                  f"{name}: {family} kernel counters {counts}")
            gaps = _relative_gaps(got, want, name)
            check(max(gaps) < (2e-2 if chip else 1e-4),
                  f"{name} differs from the jax.numpy form: {gaps}")
            if chip:
                check(_has_kernel(fn, *args),
                      f"{name}: no Mosaic kernel compiled")
            out[name] = {**report, "counters": counts,
                         "worst_relative_gap": max(gaps), "seconds": lap(t)}

        def scan_inputs(shape, seed, decay_shape):
            keys = jax.random.split(jax.random.key(seed), 5)
            q, k, v = (jax.random.normal(key, shape) for key in keys[:3])
            k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
            return (0.1 * q.astype(cdt), k.astype(cdt), v.astype(cdt),
                    -jax.random.uniform(keys[3], decay_shape),
                    jax.random.uniform(keys[4], shape[:3]))

        # Kimi Delta Attention's call: a decay a channel, heads of 128
        shape = (1, s.attn_seq + 40, 8 if chip else 2, 128)
        kargs = scan_inputs(shape, ctx.seed, shape)
        check(kda.supported(*kargs, 64, 8),
              "kda_scan does not take heads of 128 in chunks of 64")
        both_arms("kda_scan", "kda_scan", chunked_kda, kargs,
                  shape=list(shape))
        # Gated DeltaNet's call at the Qwen3-Next cell's shape: ONE decay a
        # head spread over its channels (its cotangent summed back), 16 q/k
        # heads repeated to 32 value heads
        steps, hk, hv = (8192, 16, 32) if chip else (s.attn_seq + 40, 1, 2)
        q, k, v, g, b = scan_inputs((1, steps, hv, 128), ctx.seed + 2,
                                    (1, steps, hv))

        def scalar_decay_scan(q, k, v, g, b):
            q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
            return chunked_kda(q, k, v, jnp.broadcast_to(g[..., None],
                                                         q.shape), b)
        both_arms("kda_scan_scalar_decay", "kda_scan", scalar_decay_scan,
                  (q[:, :, :hk], k[:, :, :hk], v, g, b),
                  shape=[1, steps, hk, hv, 128])

        # the Mamba-2 scan at the Granite cell's shape: 64 heads of 64 over
        # one shared 128-wide B and C, 8192 steps in chunks of 256, steps
        # log-uniform in [1e-3, 1e-1] and A = -1..-heads as the public
        # initialiser draws them
        from deeplearning4j_tpu.nn.conf.state_space import chunked_ssd
        from deeplearning4j_tpu.perf.pallas import ssd
        steps, heads, chunk = (8192, 64, 256) if chip else (256, 2, 128)
        keys = jax.random.split(jax.random.key(ctx.seed + 4), 4)
        x, bm, cm = (jax.random.normal(key, (1, steps) + tail).astype(cdt)
                     for key, tail in zip(keys, ((heads, 64), (1, 128),
                                                 (1, 128))))
        sargs = (x, jnp.exp(jax.random.uniform(
            keys[3], (1, steps, heads), minval=math.log(1e-3),
            maxval=math.log(1e-1))),
            -jnp.arange(1, heads + 1, dtype=jnp.float32), bm, cm)
        check(ssd.supported(*sargs, chunk),
              "ssd_scan does not take heads of 64 in chunks of 256")
        both_arms("ssd_scan", "ssd_scan",
                  lambda *a: chunked_ssd(*a, chunk=chunk), sargs,
                  shape=[1, steps, heads, 64, 128], chunk=chunk)

        # the Mamba-1 selective scan at the Phi-4-mini-flash cell's shape:
        # 5,120 channels x 16 states, 8,192 steps, a D term, steps
        # log-uniform in [1e-3, 1e-1] and A = -1..-N a channel as the public
        # initialiser draws them: output and six gradients
        from deeplearning4j_tpu.nn.conf.state_space import (
            chunked_selective_scan)
        from deeplearning4j_tpu.perf.pallas import selective_scan
        steps, channels = (8192, 5120) if chip else (256, 128)
        keys = jax.random.split(jax.random.key(ctx.seed + 5), 5)
        x, bm, cm = (jax.random.normal(key, (1, steps, width)).astype(cdt)
                     for key, width in zip(keys, (channels, 16, 16)))
        margs = (x, jnp.exp(jax.random.uniform(
            keys[3], (1, steps, channels), minval=math.log(1e-3),
            maxval=math.log(1e-1))),
            -jnp.broadcast_to(jnp.arange(1, 17, dtype=jnp.float32),
                              (channels, 16)), bm, cm,
            jax.random.normal(keys[4], (channels,)))
        check(selective_scan.supported(*margs),
              "selective_scan does not take 16 states over whole lane tiles")
        both_arms("selective_scan", "selective_scan",
                  lambda *a: chunked_selective_scan(*a[:5], skip=a[5]),
                  margs, shape=[1, steps, channels, 16])

        # latent attention's call: q/k heads of 192 and v heads of 128,
        # several tiles, a length that is padded
        heads, steps = (8, 3 * s.attn_seq - 24) if chip else (2, 360)
        block = s.attn_seq // 2 if chip else 128
        keys = jax.random.split(jax.random.key(ctx.seed + 1), 3)
        aargs = tuple(jax.random.normal(key, (2, heads, steps, width), cdt)
                      for key, width in zip(keys, (192, 192, 128)))
        padded = tuple(jnp.pad(a, ((0, 0), (0, 0), (0, -steps % block),
                                   (0, 0))) for a in aargs)
        check(attn_kernels.supported(*padded, block),
              "blocked_attention does not take heads of 192 / 128")
        both_arms("blocked_attention", "blocked_attention",
                  lambda *a: blocked_causal_attention(*a, block), aargs,
                  shape=[2, heads, steps, 192, 128], block=block)
        # gated attention's call at the Qwen3-Next cell's shape: 16 query
        # heads of 256 over 2 k/v heads repeated in front of the kernels
        # (dk, dv summed over the group on the way back)
        h, hkv, steps, width, block = ((16, 2, 8192, 256, 512) if chip
                                       else (4, 2, 360, 64, 128))
        keys = jax.random.split(jax.random.key(ctx.seed + 3), 3)
        gargs = tuple(jax.random.normal(key, (1, n, steps, width), cdt)
                      for key, n in zip(keys, (h, hkv, hkv)))

        def grouped_attention(q, k, v):
            k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
            return blocked_causal_attention(q, k, v, block)
        both_arms("blocked_attention_grouped", "blocked_attention",
                  grouped_attention, gargs,
                  shape=[1, h, hkv, steps, width], block=block)

        # ---- kda_inputs: the delta-rule layers with their input path as
        # kernels (and the scan on their heads-major operands) against the
        # layers' jax.numpy lines, at the token cells' head counts and a
        # length that is padded; gradients of every parameter and the input
        from deeplearning4j_tpu.nn.conf.linear_attention import (
            GatedDeltaNet, KimiDeltaAttention)
        steps, width = (2048 + 40, 256) if chip else (s.attn_seq + 40, 12)
        hk, hv = (16, 32) if chip else (1, 2)
        for name, layer in (
                ("kda_inputs", KimiDeltaAttention(
                    n_heads=hv, head_dim=128, low_rank=16)),
                ("kda_inputs_gated_delta_net", GatedDeltaNet(
                    n_key_heads=hk, n_value_heads=hv, head_dim=128))):
            params, state = layer.init(jax.random.key(ctx.seed + 5),
                                       InputType.recurrent(width, steps))
            params = jax.tree_util.tree_map(lambda a: a.astype(cdt), params)
            x = jax.random.normal(jax.random.key(ctx.seed + 6),
                                  (1, steps, width)).astype(cdt)
            both_arms(name, "kda_inputs",
                      lambda p, x, layer=layer, state=state: layer.apply(
                          p, state, x)[0], (params, x),
                      shape=[1, steps, width, hk, hv, 128])

    # ---- bn_act / bn_act_bwd: NOT in the default selection; run under an
    # explicit override where supported() says the rows fit
    z = jnp.asarray(rng.standard_normal((64, 7, 7, 512)), jnp.bfloat16)
    gamma = jnp.asarray(rng.random(512) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.standard_normal(512), jnp.float32)

    def bn_loss(z, gamma, beta):
        y, mean, var = fused_bn_act_train("relu", 1e-5, z, gamma, beta, None)
        return jnp.sum(y.astype(jnp.float32) ** 2), (y, mean, var)
    arms = {}
    for arm in (False, True):
        with pk.override(enabled=arm):
            arms[arm] = jax.value_and_grad(bn_loss, (0, 1, 2),
                                           has_aux=True)(z, gamma, beta)
    for got, want in zip(jax.tree_util.tree_leaves(arms[True]),
                         jax.tree_util.tree_leaves(arms[False])):
        got, want = (np.asarray(v, np.float32) for v in (got, want))
        check(np.allclose(got, want, rtol=2e-2,
                          atol=2e-2 * float(np.max(np.abs(want)))),
              "bn_act fwd/bwd differs from the XLA reference")
    out["bn_act_explicit"] = {"shape": list(z.shape), "seconds": lap(t)}

    # ---- flash attention through the layer, at seq >= 128
    seq, width = s.attn_seq, 512
    conf = (NeuralNetConfiguration.builder().seed(ctx.seed)
            .dtype("bfloat16").list()
            .layer(SelfAttentionLayer(n_out=width, n_heads=8, causal=True))
            .layer(RnnOutputLayer(n_out=8))
            .set_input_type(InputType.recurrent(width, seq)).build())
    attn = MultiLayerNetwork(conf).init()
    xa = np.asarray(rng.standard_normal((4, seq, width)), np.float32)
    y_flash = np.asarray(attn.output(xa), np.float32)
    # an all-ones mask takes the dense path by design: the reference
    y_dense = np.asarray(attn.output(
        xa, features_mask=np.ones((4, seq), np.float32)), np.float32)
    acounts = attn.compile_watch.counters("attention.")
    if chip:
        check(acounts.get("attention.flash", 0) == 1
              and not acounts.get("attention.flash_fallback"),
              f"flash kernel was not the branch taken: {acounts}")
    check(np.allclose(y_flash, y_dense, rtol=5e-2, atol=5e-2),
          f"flash vs dense attention differ by "
          f"{np.max(np.abs(y_flash - y_dense))}")
    out["flash_attention"] = {"shape": [4, 8, seq, width // 8],
                              "counters": acounts, "seconds": lap(t)}

    # ---- Word2Vec scatter-add
    rows = s.scatter_rows
    table = jnp.asarray(rng.standard_normal((rows, 100)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, rows, 8192), jnp.int32)
    grads = jnp.asarray(rng.standard_normal((8192, 100)), jnp.float32)
    want = np.asarray(table.at[idx].add(grads))
    if chip:
        check(_has_kernel(scatter_add_pallas, table, idx, grads),
              "scatter: no Mosaic kernel compiled")
    got = np.asarray(scatter_add_pallas(table, idx, grads))
    check(np.allclose(got, want, rtol=1e-4, atol=1e-4),
          "Pallas scatter-add differs from .at[].add")
    out["w2v_scatter"] = {"table": [rows, 100], "seconds": lap(t)}
    return out


# --------------------------------------------------------------- multichip
def phase_multichip(ctx: Ctx) -> dict:
    """ParallelWrapper on four real chips against one device, same seed."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import \
        CollectScoresIterationListener
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.mesh import (MODEL_AXIS, make_mesh,
                                                  shard_batch)
    from deeplearning4j_tpu.perf import compile_watch

    devices = jax.devices()
    check(len(devices) >= 4, f"needs 4 devices, found {len(devices)} "
                             f"(no virtual mesh): {devices}")
    s = ctx.size
    batch = s.mc_batch
    data = _image_batches(ctx, 3, batch, s.mc_side, salt=5)

    def run(wrap):
        """3 steps from the seed; returns (net, losses, compiled text of
        the train step as dispatched, seconds)."""
        net = _resnet50(ctx, s.mc_side)
        scores = CollectScoresIterationListener()
        net.set_listeners(scores)
        seen = {}

        def grab(key, fn, args, kwargs, compiled):
            if key == "train" and "args" not in seen:
                seen["fn"] = fn
                # (an uncommitted array, such as an updater's step count,
                # goes wherever the committed arguments are)
                seen["args"] = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype,
                        sharding=a.sharding if a.committed else None),
                    (args, kwargs))
        compile_watch.add_dispatch_observer(grab)
        t = Stopwatch().start()
        try:
            wrap(net).fit(ListDataSetIterator(data, batch))
            secs = lap(t, net.params)
        finally:
            compile_watch.remove_dispatch_observer(grab)
        losses = [v for _, v in scores.scores]
        check(len(losses) == 3 and np.all(np.isfinite(losses)),
              f"losses {losses}")
        a, kw = seen["args"]
        text = seen["fn"].lower(*a, **kw).compile().as_text()
        return net, losses, text, secs

    out = {"model": f"ResNet50 {s.mc_side}x{s.mc_side}x3 -> {s.classes}, "
                    f"bfloat16, global batch {batch}"}
    _, base, _, secs = run(lambda net: net)
    out["one_device"] = {"losses": [round(v, 4) for v in base],
                         "seconds": secs}
    for name, dp, tp in (("dp4", 4, 1), ("dp2_tp2", 2, 2)):
        mesh = make_mesh(dp=dp, tp=tp, devices=devices[:4])
        net, losses, text, secs = run(lambda net: ParallelWrapper(
            net, mesh=mesh, tensor_parallel=tp > 1))
        # bf16 tolerance: the first step is the same forward; after it
        # Adam's g/sqrt(v) turns rounding in near-zero gradients into
        # full-size steps, so later losses drift further apart
        check(np.allclose(losses[0], base[0], rtol=3e-2)
              and np.allclose(losses, base, rtol=2e-1),
              f"{name} losses {losses} vs one device {base}")
        check("all-reduce" in text, f"{name}: no all-reduce in the step")
        xb = shard_batch(mesh, data[0].features)
        rows = sorted(sh.data.shape[0] for sh in xb.addressable_shards)
        check(len({sh.device for sh in xb.addressable_shards}) == 4
              and rows == [batch // dp] * 4,
              f"{name}: batch shards {rows} on "
              f"{[sh.device for sh in xb.addressable_shards]}")
        report = {"losses": [round(v, 4) for v in losses], "seconds": secs,
                  "batch_shard_rows": rows, "all_reduce": True}
        if tp > 1:
            split = [leaf for leaf in jax.tree_util.tree_leaves(net.params)
                     if MODEL_AXIS in jax.tree_util.tree_leaves(
                         tuple(leaf.sharding.spec))]
            check(split, f"{name}: no parameter is sharded over 'model'")
            kernel = max(split, key=lambda a: a.size)
            shard_shapes = {tuple(sh.data.shape)
                            for sh in kernel.addressable_shards}
            check(len({sh.device for sh in kernel.addressable_shards}) == 4
                  and shard_shapes == {kernel.shape[:-1]
                                       + (kernel.shape[-1] // tp,)},
                  f"{name}: kernel {kernel.shape} shards {shard_shapes}")
            report["sharded_param_leaves"] = len(split)
            report["largest_kernel"] = {"shape": list(kernel.shape),
                                        "shard": list(shard_shapes.pop())}
        out[name] = report
    return out


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the ParallelWrapper path on four chips")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on whatever backend there is; the "
                        "last line names it")
    args = p.parse_args(argv)
    if args.rehearse and args.chips == 4:
        # four virtual devices where the CPU is the backend
        jax.config.update("jax_num_cpu_devices", 4)
    enable_compilation_cache()
    ctx = Ctx(args)
    phases = [phase_device] + (
        [phase_multichip] if args.chips == 4 else
        [phase_train, phase_serve, phase_legacy, phase_kernels])
    for fn in phases:
        line = {"phase": fn.__name__[len("phase_"):], "ok": False}
        sw = Stopwatch().start()
        try:
            checked = fn(ctx)
            line["ok"] = True
        except Exception as e:
            traceback.print_exc()
            checked = {"error": f"{type(e).__name__}: {e}"}
        line["seconds"] = lap(sw)
        for key in ("compile_seconds", "run_seconds"):
            if key in checked:
                line[key] = checked.pop(key)
        line["compile_cache_hits"] = cache_hits()
        line["checked"] = checked
        print(json.dumps(line), flush=True)
        if not line["ok"]:
            return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

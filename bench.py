"""Benchmarks for the BASELINE configs, on one TPU chip.

Covers BASELINE.json configs[0]-[3] plus the serving microbench:
  0. LeNet MultiLayerNetwork on MNIST            -> imgs/sec
  1. ResNet50 ComputationGraph (north star)      -> imgs/sec (+ MFU estimate)
  2. GravesLSTM char-RNN (tBPTT windows)         -> chars/sec
  3. Word2Vec skip-gram negative sampling        -> words/sec
  4. ParallelInference serving (concurrent clients, mixed request sizes)
                                                 -> req/sec + p50/p99 latency,
                                                    batch-size summary, compiles
  4b. serving_load: open-loop Poisson HTTP load against serving.ModelServer
                                                 -> goodput, p50/p99, shed +
                                                    expired rates, occupancy
  5. Checkpoint overhead (checkpoint/ subsystem) -> steps/sec off vs async
                                                    vs sync save_every_n_steps

The reference repo publishes no numbers (BASELINE.md); each ``vs_baseline``
is reported against a fixed nominal V100-era denominator so the ratio is
meaningful across rounds.

Prints ONE JSON line per benchmark; the north-star ResNet50 line prints
last. Set BENCH_QUICK=1 for a tiny smoke run (CI / CPU);
BENCH_ONLY=lenet,serving (comma list of bench names) restricts which
benches run — the tier-1 smoke test uses it to exercise the bucketing +
prefetch hot paths end-to-end without paying for the ResNet compile.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from deeplearning4j_tpu.obs import Stopwatch

QUICK = os.environ.get("BENCH_QUICK") == "1"

REPS = 1 if QUICK else 3

_REPS_NOTE = "best of %d timed repetitions" % REPS

# bf16 peak FLOP/s by device_kind (Google Cloud documentation, "TPU v5e").
# A device that is not here gets no mfu field.
PEAK_FLOPS = {"TPU v5 lite": 197e12}


def _best_of(fn):
    """fn() -> elapsed seconds; returns the fastest of REPS repetitions."""
    return min(fn() for _ in range(REPS))

# Nominal V100-era denominators (the reference publishes nothing; these are
# order-of-magnitude figures for the CUDA stacks of that generation).
NOMINAL = {
    "lenet": 10_000.0,      # imgs/sec, LeNet MNIST
    "resnet50": 360.0,      # imgs/sec, fp32 V100 ResNet50 ImageNet
    "charlstm": 100_000.0,  # chars/sec, cuDNN LSTM char-RNN
    "word2vec": 500_000.0,  # words/sec, multithreaded host SGNS
    "serving": 10_000.0,    # req/sec, nominal GPU dynamic-batching server
    "checkpoint": 1_000.0,  # steps/sec, nominal small-model step loop
    "resilience": 100.0,    # ms, nominal small-model restore/swap budget
    "elastic": 1_000.0,     # ms, nominal membership-transition budget
    "compression": 4.0,     # x, byte-reduction bar for the default
                            # threshold policy (the DCN-win acceptance)
    "quant": 4.0,           # x, ideal int8 model-byte reduction (the
                            # acceptance bar is >= 3x after scale/bias
                            # overhead)
    "data_plane": 1_000_000.0,  # records/sec, nominal host-side ETL
                                # throughput for small-record corpora
    "data_plane_claim": 1_000.0,  # us, nominal one-RTT object-store
                                  # lease claim budget
    "data_plane_wait": 10.0,    # %, nominal data-wait share of a fit
                                # epoch before prefetch tuning
    "data_lake": 1_000_000.0,   # records/sec, same host-ETL nominal as
                                # data_plane — the lake arms show what
                                # the wire + cache tiers cost vs it
    "data_lake_restore": 100.0,  # ms, nominal small-model restore budget
                                 # (the resilience figure, now per tier)
    "retrieval": 10_000.0,      # queries/sec, nominal GPU brute-force
                                # ANN server at ~100k vectors
    "autotune": 1.0,            # x, tuned-vs-default step-time ratio
                                # (>= 1 means the record's choice is at
                                # least as fast as the default execution)
    "fleet": 5.0,               # ms, nominal router-hop overhead budget
                                # (one lease-table lookup + one proxied
                                # loopback HTTP round trip)
    "fleet_scaleup": 10.0,      # s, nominal cold-replica time-to-ready
                                # (restore + TuningRecord ladder warmup,
                                # no serve-path compiles)
    "decode": 1_000.0,          # tokens/sec, nominal GPU streaming-decode
                                # aggregate for a small char-RNN serving
                                # tier (~1ms/token budget)
    "pallas": 1.0,              # x, identity denominator: bench_pallas
                                # metrics come in kernel-on/off PAIRS and
                                # the on-arm's speedup_vs_off field is the
                                # signal, not vs_baseline
}


def _device_fields():
    """What every emitted line says about where it ran."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind}


def _mfu_fields(achieved_flops):
    """``{"mfu": ...}`` on a device whose peak is known, else nothing."""
    peak = PEAK_FLOPS.get(_device_fields()["device_kind"])
    return {} if peak is None else {"mfu": round(achieved_flops / peak, 4)}


def emit(metric, value, unit, baseline_key, **extra):
    line = {"metric": metric, "value": round(value, 1), "unit": unit,
            "vs_baseline": round(value / NOMINAL[baseline_key], 3)}
    line.update(_device_fields())
    line.update(extra)
    print(json.dumps(line), flush=True)


def bench_lenet():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.fetchers import synthetic_mnist
    from deeplearning4j_tpu.models import LeNet

    batch = 64 if QUICK else 256
    # LeNet at batch 256 has ~1 ms of device compute per step: train
    # through fit_fused, the framework's scan-fused multi-batch step
    # (exactly equivalent math, one dispatch per GROUP of minibatches)
    group = 2 if QUICK else 25
    n_groups, warmup_groups = (2, 1) if QUICK else (12, 2)
    net = LeNet(num_classes=10).init()
    x_np, y_np = synthetic_mnist(batch * 4, seed=7)
    xs = jnp.stack([jnp.asarray(x_np[(i % 4) * batch:(i % 4 + 1) * batch])
                    for i in range(group)])   # device-resident stack
    ys = jnp.stack([jnp.asarray(y_np[(i % 4) * batch:(i % 4 + 1) * batch])
                    for i in range(group)])

    def run_group():
        net.fit_fused((xs, ys))

    for _ in range(warmup_groups):
        run_group()
    float(net._score)

    def timed():
        t0 = time.perf_counter()
        for _ in range(n_groups):
            run_group()
        float(net._score)  # VALUE fetch forces the whole chain
        return time.perf_counter() - t0

    dt = _best_of(timed)
    emit("lenet_mnist_train_imgs_per_sec_per_chip",
         n_groups * group * batch / dt, "imgs/sec", "lenet",
         note="trained via fit_fused (scan-fused multi-batch step, exact "
              "same sequential-update math). "
              "Reference-equivalent per-batch fit() reported "
              "separately as ..._plain_fit. " + _REPS_NOTE)

    # plain per-batch fit(): reference MultiLayerNetwork.fit semantics —
    # one dispatch AND one listener firing per iteration (VERDICT r4 #7:
    # report both so the headline isn't an API users must opt into) — now
    # driven through DevicePrefetchIterator so batch N+1's device_put
    # overlaps step N (perf/prefetch.py)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    ds = [DataSet(x_np[(i % 4) * batch:(i % 4 + 1) * batch],
                  y_np[(i % 4) * batch:(i % 4 + 1) * batch])
          for i in range(group)]
    it = ListDataSetIterator(ds, batch)
    net2 = LeNet(num_classes=10).init()
    net2.fit(it, prefetch=True)  # compile + warmup
    float(net2._score)

    def timed_plain():
        t0 = time.perf_counter()
        net2.fit(it, num_epochs=n_groups, prefetch=True)
        float(net2._score)
        return time.perf_counter() - t0

    dt2 = _best_of(timed_plain)
    emit("lenet_mnist_train_imgs_per_sec_per_chip_plain_fit",
         n_groups * group * batch / dt2, "imgs/sec", "lenet",
         compiles=net2.compile_watch.compiles(),
         dispatches=net2.compile_watch.dispatches(),
         note="reference-equivalent fit(): one dispatch + per-iteration "
              "listener semantics per minibatch, host->device transfer "
              "double-buffered via DevicePrefetchIterator. "
              + _REPS_NOTE)


def _model_fwd_flops_per_image(net) -> float:
    """Forward FLOPs per image computed from the ACTUAL graph (convs +
    dense/output matmuls), counting one multiply-add as 2 FLOPs.

    Replaces the former hard-coded 4.1e9 constant, which was the standard
    ResNet50 multiply-ACCUMULATE count mislabelled as already-doubled FLOPs
    — it under-reported achieved TFLOP/s and MFU by ~1.88x (the true count
    for this graph is ~7.7e9). Methodology change recorded in the emitted
    ``note`` field (r4).
    """
    from deeplearning4j_tpu.nn.conf.convolutional import (
        ConvolutionLayer, FusedConvBNActivation)
    total = 0.0
    for name in net.order:
        obj, _ = net.vertices[name]
        it = net.vertex_input_types[name][0]
        if isinstance(obj, (ConvolutionLayer, FusedConvBNActivation)):
            from deeplearning4j_tpu.nn.conf.convolutional import _pair
            out_t = obj.output_type(it)
            kh, kw = _pair(obj.kernel_size)
            cin = obj.n_in or it.channels
            total += 2.0 * out_t.height * out_t.width * kh * kw * cin * obj.n_out
        elif hasattr(obj, "n_out") and hasattr(obj, "n_in") and \
                getattr(obj, "n_out", 0) and obj.__class__.__name__ in (
                    "DenseLayer", "OutputLayer"):
            n_in = obj.n_in or it.flat_size()
            total += 2.0 * n_in * obj.n_out
    return total


def _bench_resnet50_once(dtype: str, batch: int, side: int, warmup: int,
                         steps: int, fused: bool = False):
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    conf = _dc.replace(
        ResNet50(num_classes=1000, input_shape=(side, side, 3)).conf(),
        dtype=dtype)
    if fused:
        conf = conf.fused()  # conv→BN→act fused blocks (perf/fusion.py)
    net = ComputationGraph(conf).init()
    fwd_flops = _model_fwd_flops_per_image(net)
    step = net._get_jitted("train")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, side, side, 3), np.float32))
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, batch)])
    loss = None

    def run_one():
        nonlocal loss
        net._rng, k = jax.random.split(net._rng)
        net.params, net.state, net.opt_state, loss = step(
            net.params, net.state, net.opt_state, k, [x], [y], None, None)

    for _ in range(warmup):
        run_one()
    float(loss)  # hard sync: a VALUE fetch, stronger than block_until_ready

    def timed():
        t0 = time.perf_counter()
        for _ in range(steps):
            run_one()
        float(loss)  # forces the whole dependency chain of the last step
        return time.perf_counter() - t0

    # best-of over the SAME compiled step
    return steps * batch / _best_of(timed), fwd_flops


def bench_resnet50():
    if QUICK:
        batch, side, warmup, steps = 2, 64, 1, 2
    else:
        batch = int(os.environ.get("BENCH_RESNET_BATCH", "128"))
        # warmup 6: the first few post-compile steps run cold
        # (queue/alloc warmth)
        side, warmup, steps = 224, 6, 30
    # Training FLOPs ~ 3x fwd (fwd + dX + dW). Fwd FLOPs are computed from
    # the actual graph in _model_fwd_flops_per_image; mfu is reported
    # only where PEAK_FLOPS knows the device.
    notes = {
        "float32": (
            "fp32 ablation (tools/PROFILE_r5.md): 'default' matmul "
            "precision already lowers f32 convs to single bf16 MXU passes "
            "(forcing true-f32 multi-pass costs a further 1.6x); the "
            "deficit vs bf16 is doubled HBM bytes per activation crossing "
            "in a bandwidth-bound step — bf16 compute with f32 master "
            "weights is the measured-optimal mode."),
        "bfloat16": (
            "step sits within ~5% of the measured bandwidth floor: conv "
            "fwd+dW+dX alone = 29.2 ms (51.4% MFU ceiling); the ~16 ms "
            "non-conv remainder is BN-train stats/normalize/residual + BN "
            "backward re-reads, ~4.7 full activation-set HBM crossings "
            "(tools/PROFILE_r5.md) — practical cap ~0.33 MFU on this XLA "
            "build. FLOPs computed from the graph, 2 FLOPs/MAC; value-"
            "fetch sync."),
    }
    # fp32 secondary line first; bf16 (the TPU-idiomatic compute dtype) is
    # the headline and prints LAST
    for dtype, metric in (
            ("float32", "resnet50_imagenet_train_imgs_per_sec_per_chip_fp32"),
            ("bfloat16", "resnet50_imagenet_train_imgs_per_sec_per_chip")):
        imgs_per_sec, fwd_flops = _bench_resnet50_once(
            dtype, batch, side, warmup, steps)
        achieved = imgs_per_sec * 3 * fwd_flops
        emit(metric, imgs_per_sec, "imgs/sec", "resnet50", batch=batch,
             dtype=dtype, achieved_tflops=round(achieved / 1e12, 2),
             fwd_gflops_per_img=round(fwd_flops / 1e9, 2),
             note=notes[dtype] + " " + _REPS_NOTE, **_mfu_fields(achieved))


def bench_resnet50_fusion():
    """Fusion on/off ablation for the north-star model (perf/fusion.py):
    the same bf16 train step with the conv→BN→act chains left unfused vs
    rewritten into FusedConvBNActivation blocks whose custom-VJP BN
    backward recomputes x-hat instead of re-reading activation-sized
    saves. Emits one metric per mode (``..._fusion_{off,on}``) plus the
    jaxpr-derived training-activation-bytes each mode hands its backward —
    the HBM-traffic number the fusion attacks. Thresholds only on full
    runs (BASELINE notes): this hook exists so the next on-chip run
    records the attribution."""
    import dataclasses as _dc

    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.perf.fusion import training_activation_bytes

    if QUICK:
        batch, side, warmup, steps = 2, 64, 1, 2
    else:
        batch = int(os.environ.get("BENCH_RESNET_BATCH", "128"))
        side, warmup, steps = 224, 6, 30
    conf = _dc.replace(
        ResNet50(num_classes=1000, input_shape=(side, side, 3)).conf(),
        dtype="bfloat16")
    act_bytes = {}
    for fused, tag in ((False, "off"), (True, "on")):
        c = conf.fused() if fused else conf
        act_bytes[tag] = int(training_activation_bytes(c, minibatch=batch))
    for fused, tag in ((False, "off"), (True, "on")):
        imgs_per_sec, fwd_flops = _bench_resnet50_once(
            "bfloat16", batch, side, warmup, steps, fused=fused)
        achieved = imgs_per_sec * 3 * fwd_flops
        emit(f"resnet50_imagenet_train_imgs_per_sec_per_chip_fusion_{tag}",
             imgs_per_sec, "imgs/sec", "resnet50", batch=batch,
             dtype="bfloat16", fusion=tag,
             achieved_tflops=round(achieved / 1e12, 2),
             training_activation_bytes=act_bytes[tag],
             note="fusion ablation (perf/fusion.py): identical math within "
                  "fp tolerance; training_activation_bytes is the "
                  "jaxpr-derived fwd->bwd residual set the BN-backward "
                  "traffic rides on. " + _REPS_NOTE,
             **_mfu_fields(achieved))


def bench_graveslstm():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import TextGenerationLSTM

    vocab = 47
    if QUICK:
        batch, T, windows, groups = 8, 16, 2, 1
    else:
        # one long document per group, trained through fit_tbptt_fused
        # (scan-fused windows, exact per-window tBPTT math)
        batch, T, windows, groups = 64, 50, 30, 3
    net = TextGenerationLSTM(total_unique_characters=vocab,
                             tbptt_length=T).init()
    rng = np.random.default_rng(0)
    seq_len = T * windows
    ids = rng.integers(0, vocab, (batch, seq_len))
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[ids])
    y = jnp.asarray(np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, (batch, seq_len))])

    def run_group():
        net.fit_tbptt_fused(x, y)

    run_group()                       # compile + warmup
    float(net._score)

    def timed():
        t0 = time.perf_counter()
        for _ in range(groups):
            run_group()
        float(net._score)
        return time.perf_counter() - t0

    dt = _best_of(timed)
    emit("graveslstm_charrnn_train_chars_per_sec_per_chip",
         groups * batch * seq_len / dt, "chars/sec", "charlstm",
         note="fit_tbptt_fused (all windows of a batch scan-fused into "
              "one dispatch, exact per-window tBPTT math). " + _REPS_NOTE)


def bench_word2vec():
    from deeplearning4j_tpu.nlp import Word2Vec

    rng = np.random.default_rng(0)
    if QUICK:
        n_sent, sent_len, vocab_n, batch = 200, 10, 500, 1024
    else:
        # 500k-word corpus (r5, was 100k): the corpus-resident device path
        # has a fixed per-fit cost (uploads + final loss fetch); the old
        # tiny corpus measured mostly that, not sustained throughput.
        n_sent, sent_len, vocab_n, batch = 25_000, 20, 10_000, 8192
    # zipf-ish unigram distribution over a synthetic vocab
    ranks = np.arange(1, vocab_n + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    words = np.array([f"w{i}" for i in range(vocab_n)])
    choice = rng.choice(vocab_n, (n_sent, sent_len), p=probs)
    sents = [" ".join(words[row]) for row in choice]
    model = Word2Vec(layer_size=128, window_size=5, negative=5, epochs=1,
                     batch_size=batch, min_word_frequency=1, seed=1)
    model.fit(sents)    # vocab + compile + warmup
    total_words = model.vocab.total_word_occurrences

    def timed():
        with Stopwatch() as sw:  # fit() syncs internally: vocab/vectors land on host
            model.fit(sents)
        return sw.seconds

    dt = _best_of(timed)
    emit("word2vec_sgns_train_words_per_sec_per_chip", total_words / dt,
         "words/sec", "word2vec",
         note="r5: corpus-resident device training — encoded corpus ships "
              "to HBM once (content-hash cached across fits/epochs, int16), "
              "pair windows AND negatives generated on-device from the "
              "unigram table (jax PRNG), shared-negative batches turn the "
              "negative accumulation into a dense matmul; segmented async "
              "dispatches overlap host indexing with device training. "
              "Throughput no longer scales with host->device bandwidth. "
              + _REPS_NOTE)


def bench_serving():
    """ParallelInference under concurrent clients with MIXED request sizes —
    the workload where shape bucketing pays: without it every distinct
    coalesced batch size is a fresh XLA compile mid-traffic. Reports
    latency percentiles, batch-size summary and compile counters in one
    JSON line (the shape-stability regression tripwire)."""
    import threading

    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.parallel import ParallelInference
    from deeplearning4j_tpu.perf import BucketPolicy

    if QUICK:
        n_clients, reqs_per_client, batch_limit, hidden = 4, 4, 16, 32
    else:
        n_clients, reqs_per_client, batch_limit, hidden = 16, 16, 32, 256
    n_features, n_classes = 784, 10
    conf = (NeuralNetConfiguration.builder()
            .seed(11).updater(Sgd(learning_rate=0.01)).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=n_classes, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_features))
            .build())
    net = MultiLayerNetwork(conf).init()
    policy = BucketPolicy(floor=8)
    pi = ParallelInference(net, batch_limit=batch_limit, queue_timeout_ms=3,
                           bucket_policy=policy)
    sizes = [1, 3, 7, 20, 4, 12, 2, 32][: max(4, batch_limit // 4)]
    # pre-compile every bucket BEFORE traffic (the serving contract).
    # batch_limit caps coalesced REQUESTS, not rows: the worst-case
    # dispatch is every in-flight client's largest request in one batch.
    max_rows = min(batch_limit, n_clients) * max(sizes)
    t0 = time.perf_counter()
    warmed = pi.warmup(np.zeros((1, n_features), np.float32),
                       buckets=policy.buckets_up_to(max_rows))
    warmup_s = time.perf_counter() - t0
    compiles_after_warmup = net.compile_watch.compiles()
    lat: list = []
    lat_lock = threading.Lock()

    def client(cid):
        r = np.random.default_rng(cid)
        for i in range(reqs_per_client):
            x = r.standard_normal(
                (sizes[(cid + i) % len(sizes)], n_features)).astype(np.float32)
            sw = Stopwatch().start()
            sw.stop(pi.output_batched(x))  # blocks on the observable's host array
            with lat_lock:
                lat.append(sw.seconds)

    def timed():
        sw = Stopwatch().start()  # joins client threads; every client is synced
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return sw.stop()

    dt = _best_of(timed)
    n_requests = n_clients * reqs_per_client
    st = pi.stats()
    pi.shutdown()
    emit("parallel_inference_serving_reqs_per_sec", n_requests / dt,
         "req/sec", "serving",
         p50_ms=round(float(np.percentile(lat, 50)) * 1000, 2),
         p99_ms=round(float(np.percentile(lat, 99)) * 1000, 2),
         requests=n_requests,
         batches_dispatched=st["batches_dispatched"],
         batch_size=st["batch_size"],
         warmed_buckets=warmed,
         warmup_s=round(warmup_s, 2),
         compiles=st.get("model_compiles"),
         compiles_after_warmup=compiles_after_warmup,
         unwarmed_dispatches=st["unwarmed_dispatches"],
         note="concurrent clients, request sizes cycling %s; every dispatch "
              "pads to a warmed bucket, so compiles == compiles_after_warmup "
              "must hold (shape-stability tripwire). " % sizes + _REPS_NOTE)


def bench_serving_load():
    """Open-loop serving load bench against the serving/ HTTP front-end:
    seeded POISSON arrivals at a configured offered load. Unlike the
    closed-loop bench_serving clients (whose arrival rate collapses to
    the service rate the moment the server slows), an open-loop generator
    keeps offering load under overload — which is exactly what exposes
    the admission-control story: goodput, p50/p99 latency, shed rate
    (429s), deadline expiries (504s) and batch occupancy at the offered
    rate. Metrics only on container runs per the 9p/bench-sensitivity
    note; thresholds belong to quiet full runs."""
    import threading
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.serving import ModelServer

    if QUICK:
        offered_rps, duration_s, deadline_ms, hidden = 60.0, 1.2, 1000.0, 32
    else:
        offered_rps, duration_s, deadline_ms, hidden = 400.0, 5.0, 250.0, 256
    n_features, n_classes = 784, 10
    conf = (NeuralNetConfiguration.builder()
            .seed(11).updater(Sgd(learning_rate=0.01)).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=n_classes, loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_features))
            .build())
    net = MultiLayerNetwork(conf).init()
    sizes = [1, 2, 4, 8]
    srv = ModelServer(default_deadline_ms=deadline_ms)
    ep = srv.add_model("mlp", net, queue_depth=64,
                       warmup_example=np.zeros((1, n_features), np.float32))
    # worst coalesced dispatch = batch_limit requests of the largest size;
    # warm the whole ladder so no live request pays an XLA compile
    ep.warmup_buckets = ep.pi.bucket_policy.buckets_up_to(
        ep.pi.batch_limit * max(sizes))
    srv.start(warmup_async=False)  # /readyz gating: ladder compiled first
    url = srv.address + "/v1/models/mlp:predict"
    # both predict encodings ride the same load mix: JSON float lists and
    # the binary wire format (base64 little-endian raw arrays). The byte
    # accounting below is exact for the request tensors in play; int8 is
    # the quantized-endpoint payload (same base64 framing, 1 byte/elem).
    import base64

    rng_x = np.random.default_rng(77)
    xs = [rng_x.standard_normal((s, n_features)).astype(np.float32)
          for s in sizes]
    payloads_json = [json.dumps({"inputs": x.tolist()}).encode()
                     for x in xs]
    payloads_b64 = [json.dumps(
        {"x_b64": base64.b64encode(x.tobytes()).decode(),
         "dtype": "float32", "shape": list(x.shape)}).encode() for x in xs]
    int8_bytes = [len(json.dumps(
        {"x_b64": base64.b64encode(
            x.astype(np.int8).tobytes()).decode(),
         "dtype": "int8", "shape": list(x.shape)}).encode()) for x in xs]
    json_bytes = sum(len(p) for p in payloads_json) / len(sizes)
    b64_bytes = sum(len(p) for p in payloads_b64) / len(sizes)
    i8_bytes = sum(int8_bytes) / len(sizes)
    # alternate encodings request to request: the binary decode path is
    # exercised under the same offered load as the JSON path
    payloads = [p for pair in zip(payloads_json, payloads_b64)
                for p in pair]
    results: list = []
    res_lock = threading.Lock()

    def fire(body):
        sw = Stopwatch().start()
        try:
            with urllib.request.urlopen(urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=30) as r:
                r.read()
                code = r.status
        except urllib.error.HTTPError as e:
            e.read()
            code = e.code
        except Exception:
            code = -1
        sw.stop()  # the HTTP response IS host-synced data
        with res_lock:
            results.append((code, sw.seconds))

    # the arrival schedule is drawn up front (seeded), then replayed on
    # the wall clock: arrivals never wait for completions (open loop)
    rng = np.random.default_rng(1234)
    arrivals, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / offered_rps))
        if t >= duration_s:
            break
        arrivals.append(t)
    threads = []
    sw_run = Stopwatch().start()
    start = time.perf_counter()
    for i, at in enumerate(arrivals):
        delay = start + at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=fire,
                              args=(payloads[i % len(payloads)],),
                              daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=60)
    wall = float(sw_run.stop())  # client threads joined: host-synced

    codes = [c for c, _ in results]
    ok_lat = [l * 1000.0 for c, l in results if c == 200]
    shed = codes.count(429)
    expired = codes.count(504)
    other = sum(1 for c in codes if c not in (200, 429, 504))
    st = srv.endpoints["mlp"].stats()
    srv.stop(drain=True)
    n = max(1, len(results))
    emit("serving_load_goodput_reqs_per_sec", len(ok_lat) / wall,
         "req/sec", "serving",
         offered_rps=offered_rps,
         arrivals=len(arrivals),
         ok=len(ok_lat), shed=shed, expired=expired, other=other,
         shed_rate=round(shed / n, 3),
         expired_rate=round(expired / n, 3),
         p50_ms=(round(float(np.percentile(ok_lat, 50)), 2)
                 if ok_lat else None),
         p99_ms=(round(float(np.percentile(ok_lat, 99)), 2)
                 if ok_lat else None),
         batch_occupancy=st["batch_size"],
         queue=st["queue"],
         payload_bytes={
             "json_f32": round(json_bytes),
             "b64_f32": round(b64_bytes),
             "b64_int8": round(i8_bytes),
             "json_to_b64_x": round(json_bytes / b64_bytes, 2),
             "json_to_int8_x": round(json_bytes / i8_bytes, 2),
         },
         note="open-loop seeded Poisson arrivals over HTTP at the offered "
              "rate (request sizes cycling %s, deadline %gms, JSON and "
              "binary-b64 encodings alternating); shed = 429 admission "
              "rejections, expired = 504 deadline evictions. payload_bytes "
              "= mean request body size per encoding over the size mix "
              "(b64_int8 is the quantized-endpoint wire format). "
              "metrics only — thresholds on quiet full runs per the 9p "
              "note. " % (sizes, deadline_ms) + _REPS_NOTE)


def bench_decode():
    """Generative decode tier: aggregate tokens/sec with N concurrent
    sessions through the continuous-batching DecodeEngine (one jitted
    step advances every active session per dispatch, sessions joining at
    token boundaries) vs the sequential per-session ``rnn_time_step``
    loop on the SAME model — the measured-throughput gap ISSUE 19 exists
    to close. Also reports client-side time-to-first-token and
    inter-token-latency p50/p99 and the steady-state compile count
    (must be zero: every program warmed before the measured wave)."""
    import threading

    from deeplearning4j_tpu.models.textgenlstm import TextGenerationLSTM
    from deeplearning4j_tpu.serving.decode import DecodeEngine

    vocab = 16 if QUICK else 64
    units = 16 if QUICK else 96
    n_sessions = 8 if QUICK else 32
    gen_tokens = 16 if QUICK else 128
    prompt_len = 3 if QUICK else 12
    net = TextGenerationLSTM(total_unique_characters=vocab, units=units,
                             seed=7).init()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(0, vocab, prompt_len)]
               for _ in range(n_sessions)]

    # ---- baseline: sequential greedy decode, one session at a time on
    # the stateful host API (prefill = step through the prompt, then
    # closed-loop argmax) — warmed first so both sides are steady-state
    def one_hot(tok):
        x = np.zeros((1, vocab), np.float32)
        x[0, tok] = 1.0
        return x

    net.rnn_clear_previous_state()
    net.rnn_time_step(one_hot(0))
    net.rnn_clear_previous_state()

    def seq_run():
        t0 = time.perf_counter()  # lint: disable=DLT003 (rnn_time_step returns HOST numpy — every step in the loop is already a device sync; int(argmax) consumes it)
        for prompt in prompts:
            net.rnn_clear_previous_state()
            for tok in prompt:
                out = net.rnn_time_step(one_hot(tok))
            cur = int(out[0].argmax())  # generated token 1
            for _ in range(gen_tokens - 1):
                out = net.rnn_time_step(one_hot(cur))
                cur = int(out[0].argmax())
        return time.perf_counter() - t0  # rnn_time_step returns host np

    seq_s = _best_of(seq_run)
    seq_tps = n_sessions * gen_tokens / seq_s

    # ---- continuous batching: N concurrent sessions, temperature 0
    # (greedy — the same per-token work as the baseline)
    engine = DecodeEngine(net, max_sessions=n_sessions,
                          min_slots=min(8, n_sessions),
                          prefill_buckets=(4, 16) if QUICK else (16, 64),
                          seed=1)
    engine.warmup()
    compiles_before = dict(engine.stats()["compiles"])

    def wave():
        ttfts, itls = [], []

        def consume(sess, opened):
            last = None
            for ev in sess.events(token_deadline_s=120.0):
                now = time.perf_counter()
                if ev["type"] != "token":
                    continue
                if last is None:
                    ttfts.append((now - opened) * 1e3)
                else:
                    itls.append((now - last) * 1e3)
                last = now

        threads = []
        t0 = time.perf_counter()  # lint: disable=DLT003 (clocks time CLIENT-side event arrival off the streaming queue — the engine worker's bulk readback synced the device before each event was emitted)
        for prompt in prompts:
            sess = engine.open_session(prompt, max_tokens=gen_tokens,
                                       temperature=0.0)
            th = threading.Thread(target=consume,
                                  args=(sess, time.perf_counter()),
                                  daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=300.0)
        return time.perf_counter() - t0, ttfts, itls

    best = None
    for _ in range(REPS):
        elapsed, ttfts, itls = wave()
        if best is None or elapsed < best[0]:
            best = (elapsed, ttfts, itls)
    eng_s, ttfts, itls = best
    eng_tps = n_sessions * gen_tokens / eng_s
    compiles_after = dict(engine.stats()["compiles"])
    steady = sum(compiles_after.values()) - sum(compiles_before.values())
    engine.stop()

    emit("decode_tokens_per_sec", eng_tps, "tokens/sec", "decode",
         sessions=n_sessions, tokens_per_session=gen_tokens,
         sequential_tokens_per_sec=round(seq_tps, 1),
         speedup_vs_sequential=round(eng_tps / seq_tps, 2),
         ttft_ms={"p50": round(float(np.percentile(ttfts, 50)), 2),
                  "p99": round(float(np.percentile(ttfts, 99)), 2)},
         itl_ms={"p50": round(float(np.percentile(itls, 50)), 2),
                 "p99": round(float(np.percentile(itls, 99)), 2)}
         if itls else None,
         compiles=compiles_after, steady_state_compiles=steady,
         note="aggregate greedy decode throughput, %d concurrent "
              "sessions x %d tokens through the device-resident session "
              "ladder vs the SAME model decoded sequentially per session "
              "via rnn_time_step; steady_state_compiles counts programs "
              "compiled during the measured wave (0 = every dispatch "
              "replayed a warmed program). " % (n_sessions, gen_tokens)
              + _REPS_NOTE)


def bench_fleet():
    """Fleet-tier overhead and elasticity: (a) predict p50/p99 direct to
    one ModelServer vs through the FleetRouter over 2 replicas — the
    router hop is one lease-table lookup + one proxied HTTP round trip,
    so the delta is the routing tax; (b) scale-up time-to-ready: lease
    write of a fresh (cold) replica → first 200 THROUGH the router for a
    model only that replica hosts — the instant-start story end to end
    (warmup off-path, lease flips only when ready, router never routes
    cold). Metrics only per the 9p/bench-sensitivity note."""
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu.checkpoint.storage import ObjectStoreBackend
    from deeplearning4j_tpu.fleet import FleetRouter, FleetView, ServingReplica
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.serving import ModelServer

    n_requests, hidden = (30, 32) if QUICK else (200, 256)
    n_features, n_classes = 784, 10

    def _net(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Sgd(learning_rate=0.01)).weight_init("xavier")
                .list()
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(OutputLayer(n_out=n_classes, loss="mcxent"))
                .set_input_type(InputType.feed_forward(n_features))
                .build())
        return MultiLayerNetwork(conf).init()

    example = np.zeros((1, n_features), np.float32)
    rng = np.random.default_rng(77)
    body = json.dumps({"inputs": rng.standard_normal(
        (4, n_features)).astype(np.float32).tolist()}).encode()

    def _drive(url, n):
        lat = []
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        for _ in range(n):
            sw = Stopwatch().start()
            with urllib.request.urlopen(req, timeout=30) as r:
                r.read()
                assert r.status == 200
            lat.append(sw.stop() * 1000.0)
        return lat

    # (a) direct single server
    direct = ModelServer(port=0)
    direct.add_model("mlp", _net(0), warmup_example=example)
    direct.start(warmup_async=False)
    lat_direct = _drive(direct.address + "/v1/models/mlp:predict",
                        n_requests)
    direct.stop(drain=True)

    # (a) same model behind the router over 2 warmed replicas
    store = ObjectStoreBackend()
    replicas = []
    for i in range(2):
        srv = ModelServer(port=0)
        srv.add_model("mlp", _net(i), warmup_example=example)
        replicas.append(ServingReplica(srv, store, f"bench{i}",
                                       heartbeat_s=0.5).start())
    for r in replicas:
        r.wait_ready(300)
    router = FleetRouter(FleetView(store), refresh_s=0.1, seed=0).start()
    lat_routed = _drive(router.address + "/v1/models/mlp:predict",
                        n_requests)

    # (b) scale-up: cold replica hosting a model nothing else hosts;
    # clock runs from BEFORE its first lease write to the first 200
    # through the router
    srv3 = ModelServer(port=0)
    srv3.add_model("scaled", _net(7), warmup_example=example)
    rep3 = ServingReplica(srv3, store, "bench-scaleup", heartbeat_s=0.5)
    url3 = router.address + "/v1/models/scaled:predict"
    req3 = urllib.request.Request(
        url3, data=body, headers={"Content-Type": "application/json"})
    sw_up = Stopwatch().start()
    rep3.start()
    first_200_s = None
    deadline = time.perf_counter() + 300.0
    while time.perf_counter() < deadline:
        try:
            with urllib.request.urlopen(req3, timeout=10) as r:
                r.read()
                if r.status == 200:
                    first_200_s = float(sw_up.stop())
                    break
        except urllib.error.HTTPError as e:
            e.read()  # 503 no_replica until the lease flips warmed
        except Exception:
            pass
        time.sleep(0.05)

    router.stop()
    rep3.stop(drain_timeout_s=5.0)
    for r in replicas:
        r.stop(drain_timeout_s=5.0)

    p50_d = float(np.percentile(lat_direct, 50))
    p50_r = float(np.percentile(lat_routed, 50))
    emit("fleet_router_overhead_p50_ms", p50_r - p50_d, "ms", "fleet",
         direct_p50_ms=round(p50_d, 2),
         direct_p99_ms=round(float(np.percentile(lat_direct, 99)), 2),
         routed_p50_ms=round(p50_r, 2),
         routed_p99_ms=round(float(np.percentile(lat_routed, 99)), 2),
         requests=n_requests, replicas=2,
         note="sequential predicts, direct ModelServer vs through the "
              "FleetRouter (2 warmed replicas); the delta is the "
              "router hop. metrics only per the 9p note. " + _REPS_NOTE)
    emit("fleet_scale_up_time_to_ready_s",
         first_200_s if first_200_s is not None else -1.0,
         "s", "fleet_scaleup",
         note="cold replica start (lease write) -> first 200 through "
              "the router for a model only it hosts: warmup runs "
              "off-path and the lease flips warmed only when /readyz "
              "would pass, so this is the true scale-up latency the "
              "autoscaler pays. metrics only per the 9p note.")


def bench_checkpoint():
    """Checkpoint-overhead microbench: steps/sec for the same small-MLP
    train loop with checkpointing OFF, ASYNC every N steps (checkpoint/
    contract: snapshot on the training thread, write on a worker — the
    step loop must not pay for disk) and SYNC every N steps (the cost the
    async path hides). The acceptance bar is overhead_async_pct < 10 at
    save_every_n_steps=10."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.checkpoint import CheckpointManager
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Sgd

    # sized so the 10-step save interval (~150 ms at ~15 ms/step on CPU;
    # comparable for a real model on TPU) comfortably covers one atomic
    # commit (~15-20 ms for the ~0.3 MB payload on this host's 9p fs) —
    # the async path then hides the whole write. A model so small that
    # steps outrun the disk would instead measure the bounded queue's
    # BACKPRESSURE (by design: snapshots must not accumulate unboundedly
    # in host RAM), and on this CPU-only host the writer additionally
    # steals XLA compute cores, which a TPU deployment does not pay.
    steps = 40 if QUICK else 200
    batch, hidden = 2048, 256
    every = 10
    n_features, n_classes = 256, 10
    rng = np.random.default_rng(3)
    x = rng.standard_normal((batch, n_features)).astype(np.float32)
    y = np.eye(n_classes, dtype=np.float32)[rng.integers(0, n_classes, batch)]
    batches = [DataSet(x, y)] * steps  # one resident batch, `steps` steps

    def make_net():
        conf = (NeuralNetConfiguration.builder()
                .seed(11).updater(Sgd(learning_rate=0.01))
                .weight_init("xavier").list()
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(OutputLayer(n_out=n_classes, loss="mcxent"))
                .set_input_type(InputType.feed_forward(n_features))
                .build())
        return MultiLayerNetwork(conf).init()

    def steps_per_sec(cm):
        net = make_net()
        net.fit(batches[0])      # compile + warmup
        float(net._score)

        def timed():
            t0 = time.perf_counter()
            net.fit(batches, checkpoint_manager=cm)
            float(net._score)    # VALUE fetch forces the whole chain
            return time.perf_counter() - t0

        return steps / _best_of(timed)

    tmp = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        sps_off = steps_per_sec(None)
        cm_async = CheckpointManager(os.path.join(tmp, "async"),
                                     save_every_n_steps=every, keep_last=3,
                                     async_write=True)
        sps_async = steps_per_sec(cm_async)
        cm_async.flush()
        written = cm_async.saves_committed
        retained = cm_async.checkpoints()
        ckpt_bytes = retained[-1]["size"] if retained else 0
        cm_async.close()
        cm_sync = CheckpointManager(os.path.join(tmp, "sync"),
                                    save_every_n_steps=every, keep_last=3,
                                    async_write=False)
        sps_sync = steps_per_sec(cm_sync)
        cm_sync.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    def overhead(sps):
        return round((sps_off - sps) / sps_off * 100, 1)

    emit("checkpoint_async_train_steps_per_sec", sps_async, "steps/sec",
         "checkpoint",
         steps=steps, save_every_n_steps=every,
         steps_per_sec_off=round(sps_off, 1),
         steps_per_sec_sync=round(sps_sync, 1),
         overhead_async_pct=overhead(sps_async),
         overhead_sync_pct=overhead(sps_sync),
         checkpoints_written=written, checkpoints_retained=len(retained),
         ckpt_bytes=ckpt_bytes,
         note="same train loop, checkpointing off vs async vs sync every "
              f"{every} steps (snapshot+atomic journaled commit each save); "
              "acceptance: overhead_async_pct < 10. " + _REPS_NOTE)


def bench_resilience():
    """Fault-tolerance path costs, metrics only (no thresholds here: the
    9p filesystem's fsync jitter swings disk-backed numbers run to run —
    acceptance bars belong to quiet full runs, per the checkpoint bench's
    note): (1) restore_latest latency through the LocalFS vs the
    ObjectStore backend — the time a preempted worker spends between
    process-up and training-again; (2) serving hot-swap pause — the max
    inter-dispatch gap a ParallelInference client sees while a checkpoint
    swap lands, vs its median gap without one (the swap prepares params
    off the dispatch path and only the pointer swap holds the model lock,
    so the gap should stay near the ordinary dispatch cadence)."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.checkpoint import (CheckpointManager,
                                               ObjectStoreBackend)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    n_features, n_classes, hidden = 64, 10, 128
    rng = np.random.default_rng(7)
    x = rng.standard_normal((256, n_features)).astype(np.float32)
    y = np.eye(n_classes, dtype=np.float32)[
        rng.integers(0, n_classes, 256)]
    ds = DataSet(x, y)

    def make_net():
        conf = (NeuralNetConfiguration.builder()
                .seed(23).updater(Sgd(learning_rate=0.01))
                .weight_init("xavier").list()
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(OutputLayer(n_out=n_classes, loss="mcxent"))
                .set_input_type(InputType.feed_forward(n_features))
                .build())
        return MultiLayerNetwork(conf).init()

    def restore_ms(cm):
        import jax

        def timed():
            t0 = time.perf_counter()
            m = cm.restore_latest()
            # materialize a param leaf before stopping the clock
            np.asarray(jax.tree_util.tree_leaves(m.params)[0])
            return time.perf_counter() - t0
        return _best_of(timed) * 1000.0

    # --- restore latency, local vs object store ---------------------------
    tmp = tempfile.mkdtemp(prefix="bench_resil_")
    try:
        net = make_net()
        net.fit(ds)
        cm_local = CheckpointManager(os.path.join(tmp, "local"),
                                     async_write=False)
        cm_obj = CheckpointManager(storage=ObjectStoreBackend(),
                                   async_write=False)
        for cm in (cm_local, cm_obj):
            for _ in range(3):
                net.fit(ds)
                cm.save(net)
        local_ms = restore_ms(cm_local)
        object_ms = restore_ms(cm_obj)
        cm_local.close()

        # --- serving hot-swap pause --------------------------------------
        import threading

        served = cm_obj.restore_latest(load_updater=False)
        pi = ParallelInference(served, inference_mode="sequential")
        pi.start_hot_swap(cm_obj)  # manual polls; no background thread
        req = x[:8]
        pi.warmup(req)
        gaps_plain, gaps_swap = [], []

        def drive(gaps, n, swap_at=None):
            # the swap runs on its own thread, like the real poller — the
            # client stream only feels the param-pointer swap's lock hold
            swapper = None
            last = time.perf_counter()
            for i in range(n):
                if i == swap_at:
                    swapper = threading.Thread(target=pi.poll_checkpoint)
                    swapper.start()
                np.asarray(pi.output(req))
                now = time.perf_counter()
                gaps.append(now - last)
                last = now
            if swapper is not None:
                swapper.join()

        n = 30 if QUICK else 150
        drive(gaps_plain, n)
        net.fit(ds)
        cm_obj.save(net)  # the newer checkpoint the swap run picks up
        drive(gaps_swap, n, swap_at=n // 2)
        assert pi.stats()["hot_swap"]["swaps"] == 1
        pi.shutdown()
        cm_obj.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    swap_max_ms = max(gaps_swap) * 1000.0
    emit("checkpoint_restore_latest_ms", local_ms, "ms", "resilience",
         restore_local_ms=round(local_ms, 2),
         restore_object_store_ms=round(object_ms, 2),
         note="restore_latest wall time, LocalFSBackend vs in-process "
              "ObjectStoreBackend (manifest walk + sha256 + zip + device "
              "placement; the object-store number isolates the non-disk "
              "cost). " + _REPS_NOTE)
    emit("serving_hot_swap_max_gap_ms", swap_max_ms, "ms", "resilience",
         swaps=pi.stats()["hot_swap"]["swaps"],
         served_step=pi.stats()["hot_swap"]["current_checkpoint_step"],
         gap_p50_plain_ms=round(float(np.percentile(gaps_plain, 50)) * 1000,
                                2),
         gap_max_plain_ms=round(max(gaps_plain) * 1000, 2),
         gap_p50_swap_ms=round(float(np.percentile(gaps_swap, 50)) * 1000,
                               2),
         note="max gap between consecutive served dispatches across a "
              "checkpoint hot-swap vs the same client loop without one; "
              "restore+placement runs off the dispatch path, only the "
              "param pointer swap blocks. metrics only — thresholds on "
              "quiet full runs.")


def bench_grad_compression():
    """Compressed gradient collectives (parallel/compress.py): step time +
    compression ratio + est. bytes-on-wire for dense vs threshold vs top-k
    vs int8 on the zoo LeNet CNN and the charRNN (tBPTT path). The ratio
    is ANALYTIC accounting of the wire format (what a cross-slice DCN
    all-reduce would move); the step time shows what the in-step encode/
    decode costs on top — on this CPU container both are metrics only per
    the 9p/bench-sensitivity note (the compute-cost story belongs to a
    quiet TPU run), but the byte-reduction ratio is shape-derived and
    stable anywhere. Also probes the isolated compression pass into the
    ``grad_compress_ms`` histogram (obs/)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import LeNet, TextGenerationLSTM
    from deeplearning4j_tpu.parallel.compress import (
        Int8Compression, ThresholdCompression, TopKCompression,
        compression_stats, enable_grad_compression,
        measure_compression_overhead)

    if QUICK:
        steps, cnn_batch, vocab, rnn_batch, seq = 4, 8, 16, 4, 16
    else:
        steps, cnn_batch, vocab, rnn_batch, seq = 20, 64, 47, 32, 100
    rng = np.random.default_rng(5)

    def lenet_batches():
        x = rng.standard_normal((cnn_batch, 28 * 28)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, cnn_batch)]
        return [DataSet(x, y)] * steps

    def charrnn_batches():
        ids = rng.integers(0, vocab, (rnn_batch, seq))
        x = np.eye(vocab, dtype=np.float32)[ids]
        y = np.eye(vocab, dtype=np.float32)[
            rng.integers(0, vocab, (rnn_batch, seq))]
        # T > tbptt_fwd_length: fit() runs the per-window tBPTT step, the
        # compressed path for sequence models
        return [DataSet(x, y)]

    models = (
        ("lenet", lambda: LeNet(num_classes=10).init(), lenet_batches()),
        ("charrnn",
         lambda: TextGenerationLSTM(total_unique_characters=vocab,
                                    units=32 if QUICK else 256,
                                    tbptt_length=seq // 2).init(),
         charrnn_batches()),
    )
    schemes = (
        ("dense", None),
        ("threshold", ThresholdCompression()),  # the DEFAULT policy: the
        # acceptance ratio is measured exactly here
        ("topk", TopKCompression(ratio=0.01)),
        ("int8", Int8Compression()),
    )
    for model_name, make_net, batches in models:
        results = {}
        threshold_ratio = None
        for scheme_name, scheme in schemes:
            net = make_net()
            if scheme is not None:
                enable_grad_compression(net, scheme)
            net.fit(batches[:1])  # compile + warmup
            float(net._score)

            def timed(n=net):
                sw = Stopwatch().start()
                n.fit(batches)
                return sw.stop(sync=n._score)

            dt = _best_of(timed)
            entry = {"steps_per_sec": round(len(batches) *
                                            _windows_per_batch(net, batches)
                                            / dt, 1)}
            if scheme is not None:
                st = compression_stats(net)
                per_step_dense = st["dense_bytes"] / st["steps"]
                per_step_wire = st["wire_bytes"] / st["steps"]
                entry.update(
                    ratio=round(st["dense_bytes"] / max(st["wire_bytes"], 1.0),
                                1),
                    dense_kb_per_step=round(per_step_dense / 1024.0, 1),
                    wire_kb_per_step=round(per_step_wire / 1024.0, 1),
                    residual_norm=round(st["residual_norm"], 4))
                if "tau" in st:
                    entry["tau"] = round(st["tau"], 6)
                if scheme_name == "threshold":
                    threshold_ratio = entry["ratio"]
                    entry["grad_compress_ms"] = round(
                        measure_compression_overhead(net), 3)
            results[scheme_name] = entry
        emit(f"grad_compression_{model_name}_threshold_byte_reduction_x",
             float(threshold_ratio), "x", "compression",
             schemes=results,
             note="est. bytes-on-wire reduction of the DEFAULT threshold "
                  "policy (DL4J dual sparse/bitmap accounting) vs the "
                  "dense f32 all-reduce; per-scheme step rates are "
                  "metrics-only on this host per the 9p note — the "
                  "acceptance bar is the ratio (>= 4x). " + _REPS_NOTE)


def _windows_per_batch(net, batches) -> int:
    """Optimizer steps one DataSet triggers: tBPTT batches advance one
    step per window, everything else one per batch."""
    conf = net.conf
    if getattr(conf, "backprop_type", "standard") != "tbptt":
        return 1
    T = batches[0].features.shape[1]
    L = conf.tbptt_fwd_length
    return max(1, -(-T // L))


def bench_quantized_inference():
    """Post-training int8 quantization (quant/): fp32 vs BN-folded fp32 vs
    int8 serving dispatch on the zoo LeNet and a residual conv block —
    model bytes, per-dispatch p50/p99 and the int8-vs-fp32 accuracy delta.
    The byte reduction is shape-derived and stable anywhere; dispatch
    latencies are metrics-only on this host per the 9p/bench-sensitivity
    note (XLA:CPU has no int8 matmul fast path — the latency story belongs
    to an MXU run)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import LeNet
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.convolutional import ConvolutionLayer
    from deeplearning4j_tpu.nn.conf.graph import (ElementWiseVertex,
                                                  GraphBuilder)
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (ActivationLayer,
                                                   OutputLayer)
    from deeplearning4j_tpu.nn.conf.normalization import BatchNormalization
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.perf.fusion import fold_bn
    from deeplearning4j_tpu.quant import (accuracy_delta, calibrate,
                                          param_bytes, quantize,
                                          quantized_layers)

    if QUICK:
        batch, dispatches, cal_batches, res_hw, res_ch = 8, 6, 2, 8, 8
    else:
        batch, dispatches, cal_batches, res_hw, res_ch = 64, 40, 8, 32, 32

    def resnet_block():
        """One residual conv block (conv-BN-relu ×2 + skip add), the
        fold_bn→int8 shape ResNet-family serving graphs are made of."""
        parent = NeuralNetConfiguration.builder()
        parent.seed(5).updater(Sgd(0.05)).weight_init("relu")
        g = GraphBuilder(parent)
        g.add_inputs("in")
        g.add_layer("c1", ConvolutionLayer(n_out=res_ch, kernel_size=(3, 3),
                                           convolution_mode="same",
                                           activation="identity",
                                           has_bias=False), "in")
        g.add_layer("b1", BatchNormalization(), "c1")
        g.add_layer("a1", ActivationLayer(activation="relu"), "b1")
        g.add_layer("c2", ConvolutionLayer(n_out=res_ch, kernel_size=(3, 3),
                                           convolution_mode="same",
                                           activation="identity",
                                           has_bias=False), "a1")
        g.add_layer("b2", BatchNormalization(), "c2")
        g.add_vertex("add", ElementWiseVertex(op="add"), "b2", "a1")
        g.add_layer("a2", ActivationLayer(activation="relu"), "add")
        g.add_layer("out", OutputLayer(n_out=10, loss="mcxent"), "a2")
        g.set_outputs("out")
        g.set_input_types(InputType.convolutional(res_hw, res_hw, res_ch))
        return ComputationGraph(g.build()).init()

    rng = np.random.default_rng(7)
    models = (
        ("lenet", lambda: LeNet(num_classes=10).init(), (28, 28, 1), 10),
        ("resnet_block", resnet_block, (res_hw, res_hw, res_ch), 10),
    )
    for model_name, make_net, shape, n_classes in models:
        net = make_net()
        data = [DataSet(
            rng.standard_normal((batch,) + shape).astype(np.float32),
            np.eye(n_classes, dtype=np.float32)[
                rng.integers(0, n_classes, batch)])
            for _ in range(cal_batches)]
        # a few steps of training separate the logits: the accuracy gate
        # then measures real disagreement, not coin-flips between the
        # near-tied outputs of a random init
        net.fit(data, num_epochs=2)
        record = calibrate(net, (d.features for d in data))
        qnet = quantize(net, record)
        variants = (("fp32", net), ("fold_bn", fold_bn(net)),
                    ("int8", qnet))
        x = data[0].features
        results = {}
        for tag, m in variants:
            m.output(x)  # compile outside the timed region
            lat = []
            for _ in range(dispatches):
                sw = Stopwatch().start()
                sw.stop(m.output(x))  # output() is a host array: synced
                lat.append(sw.seconds * 1000.0)
            results[tag] = {
                "model_bytes": param_bytes(m),
                "p50_ms": round(float(np.percentile(lat, 50)), 2),
                "p99_ms": round(float(np.percentile(lat, 99)), 2),
            }
        reduction = (results["fp32"]["model_bytes"]
                     / max(results["int8"]["model_bytes"], 1))
        gate = accuracy_delta(net, qnet, data)
        emit(f"quantized_inference_{model_name}_byte_reduction_x",
             float(reduction), "x", "quant",
             variants=results,
             quantized_layers=len(quantized_layers(qnet)),
             top1_delta=round(gate["top1_delta"], 4),
             top1_agreement=round(gate["top1_agreement"], 4),
             loss_delta_rel=round(gate["loss_delta_rel"], 5),
             batch=batch,
             note="int8 weights + f32 scales/biases vs the fp32 serving "
                  "graph; acceptance bar is >= 3x bytes with the accuracy "
                  "delta inside the <= 1% gate budget. Dispatch latencies "
                  "are metrics-only on this host per the 9p note. "
                  + _REPS_NOTE)


def bench_autotune():
    """HBM planner + compile-time autotuner (perf/planner.py,
    perf/autotune.py): tuned-vs-default step time and activation bytes on
    LeNet + ResNet50. For each model the autotuner searches batch/fusion/
    donation under a budget 25% below the unplanned residual set, then the
    DEFAULT and TUNED configurations train a few measured steps at the
    same batch size. Metrics only per the 9p note (XLA:CPU timings do not
    transfer); the activation-bytes column is shape-derived and stable
    anywhere — that is the planner's acceptance number."""
    import jax

    from deeplearning4j_tpu.models import LeNet, ResNet50
    from deeplearning4j_tpu.nn.memory import conf_memory_report
    from deeplearning4j_tpu.perf.autotune import autotune, build_network
    from deeplearning4j_tpu.perf.fusion import training_activation_bytes

    if QUICK:
        jobs = [("lenet", LeNet(num_classes=10).conf(), (4,), 2)]
    else:
        jobs = [
            ("lenet", LeNet(num_classes=10).conf(), (64, 128, 256), 10),
            ("resnet50",
             ResNet50(num_classes=1000, input_shape=(224, 224, 3)).conf(),
             (64, 128), 6),
        ]
    rng = np.random.default_rng(11)
    for name, conf, batch_sizes, steps in jobs:
        mb = min(batch_sizes)
        rep = conf_memory_report(conf, minibatch=mb, training_bytes=False)
        fixed = rep.total_param_bytes + rep.updater_state_bytes
        budget = fixed + int(
            0.75 * training_activation_bytes(conf, minibatch=mb))
        record = autotune(conf, batch_sizes=batch_sizes,
                          budget_bytes=budget,
                          donation=(True,), top_k=1, reps=1 if QUICK else 2)
        # report activation bytes AT THE RECORD'S batch size, for the
        # record's own tuned conf — the same configuration the timing
        # below runs (the budget above was set at mb, the search floor)
        from deeplearning4j_tpu.perf.autotune import apply_tuning
        b = record.batch_size
        base_bytes = int(training_activation_bytes(conf, minibatch=b))
        tuned_bytes = int(training_activation_bytes(
            apply_tuning(conf, record), minibatch=b))

        def steps_per_sec(net, b):
            it = (conf.input_type if hasattr(conf, "input_type")
                  else conf.input_types[0])
            shape = (b, it.height, it.width, it.channels)
            n_out = 1000 if name == "resnet50" else 10
            x = rng.standard_normal(shape).astype(np.float32)
            y = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, b)]
            net.init(validate=False)
            from deeplearning4j_tpu.datasets.dataset import DataSet
            ds = DataSet(x, y)
            net.fit(ds)  # compile + warm outside the timed region
            def run():
                sw = Stopwatch().start()
                for _ in range(steps):
                    net.fit(ds)
                sw.stop(jax.block_until_ready(net._score))
                return sw.seconds
            return steps / _best_of(run)

        from deeplearning4j_tpu.nn.graph import ComputationGraph
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        default_net = (MultiLayerNetwork(conf)
                       if hasattr(conf, "layers") else
                       ComputationGraph(conf))
        tuned_net = build_network(conf, record)
        sps_default = steps_per_sec(default_net, b)
        sps_tuned = steps_per_sec(tuned_net, b)
        emit(f"autotune_{name}_tuned_vs_default_step_x",
             sps_tuned / max(sps_default, 1e-9), "x", "autotune",
             batch=b, fusion=record.fusion,
             remat_layers=len(record.remat),
             candidates=record.candidates_searched,
             default_activation_bytes=base_bytes,
             tuned_activation_bytes=tuned_bytes,
             activation_reduction=round(1 - tuned_bytes / base_bytes, 3),
             budget_bytes=budget,
             buckets=list(record.buckets),
             note="tuned = autotune TuningRecord applied (fusion + remat "
                  "under a budget 25% below the unplanned residual set); "
                  "step timings metrics-only on this host per the 9p "
                  "note — activation bytes are shape-derived and are the "
                  "planner acceptance number. " + _REPS_NOTE)


def bench_elastic():
    """Elastic-training path costs, metrics only (no thresholds — the 9p
    filesystem's fsync jitter swings disk-backed numbers run to run;
    acceptance bars belong to quiet full runs): (1) sharded checkpoint
    save (one epoch-boundary commit: shard snapshot + put + journal);
    (2) reshard-on-restore latency — reassembling a 4-host shard set into
    a 1-process world, the work a shrunk fleet does before training
    resumes; (3) membership-transition pause — bump request → new
    generation adopted → checkpoint restored, the storage-rendezvous part
    of an elastic transition (the jax.distributed re-init a multi-process
    world adds on top is measured by the slow chaos tests)."""
    import jax

    from deeplearning4j_tpu.checkpoint import CheckpointManager, ObjectStoreBackend
    from deeplearning4j_tpu.checkpoint import sharded as shd
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.parallel.elastic import LeaseBoard, Rendezvous

    rng = np.random.default_rng(11)
    x = rng.standard_normal((256, 64)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 256)]
    conf = (NeuralNetConfiguration.builder()
            .seed(3).updater(Adam(0.01)).weight_init("xavier").list()
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(OutputLayer(n_out=10, loss="mcxent"))
            .set_input_type(InputType.feed_forward(64))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.fit(DataSet(x, y))

    # --- sharded save + restore --------------------------------------
    cm = CheckpointManager(storage=ObjectStoreBackend(), sharded=True)

    def save_once():
        t0 = time.perf_counter()
        cm.save(net)  # sharded saves are synchronous (device_get inside)
        np.asarray(jax.tree_util.tree_leaves(net.params)[0])
        return time.perf_counter() - t0
    save_ms = _best_of(save_once) * 1000.0

    def restore_once():
        t0 = time.perf_counter()
        m = cm.restore_latest()
        np.asarray(jax.tree_util.tree_leaves(m.params)[0])
        return time.perf_counter() - t0
    restore_ms = _best_of(restore_once) * 1000.0

    # --- 4-host shard set -> 1-process world (reshard-on-restore) ----
    payloads = [shd.shard_zip_bytes(s, {"batch_in_epoch": 0})
                for s in shd.simulated_shard_snapshots(net, 4)]

    def reshard_once():
        t0 = time.perf_counter()
        m, _ = shd.restore_from_payloads(payloads)
        np.asarray(jax.tree_util.tree_leaves(m.params)[0])
        return time.perf_counter() - t0
    reshard_ms = _best_of(reshard_once) * 1000.0

    # --- membership transition pause (storage-rendezvous half) -------
    store = ObjectStoreBackend()
    board = LeaseBoard(store, "w00", ttl_s=2.0, heartbeat_s=0.5)
    rd = Rendezvous(store, board, join_timeout_s=30.0, poll_s=0.01)
    rd.propose_or_await(1, expected=1)
    gen = [1]

    def transition_once():
        t0 = time.perf_counter()
        rd.request_bump(gen[0], "bench")
        rd.propose_or_await(gen[0] + 1)
        m = cm.restore_latest()
        np.asarray(jax.tree_util.tree_leaves(m.params)[0])
        gen[0] += 1
        return time.perf_counter() - t0
    transition_ms = _best_of(transition_once) * 1000.0

    emit("elastic_sharded_save_ms", save_ms, "ms", "elastic",
         restore_ms=round(restore_ms, 2),
         note="one epoch-boundary sharded commit (snapshot + shard put + "
              "journal) and its restore, in-process object store. "
              + _REPS_NOTE)
    emit("elastic_reshard_restore_ms", reshard_ms, "ms", "elastic",
         num_shards=4,
         note="reassemble a 4-host shard set into a 1-process world "
              "(N->M reshard-on-restore) incl. model build + placement. "
              + _REPS_NOTE)
    emit("elastic_membership_transition_ms", transition_ms, "ms",
         "elastic",
         note="bump request -> next generation adopted -> checkpoint "
              "restored (storage-rendezvous half of an elastic "
              "transition; multi-process re-init cost rides on top). "
              "metrics only — thresholds on quiet full runs per the 9p "
              "note. " + _REPS_NOTE)


def bench_data_plane():
    """Streaming data plane costs, metrics only (9p note: the lease path
    here runs over the in-process object store, so the numbers isolate
    protocol overhead from disk jitter; thresholds belong to quiet full
    runs): (1) host ETL records/s — plain list iterator vs the sharded
    reader vs the sharded reader with leases + consumption ledger; (2)
    record-range lease claim latency; (3) data-wait fraction of a real
    fit loop over the sharded reader, with and without async prefetch,
    via the train.data_wait spans."""
    import jax

    from deeplearning4j_tpu.checkpoint import ObjectStoreBackend
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator, ListDataSetIterator)
    from deeplearning4j_tpu.datasets.sharded import (ShardedDataset,
                                                     ShardLeaseBoard)
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.obs import trace as obs_trace
    from deeplearning4j_tpu.optimize.updaters import Adam

    rng = np.random.default_rng(23)
    n = 4096 if QUICK else 65536
    batch = 256
    x = rng.standard_normal((n, 64)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)]

    def drain(it):
        # pure host ETL, no device work to sync
        t0 = time.perf_counter()  # lint: disable=DLT003
        count = 0
        for ds in it:
            count += ds.num_examples()
        return count / (time.perf_counter() - t0)

    plain = ListDataSetIterator(DataSet(x, y), batch)
    plain_rps = max(drain(plain) for _ in range(REPS))
    sds = ShardedDataset(x, y, batch_size=batch, seed=5)
    reader_rps = max(drain(sds.reader()) for _ in range(REPS))
    store = ObjectStoreBackend()
    sds_leased = ShardedDataset(x, y, batch_size=batch, seed=5,
                                store=store, ledger=True, lease_batches=8)
    leased_rps = max(drain(sds_leased.reader()) for _ in range(REPS))
    emit("data_plane_records_per_sec", reader_rps, "records/sec",
         "data_plane", plain_iterator=round(plain_rps, 1),
         leased_ledgered=round(leased_rps, 1), batch=batch, records=n,
         note="host ETL drain of the sharded reader (shuffle plan + row "
              "gather); plain_iterator is the pre-sharding baseline, "
              "leased_ledgered adds the lease protocol + per-batch "
              "consumption ledger over an in-process object store. "
              + _REPS_NOTE)

    # --- lease-claim latency ------------------------------------------
    board = ShardLeaseBoard(ObjectStoreBackend(), "bench-worker",
                            ttl_s=30.0)
    claims = 64 if QUICK else 512

    def claim_all():
        # host-side storage protocol, no device work to sync
        t0 = time.perf_counter()  # lint: disable=DLT003
        for c in range(claims):
            board.claim(0, c, 0, 1)
        return (time.perf_counter() - t0) / claims
    claim_us = _best_of(claim_all) * 1e6
    emit("data_plane_lease_claim_us", claim_us, "us", "data_plane_claim",
         claims=claims,
         note="one record-range lease claim (conflict scan + put + "
              "read-back) on an in-process object store; real object "
              "stores add their RTT. " + _REPS_NOTE)

    # --- data-wait fraction of a real fit loop ------------------------
    conf = (NeuralNetConfiguration.builder()
            .seed(3).updater(Adam(0.01)).weight_init("xavier").list()
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(OutputLayer(n_out=10, loss="mcxent"))
            .set_input_type(InputType.feed_forward(64))
            .build())

    def wait_fraction(wrap):
        net = MultiLayerNetwork(conf).init()
        spans = []
        tracer = obs_trace.Tracer(enabled=True)
        tracer.add_sink(spans.append)
        old = obs_trace._global
        obs_trace._global = tracer
        try:
            reader = ShardedDataset(x, y, batch_size=batch, seed=5).reader()
            t0 = time.perf_counter()
            net.fit(wrap(reader), num_epochs=1)
            jax.block_until_ready(net.params)
            total_ms = (time.perf_counter() - t0) * 1000.0
        finally:
            obs_trace._global = old
        wait_ms = sum(r["dur_ms"] for r in spans
                      if r["kind"] == "span"
                      and r["name"] == "train.data_wait")
        return wait_ms / max(total_ms, 1e-9)
    frac_sync = wait_fraction(lambda r: r)
    frac_async = wait_fraction(AsyncDataSetIterator)
    emit("data_plane_data_wait_fraction", frac_sync * 100.0, "%",
         "data_plane_wait",
         async_prefetch_pct=round(frac_async * 100.0, 2),
         note="share of one fit epoch spent waiting on the sharded "
              "reader (train.data_wait spans / wall); async_prefetch_pct "
              "is the same loop under AsyncDataSetIterator. metrics "
              "only — thresholds on quiet full runs per the 9p note.")


def bench_data_lake():
    """Data lake tier costs (9p note: the emulator is loopback HTTP, so
    the numbers isolate protocol + (de)serialization + cache overhead
    from real WAN latency): (1) host ETL records/s — in-RAM sharded
    reader vs lazily-pulled shard files over the wire client, cold and
    through the warmed disk cache (+ its hit rate); (2) small-model
    ``restore_latest`` latency per storage tier — local FS, the cloud
    client over the emulator, and the disk-cached cloud stack on its
    second (warm) restore."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.checkpoint import (CheckpointManager,
                                               LocalFSBackend,
                                               RetryingBackend)
    from deeplearning4j_tpu.checkpoint.cache import CachedBackend
    from deeplearning4j_tpu.checkpoint.cloud import CloudObjectBackend
    from deeplearning4j_tpu.checkpoint.emulator import ObjectStoreEmulator
    from deeplearning4j_tpu.datasets.records import (ShardFileSource,
                                                     write_shards)
    from deeplearning4j_tpu.datasets.sharded import ShardedDataset
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.updaters import Sgd

    rng = np.random.default_rng(31)
    n = 4096 if QUICK else 65536
    batch, per_shard = 256, 512
    x = rng.standard_normal((n, 64)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n)]

    def drain(sds):
        # pure host ETL + storage wire, no device work to sync
        t0 = time.perf_counter()  # lint: disable=DLT003
        count = 0
        for ds in sds.reader():
            count += ds.num_examples()
        return count / (time.perf_counter() - t0)

    ram_rps = max(drain(ShardedDataset(x, y, batch_size=batch, seed=5))
                  for _ in range(REPS))
    tmp = tempfile.mkdtemp(prefix="bench-lake-")
    with ObjectStoreEmulator(access_key="bench",
                             secret_key="bench-secret") as emu:
        client = RetryingBackend(
            CloudObjectBackend(emu.url, "lake", access_key="bench",
                               secret_key="bench-secret"),
            base_backoff_s=0.01, max_backoff_s=0.2)
        write_shards(client, "shards/", x, y, records_per_shard=per_shard)

        def lake_sds(store):
            return ShardedDataset(source=ShardFileSource(store, "shards/"),
                                  batch_size=batch, seed=5,
                                  max_resident_shards=4)
        cold_rps = max(drain(lake_sds(client)) for _ in range(REPS))
        cache = CachedBackend(client, os.path.join(tmp, "cache"),
                              max_bytes=1 << 30)
        drain(lake_sds(cache))          # fill pass
        cached_rps = max(drain(lake_sds(cache)) for _ in range(REPS))
        emit("data_lake_records_per_sec", cached_rps, "records/sec",
             "data_lake", ram_rps=round(ram_rps, 1),
             lake_cold_rps=round(cold_rps, 1),
             lake_cached_rps=round(cached_rps, 1),
             cache_hit_rate=round(cache.stats()["hit_rate"], 3),
             batch=batch, records=n, records_per_shard=per_shard,
             note="sharded-reader drain: in-RAM arrays vs shard files "
                  "pulled through the wire client (cold) vs the warmed "
                  "disk cache; loopback emulator per the 9p note. "
                  + _REPS_NOTE)

        # --- restore latency per storage tier -------------------------
        conf = (NeuralNetConfiguration.builder()
                .seed(3).updater(Sgd(learning_rate=0.05))
                .weight_init("xavier").list()
                .layer(DenseLayer(n_out=32, activation="tanh"))
                .layer(OutputLayer(n_out=10, loss="mcxent"))
                .set_input_type(InputType.feed_forward(64))
                .build())
        net = MultiLayerNetwork(conf).init()
        net.fit(ShardedDataset(x[:512], y[:512], batch_size=128,
                               seed=5).reader(), num_epochs=1)

        def restore_ms(storage):
            cm = CheckpointManager(storage=storage, async_write=False)
            cm.save(net)

            def timed():
                # host-side storage path; restore materializes on host
                t0 = time.perf_counter()  # lint: disable=DLT003
                assert cm.restore_latest() is not None
                return time.perf_counter() - t0
            best = _best_of(timed) * 1000.0
            cm.close()
            return best
        local_ms = restore_ms(LocalFSBackend(os.path.join(tmp, "ckpt")))
        emu_ms = restore_ms(RetryingBackend(
            CloudObjectBackend(emu.url, "ckpt", access_key="bench",
                               secret_key="bench-secret")))
        warm = CachedBackend(
            RetryingBackend(CloudObjectBackend(
                emu.url, "ckpt-warm", access_key="bench",
                secret_key="bench-secret")),
            os.path.join(tmp, "ckpt-cache"), max_bytes=1 << 30)
        cached_ms = restore_ms(warm)
        emit("data_lake_restore_ms", cached_ms, "ms", "data_lake_restore",
             local_fs_ms=round(local_ms, 1),
             emulator_ms=round(emu_ms, 1),
             cached_warm_ms=round(cached_ms, 1),
             note="CheckpointManager.restore_latest of one small model "
                  "per storage tier; the cached arm is the SECOND "
                  "restore (disk hits, zero wire reads). " + _REPS_NOTE)
    shutil.rmtree(tmp, ignore_errors=True)


def bench_retrieval():
    """Vector retrieval: device-batched QPS + recall@10 + index MB for
    the full compression ladder — brute / IVF / int8-IVF / int4 / PQ /
    IVF-PQ — vs the host-side VPTree, at 100k and 1M vectors (QUICK: one
    tiny corpus, smaller PQ codebooks). Metrics only on CPU per the 9p
    note; the VPTree comparison is capped at 100k vectors (a
    million-node host tree takes minutes to build and proves nothing new
    about the host baseline)."""
    from deeplearning4j_tpu.clustering.vptree import VPTree
    from deeplearning4j_tpu.retrieval import (BruteForceIndex, IVFIndex,
                                              IVFPQIndex, PQIndex,
                                              recall_at_k,
                                              synthetic_corpus)

    sizes = [(2_000, 32)] if QUICK else [(100_000, 64), (1_000_000, 64)]
    n_queries = 64 if QUICK else 1024
    batch = 64 if QUICK else 256
    k = 10
    # QUICK shrinks the codebooks (256-entry books on a 2k corpus spend
    # the whole smoke budget inside KMeans for no extra signal)
    ksub = 64 if QUICK else 256
    for n, d in sizes:
        V, Q = synthetic_corpus(n, d, n_clusters=max(16, n // 200),
                                seed=0, queries=n_queries)

        def qps_of(ix):
            ix.warmup(max_queries=batch, ks=(k,))

            def timed():
                sw = Stopwatch().start()
                outs = None
                for lo in range(0, n_queries, batch):
                    outs = ix.search(Q[lo:lo + batch], k)
                # search() already fetched to host; bare stop is synced
                del outs
                return sw.stop()
            return n_queries / _best_of(timed)

        indexes = {
            "brute": BruteForceIndex(V),
            "ivf": IVFIndex(V),
            "ivf_int8": IVFIndex(V, int8=True),
            "int4": BruteForceIndex(V, int4=True),
            "pq": PQIndex(V, M=8, ksub=ksub, rerank=16),
            "ivf_pq": IVFPQIndex(V, M=8, ksub=ksub, rerank=8),
        }
        exact = indexes["brute"]
        # host-tree baseline: per-query tree walks on one CPU thread.
        # Capped at 100k vectors (a million-node host tree takes minutes
        # to build); the metric is NAMED by the tree's actual corpus so
        # the 1M device numbers never masquerade as a 1M host baseline.
        n_tree = min(n, 100_000)
        tree = VPTree(V[:n_tree])
        n_tree_q = min(n_queries, 32)
        sw = Stopwatch().start()
        for row in Q[:n_tree_q]:
            tree.search(row, k)
        tree_qps = n_tree_q / sw.stop()
        emit(f"retrieval_vptree_host_{n_tree // 1000}k_qps", tree_qps,
             "queries/sec", "retrieval",
             note=f"host VPTree baseline over {n_tree} vectors "
                  "(single-thread per-query tree walk)")
        for name, ix in indexes.items():
            qps = qps_of(ix)
            rec = (1.0 if name == "brute"
                   else recall_at_k(ix, Q, k, exact=exact))
            extra = {}
            if n_tree == n:
                extra["speedup_vs_vptree"] = round(qps / tree_qps, 1)
            else:  # different corpus sizes: an apples-to-apples ratio
                extra[f"vs_vptree_{n_tree // 1000}k_corpus"] = \
                    round(qps / tree_qps, 1)
            emit(f"retrieval_{name}_{n // 1000}k_qps", qps,
                 "queries/sec", "retrieval",
                 recall_at_10=round(rec, 4),
                 index_mb=round(ix.nbytes() / 1e6, 2),
                 note="device-batched top-k, batch "
                      f"{batch}, warmed pow2 ladder. " + _REPS_NOTE,
                 **extra)


def bench_pallas():
    """Pallas kernel on/off ablation (perf/pallas/): the hand-written
    kernels behind the fused BN-train custom-VJP and the retrieval
    ADC/int4 hot loops vs their XLA references. Three probes: (1) a bf16
    residual-block BN fwd+bwd micro-step; (2) the fused ResNet50 train
    step plus the jaxpr-derived training-activation-bytes each arm hands
    its backward (the HBM-traffic number the BN family attacks — the
    ~4.7 activation-set crossings of tools/PROFILE_r5.md); (3) retrieval
    QPS for the PQ / IVF-PQ / brute-int4 indexes. Off-TPU the "on" arm
    runs the kernels in Pallas interpret mode, so CPU numbers validate
    the plumbing and the metric shape, not the speedup — TPU rounds
    record the real deltas. QUICK skips the ResNet50 execution probe
    (interpret-mode compile of ~50 gridded BN kernels buys no smoke
    signal) but still emits both activation-byte lines."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.conf.convolutional import fused_bn_act_train
    from deeplearning4j_tpu.perf import pallas as _pk
    from deeplearning4j_tpu.perf.fusion import training_activation_bytes
    from deeplearning4j_tpu.retrieval import (BruteForceIndex, IVFPQIndex,
                                              PQIndex, synthetic_corpus)

    arms = ((False, "off"), (True, "on"))
    mode = "interpret" if _pk.interpret() else "native"

    # ---- probe 1: residual-block BN fwd+bwd micro-step (bf16) ----------
    if QUICK:
        n, side, c, steps = 4, 8, 32, 2
    else:
        n, side, c, steps = 32, 56, 128, 20
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.standard_normal((n, side, side, c), np.float32),
                    jnp.bfloat16)
    resid = jnp.asarray(rng.standard_normal((n, side, side, c), np.float32),
                        jnp.bfloat16)
    gamma = jnp.ones((c,), jnp.float32)
    beta = jnp.zeros((c,), jnp.float32)

    def _loss(z, gamma, beta, resid):
        out, _, _ = fused_bn_act_train("relu", 1e-5, z, gamma, beta, resid)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    bn_ms = {}
    for flag, tag in arms:
        with _pk.override(enabled=flag):
            # fresh jit per arm: kernel selection happens at trace time
            step = jax.jit(jax.grad(_loss, argnums=(0, 1, 2, 3)))
            jax.block_until_ready(step(z, gamma, beta, resid))

            def timed():
                sw = Stopwatch().start()
                for _ in range(steps):
                    grads = step(z, gamma, beta, resid)
                jax.block_until_ready(grads)
                return sw.stop()

            bn_ms[tag] = _best_of(timed) / steps * 1e3
        extra = {}
        if tag == "on":
            extra["speedup_vs_off"] = round(bn_ms["off"] / bn_ms["on"], 2)
        emit(f"pallas_bn_block_step_ms_{tag}", bn_ms[tag], "ms", "pallas",
             shape=[n, side, side, c], dtype="bfloat16", kernel_mode=mode,
             note="fused BN-train fwd+bwd over a residual block via the "
                  "fused_bn_act_train custom-VJP; on=Pallas kernels, "
                  "off=XLA reference. " + _REPS_NOTE, **extra)

    # ---- probe 2: fused ResNet50 step + activation-set bytes -----------
    if QUICK:
        batch, side2, warmup, steps2 = 2, 64, 1, 2
    else:
        batch = int(os.environ.get("BENCH_RESNET_BATCH", "128"))
        side2, warmup, steps2 = 224, 6, 30
    conf = _dc.replace(
        ResNet50(num_classes=1000, input_shape=(side2, side2, 3)).conf(),
        dtype="bfloat16").fused()
    rn_imgs = {}
    for flag, tag in arms:
        with _pk.override(enabled=flag):
            try:
                act_bytes = int(training_activation_bytes(conf,
                                                          minibatch=batch))
            except Exception:
                act_bytes = None
            extra = {"training_activation_bytes": act_bytes}
            if QUICK:  # jaxpr-derived bytes only; no interpret-mode compile
                emit(f"pallas_resnet50_activation_bytes_{tag}",
                     float(act_bytes or 0), "bytes", "pallas", batch=batch,
                     kernel_mode=mode, note="QUICK: jaxpr-derived "
                     "activation-set bytes only; execution probe runs on "
                     "full (TPU) rounds.")
                continue
            rn_imgs[tag], _ = _bench_resnet50_once(
                "bfloat16", batch, side2, warmup, steps2, fused=True)
        if not QUICK:
            if tag == "on":
                extra["speedup_vs_off"] = round(
                    rn_imgs["on"] / rn_imgs["off"], 2)
            emit(f"pallas_resnet50_imgs_per_sec_{tag}", rn_imgs[tag],
                 "imgs/sec", "pallas", batch=batch, kernel_mode=mode,
                 note="fused ResNet50 train step, Pallas BN kernels on/off. "
                      + _REPS_NOTE, **extra)

    # ---- probe 3: retrieval ADC / int4 QPS -----------------------------
    if QUICK:
        n_vec, d, n_queries, batch3, ksub = 2_000, 32, 64, 64, 64
    else:
        n_vec, d, n_queries, batch3, ksub = 100_000, 64, 512, 128, 256
    k = 10
    V, Q = synthetic_corpus(n_vec, d, n_clusters=max(16, n_vec // 200),
                            seed=0, queries=n_queries)
    indexes = {
        "pq": PQIndex(V, M=8, ksub=ksub),
        "ivf_pq": IVFPQIndex(V, M=8, ksub=ksub),
        "int4": BruteForceIndex(V, int4=True),
    }
    for name, ix in indexes.items():
        qps = {}
        for flag, tag in arms:
            with _pk.override(enabled=flag):
                # per-arm warmup: each _KernelSelect arm is its own jitted
                # function, so this traces the arm actually being timed
                ix.warmup(max_queries=batch3, ks=(k,))

                def timed():
                    sw = Stopwatch().start()
                    for lo in range(0, n_queries, batch3):
                        ix.search(Q[lo:lo + batch3], k)
                    return sw.stop()  # search() fetches to host

                qps[tag] = n_queries / _best_of(timed)
            extra = {}
            if tag == "on":
                extra["speedup_vs_off"] = round(qps["on"] / qps["off"], 2)
            emit(f"pallas_retrieval_{name}_qps_{tag}", qps[tag],
                 "queries/sec", "pallas", corpus=n_vec, kernel_mode=mode,
                 note="ADC/int4 scoring kernels on/off; identical ids "
                      "asserted in tests/test_zz_pallas.py. " + _REPS_NOTE,
                 **extra)


def main():
    benches = [("lenet", bench_lenet), ("word2vec", bench_word2vec),
               ("charlstm", bench_graveslstm), ("serving", bench_serving),
               ("serving_load", bench_serving_load),
               ("decode", bench_decode),
               ("fleet", bench_fleet),
               ("checkpoint", bench_checkpoint),
               ("resilience", bench_resilience),
               ("elastic", bench_elastic),
               ("data_plane", bench_data_plane),
               ("data_lake", bench_data_lake),
               ("retrieval", bench_retrieval),
               ("pallas", bench_pallas),
               ("grad_compression", bench_grad_compression),
               ("quantized_inference", bench_quantized_inference),
               ("autotune", bench_autotune),
               ("resnet50_fusion", bench_resnet50_fusion),
               ("resnet50", bench_resnet50)]
    only = os.environ.get("BENCH_ONLY")
    if only:
        wanted = {w.strip() for w in only.split(",") if w.strip()}
        unknown = wanted - {n for n, _ in benches}
        if unknown:
            raise SystemExit(f"BENCH_ONLY names unknown benches: "
                             f"{sorted(unknown)}")
        benches = [(n, f) for n, f in benches if n in wanted]
    from deeplearning4j_tpu.perf.compile_cache import \
        enable_compilation_cache
    enable_compilation_cache()
    device = _device_fields()  # no backend, no benchmark: raises here
    failed = []
    for name, fn in benches:
        try:
            fn()
        except Exception as e:  # keep the remaining benches alive
            failed.append(name)
            print(json.dumps({"metric": name,
                              "error": f"{type(e).__name__}: {e}", **device}),
                  flush=True)
    if failed:
        raise SystemExit(f"benches failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()

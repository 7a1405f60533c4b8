"""Framework linter: rule fixtures + the tier-1 repo-wide clean run.

The repo-wide test IS the CI gate the ISSUE asks for: any new violation in
``deeplearning4j_tpu/`` or ``tools/`` fails here; waive
intentionally with ``# lint: disable=DLT00X`` plus a justification.
"""

import importlib.util
import json
import os
import textwrap
import time

from deeplearning4j_tpu.analysis.lint import (DEFAULT_TARGETS, audit_waivers,
                                              clear_caches, lint_file,
                                              lint_paths)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tests", "lint_fixtures")


def _lint(src, path="fixture.py"):
    return lint_file(path, src=textwrap.dedent(src))


def _rules(violations):
    return [v.rule for v in violations]


class TestModuleLevelJnp:
    def test_fires_on_import_time_compute(self):
        vs = _lint("""
            import jax.numpy as jnp
            TABLE = jnp.arange(1024)
        """)
        assert _rules(vs) == ["DLT001"]
        assert "import time" in vs[0].message

    def test_fires_in_class_body_and_default_arg(self):
        vs = _lint("""
            import jax.numpy as jnp
            class C:
                mask = jnp.ones((4, 4))
            def f(x=jnp.zeros(3)):
                return x
        """)
        assert _rules(vs) == ["DLT001", "DLT001"]

    def test_nested_jnp_calls_report_once(self):
        vs = _lint("""
            import jax.numpy as jnp
            T = jnp.cumsum(jnp.arange(4))
        """)
        assert _rules(vs) == ["DLT001"]  # outermost call only, no dupes

    def test_clean_inside_function_body(self):
        vs = _lint("""
            import jax.numpy as jnp
            def f():
                return jnp.arange(1024)
        """)
        assert vs == []

    def test_attribute_access_is_fine(self):
        assert _lint("""
            import jax.numpy as jnp
            DTYPE = jnp.float32
        """) == []

    def test_inline_waiver(self):
        vs = _lint("""
            import jax.numpy as jnp
            TABLE = jnp.arange(4)  # lint: disable=DLT001 (4 elements, cheap)
        """)
        assert vs == []


class TestImpureInJit:
    def test_time_in_jitted_function(self):
        vs = _lint("""
            import time
            import jax
            @jax.jit
            def step(x):
                t = time.time()
                return x + t
        """)
        assert _rules(vs) == ["DLT002"]
        assert "trace time" in vs[0].message

    def test_function_passed_to_jit(self):
        vs = _lint("""
            import time
            import jax
            def step(x):
                return x * time.perf_counter()
            fast = jax.jit(step)
        """)
        assert _rules(vs) == ["DLT002"]

    def test_scan_body(self):
        vs = _lint("""
            import random
            from jax import lax
            def body(c, x):
                return c, x * random.random()
            def run(xs):
                return lax.scan(body, 0.0, xs)
        """)
        assert _rules(vs) == ["DLT002"]

    def test_np_random_in_traced_lambda(self):
        vs = _lint("""
            import numpy as np
            import jax
            fast = jax.jit(lambda x: x + np.random.rand())
        """)
        assert _rules(vs) == ["DLT002"]

    def test_host_code_unflagged(self):
        assert _lint("""
            import time
            def host_loop():
                return time.time()
        """) == []


class TestBenchSync:
    def test_unsynced_stopwatch_in_bench_file(self):
        vs = _lint("""
            import time
            def measure(step):
                t0 = time.perf_counter()
                step()
                return time.perf_counter() - t0
        """, path="tools/perf_thing.py")
        assert _rules(vs) == ["DLT003"]

    def test_synced_stopwatch_clean(self):
        assert _lint("""
            import time
            import jax
            def measure(step):
                t0 = time.perf_counter()
                jax.block_until_ready(step())
                return time.perf_counter() - t0
        """, path="tools/perf_thing.py") == []

    def test_non_bench_file_out_of_scope(self):
        assert _lint("""
            import time
            def measure(step):
                t0 = time.perf_counter()
                step()
                return time.perf_counter() - t0
        """, path="deeplearning4j_tpu/whatever.py") == []


class TestLockOrder:
    # the seeded inconsistent-ordering fixture the acceptance criteria names
    INCONSISTENT = """
        import threading
        class Manager:
            def __init__(self):
                self._state_lock = threading.Lock()
                self._io_lock = threading.Lock()
            def writer(self):
                with self._state_lock:
                    with self._io_lock:
                        pass
            def reader(self):
                with self._io_lock:
                    with self._state_lock:
                        pass
    """

    def test_flags_inconsistent_ordering(self):
        vs = _lint(self.INCONSISTENT)
        assert _rules(vs) == ["DLT004"]
        msg = vs[0].message
        assert "_state_lock" in msg and "_io_lock" in msg
        assert "writer" in msg and "reader" in msg
        assert "deadlock" in msg

    def test_consistent_ordering_clean(self):
        assert _lint("""
            import threading
            class Manager:
                def writer(self):
                    with self._state_lock:
                        with self._io_lock:
                            pass
                def reader(self):
                    with self._state_lock:
                        with self._io_lock:
                            pass
        """) == []

    def test_combined_with_statement_ordering(self):
        vs = _lint("""
            class M:
                def a(self):
                    with self._l1_lock, self._l2_lock:
                        pass
                def b(self):
                    with self._l2_lock, self._l1_lock:
                        pass
        """)
        assert _rules(vs) == ["DLT004"]

    def test_single_lock_methods_clean(self):
        assert _lint("""
            class M:
                def a(self):
                    with self._lock:
                        pass
                def b(self):
                    with self._lock:
                        pass
        """) == []

    # --- explicit acquire()/release() sequences (DLT004 false-negative fix) ---

    def test_acquire_try_finally_release_opposite_order(self):
        # Method a holds x via acquire()/try-finally-release() while taking
        # y; method b nests them the other way round via ``with``.  The old
        # with-only scan missed the explicit acquire entirely.
        vs = _lint("""
            class Pool:
                def a(self):
                    self._x_lock.acquire()
                    try:
                        with self._y_lock:
                            pass
                    finally:
                        self._x_lock.release()
                def b(self):
                    with self._y_lock:
                        self._x_lock.acquire()
                        self._x_lock.release()
        """)
        assert _rules(vs) == ["DLT004"]
        assert "_x_lock" in vs[0].message and "_y_lock" in vs[0].message

    def test_both_methods_pure_acquire_release(self):
        vs = _lint("""
            class Pool:
                def a(self):
                    self._x_lock.acquire()
                    self._y_lock.acquire()
                    self._y_lock.release()
                    self._x_lock.release()
                def b(self):
                    self._y_lock.acquire()
                    self._x_lock.acquire()
                    self._x_lock.release()
                    self._y_lock.release()
        """)
        assert _rules(vs) == ["DLT004"]

    def test_sequential_acquire_release_is_not_nesting(self):
        # release before the second acquire: the locks are never held
        # together, so opposite sequential order is fine.
        assert _lint("""
            class Pool:
                def a(self):
                    self._x_lock.acquire()
                    self._x_lock.release()
                    self._y_lock.acquire()
                    self._y_lock.release()
                def b(self):
                    self._y_lock.acquire()
                    self._y_lock.release()
                    self._x_lock.acquire()
                    self._x_lock.release()
        """) == []

    def test_acquire_consistent_order_clean(self):
        assert _lint("""
            class Pool:
                def a(self):
                    self._x_lock.acquire()
                    try:
                        with self._y_lock:
                            pass
                    finally:
                        self._x_lock.release()
                def b(self):
                    self._x_lock.acquire()
                    self._y_lock.acquire()
                    self._y_lock.release()
                    self._x_lock.release()
        """) == []


class TestServingBnFold:
    _SERVING_WITH_BN = """
        from deeplearning4j_tpu.nn.conf.normalization import BatchNormalization
        from deeplearning4j_tpu.parallel import ParallelInference

        def serve(builder, net_cls):
            conf = builder.layer(BatchNormalization()).build()
            net = net_cls(conf).init()
            pi = ParallelInference(net, batch_limit=8)
            return pi
    """

    def test_fires_on_bn_model_served_unfolded(self):
        vs = _lint(self._SERVING_WITH_BN)
        assert _rules(vs) == ["DLT005"]
        assert "fold_bn" in vs[0].message

    def test_fold_bn_call_clean(self):
        src = self._SERVING_WITH_BN.replace(
            "pi = ParallelInference(net, batch_limit=8)",
            "pi = ParallelInference(fold_bn(net), batch_limit=8)")
        assert _lint(src) == []

    def test_fold_bn_kwarg_clean(self):
        src = self._SERVING_WITH_BN.replace(
            "ParallelInference(net, batch_limit=8)",
            "ParallelInference(net, batch_limit=8, fold_bn=True)")
        assert _lint(src) == []

    def test_explicit_fold_bn_false_still_fires(self):
        src = self._SERVING_WITH_BN.replace(
            "ParallelInference(net, batch_limit=8)",
            "ParallelInference(net, batch_limit=8, fold_bn=False)")
        assert _rules(_lint(src)) == ["DLT005"]

    def test_no_bn_clean(self):
        assert _lint("""
            from deeplearning4j_tpu.parallel import ParallelInference

            def serve(net):
                return ParallelInference(net)
        """) == []

    def test_inline_waiver(self):
        src = self._SERVING_WITH_BN.replace(
            "pi = ParallelInference(net, batch_limit=8)",
            "pi = ParallelInference(net, batch_limit=8)  "
            "# lint: disable=DLT005 (train-mode serving by design)")
        assert _lint(src) == []


class TestSwallowedStorageError:
    _SWALLOW = """
        def commit(backend, name, data):
            try:
                backend.put(name, data)
            except Exception:
                pass
    """

    def test_fires_on_swallowed_except_in_checkpoint_path(self):
        vs = _lint(self._SWALLOW,
                   path="deeplearning4j_tpu/checkpoint/thing.py")
        assert _rules(vs) == ["DLT006"]
        assert "swallows" in vs[0].message

    def test_fires_on_bare_except_in_storage_path(self):
        vs = _lint("""
            def fetch(b, n):
                try:
                    return b.get(n)
                except:
                    return None
        """, path="deeplearning4j_tpu/storage/thing.py")
        assert _rules(vs) == ["DLT006"]

    def test_logging_the_error_is_clean(self):
        vs = _lint("""
            import logging
            log = logging.getLogger(__name__)
            def commit(backend, name, data):
                try:
                    backend.put(name, data)
                except Exception as e:
                    log.warning("put failed: %s", e)
        """, path="deeplearning4j_tpu/checkpoint/thing.py")
        assert vs == []

    def test_reraise_is_clean(self):
        vs = _lint("""
            def commit(backend, name, data):
                try:
                    backend.put(name, data)
                except Exception:
                    raise RuntimeError("commit failed")
        """, path="deeplearning4j_tpu/checkpoint/thing.py")
        assert vs == []

    def test_stashing_for_deferred_reraise_is_clean(self):
        vs = _lint("""
            class W:
                def work(self, item):
                    try:
                        self._write(item)
                    except BaseException as e:
                        self._write_err = e
        """, path="deeplearning4j_tpu/checkpoint/thing.py")
        assert vs == []

    def test_unrelated_call_with_log_substring_still_fires(self):
        """Only a reporting CALL counts — `self.catalog.refresh()` has
        'log' buried in an attribute name and must not silence the rule."""
        vs = _lint("""
            class C:
                def commit(self, backend, name, data):
                    try:
                        backend.put(name, data)
                    except Exception:
                        self.catalog.refresh()
        """, path="deeplearning4j_tpu/checkpoint/thing.py")
        assert _rules(vs) == ["DLT006"]

    def test_narrow_handler_is_clean(self):
        vs = _lint("""
            import os
            def prune(path):
                try:
                    os.remove(path)
                except OSError:
                    pass
        """, path="deeplearning4j_tpu/checkpoint/thing.py")
        assert vs == []

    def test_out_of_scope_file_is_clean(self):
        vs = _lint(self._SWALLOW, path="deeplearning4j_tpu/nn/thing.py")
        assert vs == []

    def test_inline_waiver(self):
        src = self._SWALLOW.replace(
            "except Exception:",
            "except Exception:  # lint: disable=DLT006 (probe, loss ok)")
        assert _lint(src,
                     path="deeplearning4j_tpu/checkpoint/thing.py") == []


class TestMetricRegistration:
    def test_fires_on_missing_unit_and_help(self):
        vs = _lint("""
            from deeplearning4j_tpu.obs import get_registry
            def setup():
                registry = get_registry()
                return registry.counter("requests_total")
        """)
        assert _rules(vs) == ["DLT007"]
        assert "unit and help" in vs[0].message

    def test_fires_on_missing_help_only(self):
        vs = _lint("""
            def setup(reg):
                return reg.gauge("depth", unit="requests")
        """)
        assert _rules(vs) == ["DLT007"]
        assert "help" in vs[0].message and "unit" not in \
            vs[0].message.split("—")[0].replace("without help", "")

    def test_empty_literal_unit_counts_as_missing(self):
        vs = _lint("""
            def setup(registry):
                return registry.histogram("lat_ms", unit="", help="x")
        """)
        assert _rules(vs) == ["DLT007"]

    def test_full_registration_clean(self):
        assert _lint("""
            def setup(registry):
                registry.counter("requests_total", unit="requests",
                                 help="requests served")
                registry.histogram("lat_ms", "ms", "request latency")
        """) == []

    def test_non_registry_receiver_out_of_scope(self):
        # CompileWatch.counter(name) is a QUERY, not a registration
        assert _lint("""
            def read(watch):
                return watch.counter("attention.flash")
        """) == []

    def test_fires_on_bare_counter_dict(self):
        vs = _lint("""
            class Stats:
                def __init__(self):
                    self.counters = {}
        """)
        assert _rules(vs) == ["DLT007"]
        assert "bare counter dict" in vs[0].message

    def test_fires_on_annotated_counter_dict(self):
        vs = _lint("""
            from typing import Dict
            class W:
                def __init__(self):
                    self._event_counters: Dict[str, int] = {}
        """)
        assert _rules(vs) == ["DLT007"]

    def test_unrelated_dict_clean(self):
        assert _lint("""
            class C:
                def __init__(self):
                    self.cache = {}
                    self.bucket_sizes = {}
        """) == []

    def test_inline_waiver(self):
        assert _lint("""
            class Stats:
                def __init__(self):
                    self.counters = {}  # lint: disable=DLT007 (absorbed via obs.absorb_training_stats)
        """) == []


class TestUnboundedQueue:
    def test_fires_on_unbounded_queue_in_parallel_path(self):
        vs = _lint("""
            import queue
            class W:
                def __init__(self):
                    self._q = queue.Queue()
        """, path="deeplearning4j_tpu/parallel/thing.py")
        assert _rules(vs) == ["DLT008"]
        assert "unbounded" in vs[0].message and "maxsize" in vs[0].message

    def test_fires_on_maxsize_zero_and_from_import(self):
        vs = _lint("""
            from queue import Queue
            def make():
                return Queue(maxsize=0)
        """, path="deeplearning4j_tpu/serving/thing.py")
        assert _rules(vs) == ["DLT008"]

    def test_fires_on_positional_zero(self):
        vs = _lint("""
            import queue
            q = queue.Queue(0)
        """, path="deeplearning4j_tpu/datasets/thing.py")
        assert _rules(vs) == ["DLT008"]

    def test_bounded_queue_clean(self):
        assert _lint("""
            import queue
            from queue import Queue
            a = queue.Queue(maxsize=64)
            b = Queue(8)
            c = queue.Queue(maxsize=depth)
        """, path="deeplearning4j_tpu/storage/thing.py") == []

    def test_out_of_scope_path_clean(self):
        assert _lint("""
            import queue
            q = queue.Queue()
        """, path="deeplearning4j_tpu/nn/thing.py") == []

    def test_inline_waiver(self):
        assert _lint("""
            import queue
            q = queue.Queue()  # lint: disable=DLT008 (drained every step)
        """, path="deeplearning4j_tpu/parallel/thing.py") == []


class TestHostWorkInCompression:
    def test_fires_on_np_in_compress_function_with_device_math(self):
        vs = _lint("""
            import numpy as np
            import jax.numpy as jnp
            def compress_gradients(grads):
                v = jnp.abs(grads)
                return np.asarray(v)
        """)
        assert _rules(vs) == ["DLT009"]
        assert "traced train step" in vs[0].message

    def test_fires_on_item_in_compression_class_method(self):
        vs = _lint("""
            import jax.numpy as jnp
            class MyCompression:
                def encode(self, v):
                    tau = jnp.max(jnp.abs(v))
                    return float(tau.item())
        """)
        assert _rules(vs) == ["DLT009"]
        assert ".item()" in vs[0].message

    def test_fires_on_device_get(self):
        vs = _lint("""
            import jax
            import jax.numpy as jnp
            def compress_step(g):
                g = jnp.sign(g)
                return jax.device_get(g)
        """)
        assert _rules(vs) == ["DLT009"]

    def test_pure_host_reader_without_jnp_is_exempt(self):
        # scrape-time absorbers read the accumulators with numpy but do no
        # device math — exempt by construction
        assert _lint("""
            import numpy as np
            def absorb_grad_compression(registry, model):
                acc = model.compress_state["acc"]
                return {k: float(np.asarray(v)) for k, v in acc.items()}
        """) == []

    def test_out_of_scope_name_clean(self):
        assert _lint("""
            import numpy as np
            import jax.numpy as jnp
            def stack_batches(xs):
                return jnp.asarray(np.stack(xs))
        """) == []

    def test_inline_waiver(self):
        assert _lint("""
            import numpy as np
            import jax.numpy as jnp
            def compress_debug(g):
                v = jnp.abs(g)
                return np.asarray(v)  # lint: disable=DLT009 (debug dump)
        """) == []


class TestFloatCastInQuant:
    def test_fires_on_astype_float32_in_quant_function(self):
        vs = _lint("""
            import jax.numpy as jnp
            def dequantize_layer(xq, scale):
                return xq.astype(jnp.float32) * scale
        """)
        assert _rules(vs) == ["DLT010"]
        assert "int8 compute" in vs[0].message

    def test_fires_on_string_dtype_and_quantized_class_method(self):
        vs = _lint("""
            import jax.numpy as jnp
            class QuantizedThingLayer:
                def apply(self, params, x):
                    acc = x @ params["Wq"]
                    return acc.astype("float64")
        """)
        assert _rules(vs) == ["DLT010"]
        assert "float64" in vs[0].message

    def test_fires_on_float64_constructor(self):
        vs = _lint("""
            import numpy as np
            import jax.numpy as jnp
            def quantize_weights(w):
                wq = jnp.round(w)
                return np.float64(wq) / 127.0
        """)
        assert _rules(vs) == ["DLT010"]

    def test_pure_host_quant_helper_exempt(self):
        # bench/CLI data prep named *quant* with no device math — the
        # DLT009 precedent: host-on-host casts are not the int8 hot path
        assert _lint("""
            import numpy as np
            def bench_quantized_inference():
                rng = np.random.default_rng(7)
                return rng.standard_normal((8, 4)).astype(np.float32)
        """) == []

    def test_int_casts_and_scalar_wraps_exempt(self):
        # the quantize itself (.astype(int8)) and the scalar requantize
        # multiplier (jnp.float32 of a Python float) are the legal idiom
        assert _lint("""
            import jax.numpy as jnp
            def quantize_activation(x, s):
                inv = jnp.float32(1.0 / s)
                return jnp.clip(jnp.round(x * inv), -127, 127).astype(jnp.int8)
        """) == []

    def test_out_of_scope_name_clean(self):
        assert _lint("""
            import jax.numpy as jnp
            def upcast_batch(x):
                return x.astype(jnp.float32)
        """) == []

    def test_inline_waiver(self):
        assert _lint("""
            import jax.numpy as jnp
            def quantized_fallback(x):
                return x.astype(jnp.float32)  # lint: disable=DLT010 (fp32 boundary)
        """) == []


class TestUnseededGlobalRng:
    DATA_PATH = "deeplearning4j_tpu/datasets/fixture.py"

    def test_fires_on_global_shuffle_in_datasets_path(self):
        vs = _lint("""
            import random
            def make_epoch(items):
                random.shuffle(items)
                return items
        """, path=self.DATA_PATH)
        assert _rules(vs) == ["DLT011"]
        assert "deterministic-epoch" in vs[0].message

    def test_fires_on_np_random_permutation_and_seed(self):
        vs = _lint("""
            import numpy as np
            def shard_order(n):
                np.random.seed(0)
                return np.random.permutation(n)
        """, path="deeplearning4j_tpu/parallel/fixture.py")
        assert _rules(vs) == ["DLT011", "DLT011"]

    def test_seeded_instances_exempt(self):
        # the legal idiom: seeded Generator / Random instances — pure
        # functions of their seed, thread-local by construction
        assert _lint("""
            import random
            import numpy as np
            def make_epoch(seed, epoch, n):
                order = np.random.default_rng([seed, epoch]).permutation(n)
                r = random.Random(seed)
                picks = [r.random() for _ in range(4)]
                return order, picks
        """, path=self.DATA_PATH) == []

    def test_out_of_scope_path_clean(self):
        assert _lint("""
            import random
            def jitter(d):
                return d * random.random()
        """, path="deeplearning4j_tpu/serving/fixture.py") == []

    def test_inline_waiver(self):
        assert _lint("""
            import random
            def sample_debug(items):
                return random.sample(items, 2)  # lint: disable=DLT011 (debug only)
        """, path=self.DATA_PATH) == []


class TestCompileIntrospectionInHotPath:
    SERVING_PATH = "deeplearning4j_tpu/serving/fixture.py"

    def test_fires_on_lower_compile_in_serving(self):
        vs = _lint("""
            import jax
            def dispatch(step, args):
                compiled = step.lower(*args).compile()
                return compiled(*args)
        """, path=self.SERVING_PATH)
        assert _rules(vs) == ["DLT012"]
        assert "autotune-time" in vs[0].message

    def test_fires_on_cost_analysis_in_parallel(self):
        vs = _lint("""
            def serve_batch(compiled, x):
                cost = compiled.cost_analysis()
                return compiled(x), cost
        """, path="deeplearning4j_tpu/parallel/fixture.py")
        assert _rules(vs) == ["DLT012"]

    def test_fires_on_memory_analysis_in_train_path(self):
        vs = _lint("""
            def _fit_batch(self, step, ds):
                ma = step.lower(ds).compile().memory_analysis()
                return ma
        """, path="deeplearning4j_tpu/nn/multilayer.py")
        # the .lower().compile() chain AND the introspection call both fire
        assert _rules(vs) == ["DLT012", "DLT012"]

    def test_autotune_and_memory_report_out_of_scope(self):
        # the tools that OWN lower/compile introspection stay clean: the
        # autotuner, the planner, nn/memory reports, the tools
        src = """
            def estimate(step, args):
                return step.lower(*args).compile().cost_analysis()
        """
        for path in ("deeplearning4j_tpu/perf/autotune.py",
                     "deeplearning4j_tpu/nn/memory.py",
                     "tools/autotune.py"):
            assert _lint(src, path=path) == []

    def test_plain_compile_not_flagged(self):
        # an ordinary .compile() (regex, template) is not the XLA chain
        assert _lint("""
            import re
            def route(pattern, path):
                return re.compile(pattern).match(path)
        """, path=self.SERVING_PATH) == []

    def test_inline_waiver(self):
        assert _lint("""
            def dispatch(step, args):
                return step.lower(*args).compile()  # lint: disable=DLT012 (warmup path, offline)
        """, path=self.SERVING_PATH) == []


class TestHostWorkInRetrieval:
    RETRIEVAL_PATH = "deeplearning4j_tpu/retrieval/thing.py"

    def test_fires_on_np_in_jitted_kernel(self):
        vs = _lint("""
            import functools
            import jax
            import jax.numpy as jnp
            import numpy as np
            @functools.partial(jax.jit, static_argnames=("k",))
            def _rank_all(q, vecs, k):
                d = jnp.matmul(q, vecs.T)
                return np.argsort(d)
        """, path=self.RETRIEVAL_PATH)
        assert _rules(vs) == ["DLT013"]
        assert "host numpy" in vs[0].message

    def test_fires_on_item_and_device_get_in_score_fn(self):
        vs = _lint("""
            import jax
            import jax.numpy as jnp
            def score_cells(q, cells):
                d = jnp.einsum("bd,cd->bc", q, cells)
                best = d.min().item()
                return jax.device_get(d), best
        """, path=self.RETRIEVAL_PATH)
        assert _rules(vs) == ["DLT013", "DLT013"]

    def test_host_side_wrapper_and_builders_exempt(self):
        # the padding wrapper around the dispatch and pure-host builders
        # are the designed host boundary — out of scope by construction
        assert _lint("""
            import numpy as np
            import jax.numpy as jnp
            def search(self, queries, k):
                q = np.asarray(queries, np.float32)
                dist, idx = self._search_device(jnp.asarray(q), k)
                return np.asarray(idx), np.asarray(dist)
            def build_table(vecs):
                return np.clip(np.rint(vecs), -127, 127)
        """, path=self.RETRIEVAL_PATH) == []

    def test_out_of_scope_path_clean(self):
        assert _lint("""
            import jax.numpy as jnp
            import numpy as np
            def score_stuff(x):
                return np.asarray(jnp.abs(x))
        """, path="deeplearning4j_tpu/perf/thing.py") == []

    def test_inline_waiver(self):
        assert _lint("""
            import jax.numpy as jnp
            import numpy as np
            def probe_debug(q):
                v = jnp.abs(q)
                return np.asarray(v)  # lint: disable=DLT013 (debug dump)
        """, path=self.RETRIEVAL_PATH) == []


class TestHostNibbleUnpack:
    PACK_PATH = "deeplearning4j_tpu/quant/pack.py"
    PQ_PATH = "deeplearning4j_tpu/retrieval/pq.py"

    def test_fires_on_np_unpack_next_to_jnp(self):
        vs = _lint("""
            import jax.numpy as jnp
            import numpy as np
            def unpack_nibbles_fast(packed, d):
                lo = (np.left_shift(packed, 4) >> 4)
                return jnp.asarray(lo[..., :d])
        """, path=self.PACK_PATH)
        assert _rules(vs) == ["DLT014"]
        assert "host numpy" in vs[0].message

    def test_fires_on_item_in_adc_fn(self):
        vs = _lint("""
            import jax.numpy as jnp
            def adc_accumulate(lut, codes):
                d2 = jnp.take(lut, codes, axis=1)
                return d2.min().item()
        """, path=self.PQ_PATH)
        assert _rules(vs) == ["DLT014"]

    def test_fires_on_device_get_in_pq_fn(self):
        vs = _lint("""
            import jax
            import jax.numpy as jnp
            def score_pq_debug(lut):
                return jax.device_get(jnp.sum(lut))
        """, path=self.PQ_PATH)
        # name matches DLT013 (score) AND DLT014 (pq) — both rules own it
        assert "DLT014" in _rules(vs)

    def test_pure_host_packer_exempt(self):
        # the build-time boundary: packs with numpy, touches no jnp
        assert _lint("""
            import numpy as np
            def pack_nibbles(codes):
                u = codes.astype(np.uint8)
                return ((u[..., 0::2] & 0xF) | ((u[..., 1::2] & 0xF) << 4)
                        ).view(np.int8)
        """, path=self.PACK_PATH) == []

    def test_out_of_scope_path_clean(self):
        assert _lint("""
            import jax.numpy as jnp
            import numpy as np
            def pack_records(x):
                return np.asarray(jnp.abs(x))
        """, path="deeplearning4j_tpu/perf/thing.py") == []

    def test_inline_waiver(self):
        assert _lint("""
            import jax.numpy as jnp
            import numpy as np
            def unpack_probe(packed):
                v = jnp.asarray(packed)
                return np.asarray(v)  # lint: disable=DLT014 (test helper)
        """, path=self.PACK_PATH) == []


class TestHostWorkInPallasKernel:
    KERNEL_PATH = "deeplearning4j_tpu/perf/pallas/fixture.py"

    def test_fires_on_host_calls_in_kernel_body(self):
        vs = _lint("""
            import numpy as np
            import jax
            def _bad_kernel(x_ref, o_ref):
                v = np.sum(x_ref[...])
                s = x_ref[0, 0].item()
                h = jax.device_get(x_ref[...])
                o_ref[...] = v
        """, path=self.KERNEL_PATH)
        assert _rules(vs) == ["DLT015"] * 3
        assert "host numpy" in vs[0].message
        assert ".item()" in vs[1].message
        assert "device_get" in vs[2].message

    def test_fires_on_unhoisted_control_flow(self):
        vs = _lint("""
            def _bad_kernel(x_ref, o_ref):
                s = 4
                while s > 0:
                    s -= 1
                for row in x_ref[...]:
                    pass
                if x_ref:
                    o_ref[...] = x_ref[...]
        """, path=self.KERNEL_PATH)
        assert _rules(vs) == ["DLT015"] * 3
        assert "'while'" in vs[0].message
        assert "non-range" in vs[1].message
        assert "kernel block ref" in vs[2].message

    def test_detects_refs_vararg_kernels(self):
        # Kernels taking ``*refs`` (partial-bound statics) are still in scope.
        vs = _lint("""
            import numpy as np
            def accumulate(n_rows, *refs):
                z_ref, o_ref = refs
                o_ref[...] = np.asarray(z_ref[...])
        """, path=self.KERNEL_PATH)
        assert _rules(vs) == ["DLT015"]

    def test_clean_kernel_passes(self):
        # Static-bool ``if`` and ``for m in range(...)`` are the sanctioned
        # unroll idioms — must not be flagged.
        assert _lint("""
            def _clean_kernel(m_count, has_res, x_ref, o_ref):
                acc = x_ref[...] * 0
                for m in range(m_count):
                    acc = acc + x_ref[...]
                if has_res:
                    acc = acc + 1
                o_ref[...] = acc
        """, path=self.KERNEL_PATH) == []

    def test_non_kernel_function_ignored(self):
        assert _lint("""
            import numpy as np
            def build_lut(codebooks):
                return np.einsum("mkd,mkd->mk", codebooks, codebooks)
        """, path=self.KERNEL_PATH) == []

    def test_out_of_scope_path_clean(self):
        assert _lint("""
            import numpy as np
            def _bad_kernel(x_ref, o_ref):
                o_ref[...] = np.sum(x_ref[...])
        """, path="deeplearning4j_tpu/retrieval/fixture.py") == []

    def test_inline_waiver(self):
        assert _lint("""
            import numpy as np
            def _probe_kernel(x_ref, o_ref):
                o_ref[...] = np.sum(x_ref[...])  # lint: disable=DLT015 (interpret-only debug probe)
        """, path=self.KERNEL_PATH) == []


class TestBlockingIoWithoutTimeout:
    PATH = "deeplearning4j_tpu/fleet/router.py"

    def test_fires_on_urlopen_without_timeout(self):
        vs = _lint("""
            import urllib.request
            def scrape(addr):
                return urllib.request.urlopen(addr + "/metrics").read()
        """, path=self.PATH)
        assert _rules(vs) == ["DLT016"]
        assert "timeout" in vs[0].message

    def test_fires_on_http_connection_without_timeout(self):
        vs = _lint("""
            import http.client
            def forward(host, port):
                return http.client.HTTPConnection(host, port)
        """, path="deeplearning4j_tpu/serving/server.py")
        assert _rules(vs) == ["DLT016"]

    def test_fires_on_from_import_alias(self):
        vs = _lint("""
            from urllib.request import urlopen
            def scrape(addr):
                return urlopen(addr).read()
        """, path=self.PATH)
        assert _rules(vs) == ["DLT016"]

    def test_fires_on_create_connection(self):
        vs = _lint("""
            import socket
            def probe(addr):
                return socket.create_connection(addr)
        """, path=self.PATH)
        assert _rules(vs) == ["DLT016"]

    def test_clean_with_timeout_kwarg(self):
        assert _lint("""
            import http.client
            import urllib.request
            def forward(host, port, addr):
                c = http.client.HTTPConnection(host, port, timeout=5.0)
                return c, urllib.request.urlopen(addr, timeout=2.0)
        """, path=self.PATH) == []

    def test_clean_with_positional_timeout(self):
        assert _lint("""
            import socket
            def probe(addr):
                return socket.create_connection(addr, 5.0)
        """, path=self.PATH) == []

    def test_out_of_scope_path_is_exempt(self):
        assert _lint("""
            import urllib.request
            def fetch(url):
                return urllib.request.urlopen(url).read()
        """, path="deeplearning4j_tpu/datasets/fetchers.py") == []

    def test_inline_waiver(self):
        assert _lint("""
            import urllib.request
            def fetch(url):
                # deliberate unbounded wait: caller owns the deadline
                return urllib.request.urlopen(url)  # lint: disable=DLT016
        """, path=self.PATH) == []


class TestUnboundedLakeIo:
    PATH = "deeplearning4j_tpu/checkpoint/cloud.py"

    def test_fires_on_unbounded_response_read(self):
        vs = _lint("""
            import http.client
            def fetch(host):
                conn = http.client.HTTPConnection(host, timeout=5.0)
                conn.request("GET", "/o")
                return conn.getresponse().read()
        """, path=self.PATH)
        assert _rules(vs) == ["DLT021"]
        assert "byte bound" in vs[0].message

    def test_fires_on_unbounded_recv_and_readline(self):
        vs = _lint("""
            def drain(sock, f):
                return sock.recv(), f.readline()
        """, path="deeplearning4j_tpu/checkpoint/emulator.py")
        assert _rules(vs) == ["DLT021", "DLT021"]

    def test_fires_on_connection_without_timeout(self):
        vs = _lint("""
            import http.client
            def connect(host):
                return http.client.HTTPConnection(host)
        """, path="deeplearning4j_tpu/tools/lake.py")
        assert _rules(vs) == ["DLT021"]
        assert "timeout" in vs[0].message

    def test_clean_when_bounded_and_timed(self):
        assert _lint("""
            import http.client
            def fetch(host, n):
                conn = http.client.HTTPConnection(host, timeout=5.0)
                conn.request("GET", "/o")
                return conn.getresponse().read(n)
        """, path=self.PATH) == []

    def test_out_of_scope_path_is_exempt(self):
        # DLT021 is the lake-path extension of DLT016 — neither fires
        # on a path outside both scopes
        assert _lint("""
            def fetch(resp):
                return resp.read()
        """, path="deeplearning4j_tpu/datasets/fetchers.py") == []

    def test_inline_waiver(self):
        assert _lint("""
            def drain(resp):
                # stream provably bounded by the framing layer above
                return resp.read()  # lint: disable=DLT021
        """, path=self.PATH) == []


class TestPerTokenHostTransfer:
    PATH = "deeplearning4j_tpu/serving/decode.py"

    def test_fires_on_np_and_item_in_token_loop(self):
        vs = _lint("""
            import jax.numpy as jnp
            import numpy as np
            def decode_step(params, carry, toks):
                outs = []
                for t in range(50):
                    carry = jnp.tanh(carry @ params)
                    outs.append(np.asarray(carry))
                    tid = carry.sum().item()
                return outs
        """, path=self.PATH)
        assert _rules(vs) == ["DLT020", "DLT020"]
        assert "per-token" in vs[0].message

    def test_fires_on_device_get_in_while_sampling(self):
        vs = _lint("""
            import jax
            import jax.numpy as jnp
            def sample_stream(logits, n):
                while n > 0:
                    tok = jax.device_get(jnp.argmax(logits))
                    n -= 1
        """, path="deeplearning4j_tpu/nn/multilayer.py")
        assert _rules(vs) == ["DLT020"]

    def test_clean_bulk_read_outside_loop(self):
        assert _lint("""
            import jax.numpy as jnp
            import numpy as np
            def decode_step(params, carry):
                for t in range(50):
                    carry = jnp.tanh(carry @ params)
                return np.asarray(carry)
        """, path=self.PATH) == []

    def test_non_decode_function_is_exempt(self):
        assert _lint("""
            import jax.numpy as jnp
            import numpy as np
            def pad_batch(params, rows):
                out = []
                for r in rows:
                    out.append(np.asarray(jnp.asarray(r)))
                return out
        """, path=self.PATH) == []

    def test_pure_host_decode_helper_is_exempt(self):
        # no jnp/lax device math in the function: host json decode etc.
        assert _lint("""
            import numpy as np
            def decode_events(blocks):
                out = []
                for b in blocks:
                    out.append(np.frombuffer(b, dtype=np.uint8))
                return out
        """, path=self.PATH) == []

    def test_out_of_scope_path_is_exempt(self):
        assert _lint("""
            import jax.numpy as jnp
            import numpy as np
            def decode_step(params, carry, toks):
                for t in range(50):
                    carry = jnp.tanh(carry @ params)
                    toks.append(np.asarray(carry))
        """, path="deeplearning4j_tpu/datasets/iterator.py") == []

    def test_inline_waiver(self):
        assert _lint("""
            import jax.numpy as jnp
            import numpy as np
            def decode_debug(params, carry):
                for t in range(3):
                    carry = jnp.tanh(carry @ params)
                    print(np.asarray(carry))  # lint: disable=DLT020
                return carry
        """, path=self.PATH) == []


class TestFileWaiver:
    def test_disable_file(self):
        vs = _lint("""
            # lint: disable-file=DLT001 (import-time table is intentional)
            import jax.numpy as jnp
            TABLE = jnp.arange(1024)
        """)
        assert vs == []


def test_repo_lints_clean_within_budget():
    """Tier-1 gate, three assertions in one sweep: (a) the whole package +
    benches + tools lint clean under DLT001-020 (every pre-existing
    violation was fixed or waived inline with justification); (b) the cold
    run — summaries + call graph from scratch — stays under a 60s budget;
    (c) a warm run served from the content-hash caches is >=5x faster and
    reports identical findings."""
    clear_caches()
    t0 = time.perf_counter()
    violations = lint_paths(DEFAULT_TARGETS(REPO_ROOT))
    cold = time.perf_counter() - t0
    assert violations == [], "\n".join(str(v) for v in violations)

    t0 = time.perf_counter()
    warm_violations = lint_paths(DEFAULT_TARGETS(REPO_ROOT))
    warm = time.perf_counter() - t0
    assert warm_violations == violations
    assert cold < 60.0, f"cold whole-repo lint took {cold:.1f}s"
    assert warm * 5 <= cold, f"warm {warm:.3f}s vs cold {cold:.3f}s"


# ---------------------------------------------------------------------------
# interprocedural rules (DLT017/018/019) against the checked-in fixtures
# ---------------------------------------------------------------------------


class TestHostWorkFromJit:
    def _findings(self):
        return [v for v in lint_paths([os.path.join(FIXTURES, "hostwork_pkg")])
                if v.rule == "DLT017"]

    def test_reports_clock_two_hops_from_jit(self):
        clock = [v for v in self._findings() if "time.time" in v.message]
        assert len(clock) == 1
        v = clock[0]
        assert v.file.endswith(os.path.join("hostwork_pkg", "hostutil.py"))
        assert v.line == 11
        assert ("hostwork_pkg.entry.predict -> hostwork_pkg.stats.standardize"
                " -> hostwork_pkg.hostutil.drift_scale") in v.message
        assert "2 call hops" in v.message

    def test_reports_host_numpy_in_same_chain(self):
        np_hits = [v for v in self._findings() if "numpy.asarray" in v.message]
        assert len(np_hits) == 1
        assert np_hits[0].line == 12
        assert "hostwork_pkg.entry.predict" in np_hits[0].message

    def test_waiver_suppresses_and_registers_live(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "entry.py").write_text(textwrap.dedent("""
            import jax
            from . import util
            @jax.jit
            def step(x):
                return util.scale(x)
        """))
        (pkg / "util.py").write_text(textwrap.dedent("""
            import time
            import jax.numpy as jnp
            def scale(x):
                t = time.time()  # lint: disable=DLT017 (trace-time constant is fine)
                return x * jnp.float32(t)
        """))
        assert lint_paths([str(pkg)]) == []
        assert audit_waivers([str(pkg)]) == []


class TestCrossModuleLocks:
    def test_opposite_order_across_two_classes_two_files(self):
        vs = [v for v in lint_paths([os.path.join(FIXTURES, "lockpair_pkg")])
              if v.rule == "DLT018"]
        assert len(vs) == 1
        msg = vs[0].message
        assert "lockpair_pkg.journal.Journal._journal_lock" in msg
        assert "lockpair_pkg.state.StateManager._state_lock" in msg
        assert "journal.py" in msg and "state.py" in msg

    def test_same_class_direct_pair_is_dlt004_not_dlt018(self, tmp_path):
        # Both directions direct, same owner class: DLT004's per-file turf.
        mod = tmp_path / "pair.py"
        mod.write_text(textwrap.dedent("""
            import threading
            class M:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()
                def one(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass
                def two(self):
                    with self._b_lock:
                        with self._a_lock:
                            pass
        """))
        rules = _rules(lint_paths([str(tmp_path)]))
        assert "DLT004" in rules and "DLT018" not in rules

    def test_blocking_io_under_lock_in_serving_path(self, tmp_path):
        serving = tmp_path / "serving"
        serving.mkdir()
        (serving / "poller.py").write_text(textwrap.dedent("""
            import threading
            import urllib.request
            class Poller:
                def __init__(self):
                    self._lock = threading.Lock()
                def poll(self, url):
                    with self._lock:
                        return urllib.request.urlopen(url, timeout=1.0)
        """))
        vs = [v for v in lint_paths([str(serving)]) if v.rule == "DLT018"]
        assert len(vs) == 1
        assert "urlopen" in vs[0].message and "_lock" in vs[0].message

    def test_blocking_io_reached_through_callee(self, tmp_path):
        serving = tmp_path / "serving"
        serving.mkdir()
        (serving / "drain.py").write_text(textwrap.dedent("""
            import queue
            import threading
            class Drainer:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._queue = queue.Queue(maxsize=8)
                def _take(self):
                    return self._queue.get(timeout=0.1)
                def drain(self):
                    with self._lock:
                        return self._take()
        """))
        vs = [v for v in lint_paths([str(serving)]) if v.rule == "DLT018"]
        assert len(vs) == 1
        assert "queue.get" in vs[0].message and "_take" in vs[0].message


class TestThreadLifecycle:
    def test_leaked_thread_flagged_managed_twin_clean(self):
        vs = [v for v in lint_paths([os.path.join(FIXTURES, "leaky_threads.py")])
              if v.rule == "DLT019"]
        assert len(vs) == 1
        assert vs[0].line == 8
        assert "daemon" in vs[0].message and "join" in vs[0].message

    def test_handle_joined_in_sibling_method_clean(self, tmp_path):
        mod = tmp_path / "worker.py"
        mod.write_text(textwrap.dedent("""
            import threading
            class W:
                def start(self):
                    self._thread = threading.Thread(target=self._run)
                    self._thread.start()
                def stop(self):
                    self._thread.join()
                def _run(self):
                    pass
        """))
        assert [v for v in lint_paths([str(tmp_path)])
                if v.rule == "DLT019"] == []

    def test_daemon_true_clean(self, tmp_path):
        mod = tmp_path / "daemonized.py"
        mod.write_text(textwrap.dedent("""
            import threading
            def spawn(fn):
                t = threading.Thread(target=fn, daemon=True)
                t.start()
        """))
        assert [v for v in lint_paths([str(tmp_path)])
                if v.rule == "DLT019"] == []


# ---------------------------------------------------------------------------
# call-graph name resolution edge cases
# ---------------------------------------------------------------------------


class TestCallGraphResolution:
    def _pkg(self, tmp_path, files):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        for name, src in files.items():
            (pkg / name).write_text(textwrap.dedent(src))
        return str(pkg)

    def test_jnp_aliased_as_np_is_not_host_numpy(self, tmp_path):
        # ``import jax.numpy as np`` shadows the conventional numpy alias;
        # resolution must follow the alias table, not the surface name.
        pkg = self._pkg(tmp_path, {
            "entry.py": """
                import jax
                from . import util
                @jax.jit
                def step(x):
                    return util.pad(x)
            """,
            "util.py": """
                import jax.numpy as np
                def pad(x):
                    return np.concatenate([x, np.zeros(3)])
            """,
        })
        assert [v for v in lint_paths([pkg]) if v.rule == "DLT017"] == []

    def test_real_numpy_behind_same_alias_is_flagged(self, tmp_path):
        pkg = self._pkg(tmp_path, {
            "entry.py": """
                import jax
                from . import util
                @jax.jit
                def step(x):
                    return util.pad(x)
            """,
            "util.py": """
                import numpy as np
                import jax.numpy as jnp
                def pad(x):
                    return jnp.asarray(np.zeros(3)) + x
            """,
        })
        vs = [v for v in lint_paths([pkg]) if v.rule == "DLT017"]
        assert len(vs) == 1 and "numpy.zeros" in vs[0].message

    def test_inherited_method_resolved_across_modules(self, tmp_path):
        pkg = self._pkg(tmp_path, {
            "base.py": """
                import time
                class Base:
                    def slow(self, x):
                        return x + time.time()
            """,
            "sub.py": """
                import jax
                from .base import Base
                class Sub(Base):
                    @jax.jit
                    def run(self, x):
                        return self.slow(x)
            """,
        })
        vs = [v for v in lint_paths([pkg]) if v.rule == "DLT017"]
        assert len(vs) == 1
        assert "pkg.base.Base.slow" in vs[0].message
        assert vs[0].file.endswith("base.py")

    def test_functools_partial_target_is_traced(self, tmp_path):
        pkg = self._pkg(tmp_path, {
            "train.py": """
                import functools
                import jax
                from . import util
                CFG = {"lr": 0.1}
                def train_step(cfg, x):
                    return util.log_step(x)
                step = jax.jit(functools.partial(train_step, CFG))
            """,
            "util.py": """
                import time
                def log_step(x):
                    return x, time.time()
            """,
        })
        vs = [v for v in lint_paths([pkg]) if v.rule == "DLT017"]
        assert len(vs) == 1
        assert "pkg.train.train_step" in vs[0].message

    def test_lambda_passed_to_scan_is_traced(self, tmp_path):
        pkg = self._pkg(tmp_path, {
            "loop.py": """
                import jax.lax as lax
                from . import helpers
                def run_scan(xs):
                    return lax.scan(lambda c, x: (helpers.accumulate(c), x),
                                    0.0, xs)
            """,
            "helpers.py": """
                import time
                def accumulate(c):
                    return c + time.time()
            """,
        })
        vs = [v for v in lint_paths([pkg]) if v.rule == "DLT017"]
        assert len(vs) == 1
        assert "pkg.helpers.accumulate" in vs[0].message
        assert "<lambda>" in vs[0].message


# ---------------------------------------------------------------------------
# waiver audit
# ---------------------------------------------------------------------------


class TestWaiverAudit:
    def test_stale_inline_waiver_reported(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent("""
            import jax.numpy as jnp
            TABLE = jnp.arange(4)  # lint: disable=DLT001 (tiny import-time table)
            def f():
                return 1  # lint: disable=DLT003 (nothing ever fired here)
        """))
        stale = audit_waivers([str(tmp_path)])
        assert len(stale) == 1
        assert stale[0].rules == ("DLT003",)
        assert stale[0].scope == "inline"

    def test_stale_file_waiver_reported(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent("""
            # lint: disable-file=DLT008 (no queues here any more)
            def f():
                return 1
        """))
        stale = audit_waivers([str(tmp_path)])
        assert len(stale) == 1
        assert stale[0].rules == ("DLT008",)
        assert stale[0].scope == "file"

    def test_repo_rule_waiver_counts_as_live(self, tmp_path):
        mod = tmp_path / "spawn.py"
        mod.write_text(textwrap.dedent("""
            import threading
            def fire_and_forget(fn):
                t = threading.Thread(target=fn)  # lint: disable=DLT019 (process-lifetime helper)
                t.start()
        """))
        assert lint_paths([str(tmp_path)]) == []
        assert audit_waivers([str(tmp_path)]) == []

    def test_repo_waivers_all_live(self):
        assert audit_waivers(DEFAULT_TARGETS(REPO_ROOT)) == []


# ---------------------------------------------------------------------------
# tools/run_lint.py CLI contract
# ---------------------------------------------------------------------------


def _load_run_lint():
    spec = importlib.util.spec_from_file_location(
        "run_lint_under_test", os.path.join(REPO_ROOT, "tools", "run_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestRunLintCLI:
    def test_json_rule_filter_and_exit_code(self, capsys):
        run_lint = _load_run_lint()
        rc = run_lint.main(["run_lint.py", "--json", "--rule", "DLT018",
                            os.path.join(FIXTURES, "lockpair_pkg")])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert payload["count"] == 1
        assert payload["violations"][0]["rule"] == "DLT018"
        assert payload["violations"][0]["file"].endswith("journal.py")

    def test_json_carries_call_chain(self, capsys):
        run_lint = _load_run_lint()
        rc = run_lint.main(["run_lint.py", "--json", "--rule", "DLT017",
                            os.path.join(FIXTURES, "hostwork_pkg")])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        chains = [v["chain"] for v in payload["violations"]]
        assert ["hostwork_pkg.entry.predict",
                "hostwork_pkg.stats.standardize",
                "hostwork_pkg.hostutil.drift_scale"] in chains

    def test_rule_filter_to_zero_exits_clean(self, capsys):
        run_lint = _load_run_lint()
        rc = run_lint.main(["run_lint.py", "--rule", "DLT001",
                            os.path.join(FIXTURES, "lockpair_pkg")])
        capsys.readouterr()
        assert rc == 0

    def test_changed_only_filters_reporting(self, capsys, monkeypatch):
        run_lint = _load_run_lint()
        leaky = os.path.abspath(os.path.join(FIXTURES, "leaky_threads.py"))
        monkeypatch.setattr(run_lint, "_changed_files", lambda root: {leaky})
        rc = run_lint.main(["run_lint.py", "--json", "--changed-only",
                            FIXTURES])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert {v["rule"] for v in payload["violations"]} == {"DLT019"}

        monkeypatch.setattr(run_lint, "_changed_files", lambda root: set())
        rc = run_lint.main(["run_lint.py", "--changed-only", FIXTURES])
        capsys.readouterr()
        assert rc == 0

    def test_bad_rule_and_unknown_option_exit_2(self, capsys):
        run_lint = _load_run_lint()
        assert run_lint.main(["run_lint.py", "--rule", "BOGUS"]) == 2
        assert run_lint.main(["run_lint.py", "--frobnicate"]) == 2
        capsys.readouterr()

    def test_audit_waivers_flag(self, capsys, tmp_path):
        run_lint = _load_run_lint()
        mod = tmp_path / "mod.py"
        mod.write_text("def f():\n    return 1  # lint: disable=DLT003 (stale)\n")
        rc = run_lint.main(["run_lint.py", "--json", "--audit-waivers",
                            str(tmp_path)])
        payload = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert len(payload["stale_waivers"]) == 1
        assert payload["stale_waivers"][0]["rules"] == ["DLT003"]

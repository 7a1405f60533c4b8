"""Framework linter: repo-specific AST rules that encode TPU discipline.

Rules (waivable per line with ``# lint: disable=DLT00X`` or per file with
``# lint: disable-file=DLT00X``):

- **DLT001 module-level-jnp**: no ``jnp.``/``jax.numpy``/``lax.`` computation
  at module import time (module or class scope, decorators, default args).
  Import-time device work initializes the backend before configs are read,
  serializes startup behind compiles, and breaks ``JAX_PLATFORMS`` forcing.

- **DLT002 impure-in-jit**: no ``time.*`` clocks or host ``random.*`` /
  ``np.random.*`` calls inside jit-traced code paths (functions decorated
  with / passed to ``jax.jit``, ``lax.scan``/``while_loop``/``fori_loop``/
  ``cond``, ``vmap``, ``grad``, ``shard_map``, ...). These run ONCE at trace
  time and freeze into the compiled program as constants — the classic
  silent "my noise is the same every step" bug.

- **DLT003 bench-timing-sync**: in benchmark/tooling files (``bench*``,
  ``*perf*``, ``tools/``), a function that reads the wall clock twice must
  also synchronize (``block_until_ready``/``device_get``/``np.asarray``/
  ``float(...)``/``.item()``) — JAX dispatch is asynchronous, so an
  unsynced stopwatch measures dispatch latency, not execution.

- **DLT004 lock-order**: extracts nested lock-acquisition orderings per
  class — through ``with`` blocks AND explicit ``acquire()`` /
  ``release()`` sequences (including the ``acquire(); try: ... finally:
  release()`` idiom) — and flags a pair of locks taken in opposite orders
  by different methods as deadlock risk (the ``parallel/`` +
  ``checkpoint/`` subsystems are lock-heavy and multi-threaded). Same-
  class only; the cross-class/cross-module surface is DLT018's.

- **DLT005 serving-bn-fold**: a file that builds a model with
  ``BatchNormalization`` AND serves it through ``ParallelInference`` —
  without ever folding (``fold_bn``) — pays per-request BN normalize
  traffic that ``perf.fusion.fold_bn`` eliminates exactly (and any
  ``train=True`` call on that serving path would run BN-*train* semantics
  on request batches). Fold for serving, or waive inline like DLT003.

- **DLT006 swallowed-storage-error**: in checkpoint/storage code paths
  (``checkpoint/``, ``storage/`` files), an ``except Exception:`` /
  ``except BaseException:`` / bare ``except:`` handler that neither
  re-raises, nor logs, nor stashes the exception for later re-raise
  silently eats exactly the durability faults this subsystem exists to
  surface — a checkpoint that "saved" into a swallowed error is a run
  that dies at restore time. Narrow the handler, log it, or waive inline
  like DLT003.

- **DLT007 metric-registration**: metrics belong in the ``obs``
  MetricsRegistry **with units and help text** — two checks: (a) a
  ``.counter(...)`` / ``.gauge(...)`` / ``.histogram(...)`` call on a
  registry-named receiver (last segment containing ``registry``, or
  ``reg`` / ``metrics``) must pass both ``unit=`` and ``help=`` (empty
  literals count as missing); (b) no NEW bare counter dicts — assigning
  ``{}`` / ``dict()`` / ``Counter()`` / ``defaultdict(...)`` to a name
  (lowercased) equal to ``counters`` or ending ``_counters``. An
  unlabeled number on a dashboard is a guess. Pre-obs surfaces
  (``CompileWatch``, ``TrainingStats``) are absorbed into the registry by
  ``obs.absorb_*`` and carry inline waivers.

- **DLT008 unbounded-queue**: in serving/parallel/datasets/storage/
  checkpoint paths, a ``queue.Queue()`` with no ``maxsize`` (or an
  explicit ``maxsize=0``) is an unbounded buffer between threads — a
  stalled consumer then grows host memory without limit and every
  producer waits forever instead of failing fast. Pass a bound (with
  explicit full-queue semantics, e.g. ``ParallelInference``'s
  block-with-timeout ⇒ ``QueueFullError``), or waive inline like DLT003.

- **DLT009 host-work-in-compression-path**: gradient compress/encode/
  decode paths run INSIDE the traced train step (parallel/compress.py) —
  host-side work there (``np.*`` calls, ``.item()``, ``jax.device_get``)
  forces a host-device sync per step, exactly the pipeline collapse the
  compressed collective exists to avoid. Scope: functions whose name
  contains ``compress`` (or any method of a class named ``*Compression*``)
  that ALSO use ``jnp``/``jax`` device math — mixed host+device code in a
  compression path; pure-host readers (scrape-time absorbers with no jnp)
  are exempt by construction. Waivable inline like DLT003.

- **DLT010 float-cast-in-quant-path**: int8 quantized-inference code
  (quant/lowering.py) earns its ~4x by KEEPING tensors int8 until the one
  per-layer requantize — an ``.astype(jnp.float32)`` / ``.astype(float64)``
  / ``jnp.float64(...)`` on a tensor inside the quant path silently turns
  the int8 matmul back into a float one (dequant-per-element in the hot
  loop) while all tests still pass numerically. Scope: methods of classes
  named ``*Quantized*`` (quantized layer code is device code by
  construction), plus functions whose name contains ``quant`` that ALSO
  use ``jnp``/``lax`` device math — pure-host helpers (bench data prep,
  CLI loaders) are exempt, the DLT009 precedent. Scalar wraps of Python
  floats (``jnp.float32(1.0 / s)``) and int casts (``.astype(jnp.int8)``,
  the quantize itself) are exempt. float64 is flagged anywhere in scope
  (it defeats both the int8 path and the f32 serving dtype). Waivable
  inline like DLT003.

- **DLT011 unseeded-global-rng-in-data-path**: in datasets/parallel code
  paths, shuffle/sampling through MODULE-LEVEL RNG state
  (``random.shuffle/sample/choice/random/randint/uniform``,
  ``np.random.shuffle/permutation/choice/randint/random/rand/randn`` and
  ``np.random.seed``) is the deterministic-epoch hazard: the data plane's
  exactly-once resume and any-world bitwise epochs (datasets/sharded.py)
  require every shuffle to be a pure function of ``(seed, epoch)``, and
  global-state draws also race across the prefetch threads these paths
  run on. Use a seeded instance — ``np.random.default_rng(seed)`` /
  ``random.Random(seed)`` — instead; those are exempt by construction
  (method calls on an instance, not the module). Waivable inline like
  DLT003.

- **DLT012 compile-introspection-in-hot-path**: in serving/training hot
  paths (``serving/``, ``parallel/``, ``nn/multilayer.py``,
  ``nn/graph.py``), a ``.lower(...).compile()`` chain or a
  ``.cost_analysis()`` / ``.memory_analysis()`` call re-invokes XLA
  compilation/introspection on code that runs per request or per step —
  seconds of compile stall on a path budgeted in microseconds. These are
  AUTOTUNE-TIME tools (perf/autotune.py, perf/planner.py, nn/memory.py
  reports, benches); thread their RESULTS in via a TuningRecord/plan
  instead. Waivable inline like DLT003.

- **DLT013 host-work-in-retrieval-hot-path**: the retrieval scoring path
  (``retrieval/``) exists to keep the whole query batch on device — one
  matmul + ``lax.top_k`` per dispatch, zero host syncs (the trace_check
  tier-1 gate). Host work inside a scoring function — ``np.*`` distance
  math, ``.item()``, ``jax.device_get`` — silently reintroduces the
  per-query host round-trip the host VPTree already had. Scope (the
  DLT009 mixed host/device shape): in ``retrieval/`` files, functions
  that are jit-decorated (``@jax.jit`` / ``@functools.partial(jax.jit,
  ...)``) or whose name contains ``score``/``topk``/``probe``, and that
  use ``jnp``/``lax`` device math; pure-host helpers (builders, wire
  codecs, the padding wrappers around the dispatch) are exempt by
  construction. Waivable inline like DLT003.

- **DLT014 host-nibble-unpack-in-pack-path**: packed-code paths
  (``quant/pack.py`` int4 nibbles, ``retrieval/pq.py`` PQ codes) earn
  their compression by keeping the PACKED array resident and unpacking
  with shift/mask INSIDE the jitted scorer — host-side unpacking
  (``np.*`` on the codes, ``.item()``, ``jax.device_get``) materializes
  the unpacked table on the host per dispatch, exactly the ×2 (int4) /
  ×4d/M (PQ) the packing bought. Scope (the DLT009 mixed host/device
  shape): in ``retrieval/`` and ``quant/`` files, functions whose name
  contains ``pack``/``unpack``/``nibble``/``adc``/``pq`` that ALSO use
  ``jnp``/``lax`` device math; pure-host packers/builders (no jnp — the
  build-time boundary) are exempt by construction. Waivable inline like
  DLT003.

- **DLT015 host-work-in-pallas-kernel**: a Pallas kernel body
  (``perf/pallas/`` functions named ``*_kernel`` or taking ``*_ref``
  block arguments) runs per grid program on VMEM-resident blocks —
  interpret mode on CPU will happily execute host work or unhoisted
  Python control flow, and the bug only detonates when the TPU round
  Mosaic-compiles the same body. Flagged: host work (``np.*`` calls,
  ``.item()``, ``jax.device_get``), ``while`` loops, ``for`` loops over
  anything but a static ``range(...)``, and ``if`` statements whose test
  reads a ``*_ref`` block (data-dependent Python branching on traced
  values — hoist to ``pl.when``/``jnp.where``, or lift the decision to a
  static kernel parameter). Static-parameter branches (``if has_res:``)
  and ``for m in range(M)`` unrolls are exempt by construction. Waivable
  inline like DLT003.

- **DLT016 blocking-io-without-timeout**: in ``fleet/`` + ``serving/``
  paths, outbound socket/HTTP-client calls (``urllib.request.urlopen``,
  ``http.client.HTTP(S)Connection``, ``socket.create_connection``,
  ``requests.*``) must carry an explicit timeout. The stdlib default is
  block-forever, and the router fans one client request out to replicas
  — a single hung upstream without a timeout wedges a handler thread
  permanently (under a burst, all of them). An explicit positional
  timeout argument counts; waivable inline for a deliberately unbounded
  wait.

- **DLT020 per-token-host-transfer**: in ``serving/`` + ``nn/`` paths,
  a host transfer (``np.*`` call, ``jax.device_get``, ``.item()``,
  ``.tolist()``) inside a LOOP body of a decode/sampling-shaped function
  (name mentions decode/sample/generate/stream/token) that also uses
  jnp/lax device math. The generative tier's contract is ONE device
  dispatch advancing every active session and ONE bulk readback per
  dispatch — a transfer inside the per-token loop reintroduces the
  per-session host round-trip continuous batching exists to kill
  (sessions × tokens syncs instead of one per step). Transfers outside
  loops (the single bulk read) are fine; waivable inline for a
  deliberately host-side helper.

- **DLT021 unbounded-lake-io**: in the data-lake wire paths
  (``checkpoint/cloud``, ``checkpoint/emulator``, ``tools/lake``), two
  hazards the DLT016 scope doesn't cover: (a) a zero-argument
  ``.read()``/``.recv()``/``.readline()`` on a response/socket/file
  object — an unbounded read lets one hostile or wedged peer allocate
  arbitrary host memory (pass an explicit byte bound; validate
  Content-Length first per utils/http.py); (b) the DLT016 blocking-call
  table (``HTTP(S)Connection``, ``urlopen``, ``create_connection``,
  ``requests.*``) without an explicit timeout — the object-store client
  retries around deadlines, so a block-forever default turns one stalled
  server into a hung training run. Waivable inline like DLT003.

Interprocedural rule families (DLT017-019) run over the whole-repo call
graph built by ``analysis/callgraph.py`` — they only fire from
``lint_paths`` (and the ``tools/run_lint.py`` CLI), never from
single-file ``lint_file``, because they need the cross-module symbol
table:

- **DLT017 host-work-reachable-from-jit**: computes the closure of
  functions reachable from every traced entry point (jit-decorated, or
  passed to ``jax.jit``/``lax.scan``/``vmap``/... anywhere in the repo)
  and re-applies the DLT002/009/013/014/015 host-work checks there:
  wall-clock and host-RNG calls always (they freeze into the compiled
  program at trace time — the DLT002 hazard, now visible N modules away);
  ``.item()`` / ``jax.device_get`` / ``block_until_ready`` always (a
  host-device sync or trace-time error inside the traced region); bare
  ``np.*`` calls only in functions that ALSO use jnp/lax device math (the
  DLT009/013/014 mixed host/device shape — pure-host helpers whose
  results become trace-time constants by design are exempt). Only
  functions ≥1 call-hop from the entry are reported (the entry's own body
  is DLT002's), and the message carries the full call chain. Waivable
  inline at the hazard line like DLT003.

- **DLT018 cross-module-lock-analysis**: builds the global
  lock-acquisition graph — ``with`` blocks and explicit ``acquire()`` /
  ``release()`` pairs, with held-lock sets propagated through resolved
  call edges — and flags (a) lock pairs acquired in opposite orders
  anywhere in the repo, across classes and modules (same-class pairs
  visible to DLT004 from direct nesting are left to DLT004), and (b)
  blocking I/O (``urlopen``, ``HTTPConnection``, ``queue.get/put``,
  ``subprocess``, ``block_until_ready``) executed — directly or via a
  callee — while a lock is held, in serving/fleet/checkpoint/parallel
  paths, where one slow upstream then convoys every thread behind the
  lock. Waivable inline at the acquisition/call line like DLT003.

- **DLT019 thread-lifecycle**: a ``threading.Thread`` started without
  ``daemon=True`` and without a recorded ``join()``/stop path (a join on
  the same local handle in the function, a join on the same ``self.``
  attribute anywhere in the class, a post-hoc ``t.daemon = True`` /
  ``setDaemon(True)``, or the handle being returned/pooled into a
  collection that is joined) leaks on shutdown — the fleet CLI and
  replica drain paths depend on clean teardown. Waivable inline at the
  construction line like DLT003.

Adding a rule: write a ``_rule_xxx(tree, src, path) -> List[LintViolation]``
function and register it in ``_RULES``; tests in ``tests/test_lint.py``
seed a fixture violating the rule and assert it fires. Interprocedural
rules take the built ``CallGraph`` instead and register in
``_REPO_RULES``.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
from typing import Dict, Iterable, List, Optional, Set, Tuple

from . import callgraph as _cg

__all__ = ["LintViolation", "StaleWaiver", "lint_file", "lint_paths",
           "audit_waivers", "clear_caches", "DEFAULT_TARGETS"]


@dataclasses.dataclass(frozen=True)
class LintViolation:
    file: str
    line: int
    rule: str
    message: str

    def __str__(self):
        return f"{self.file}:{self.line}: {self.rule} {self.message}"


# --------------------------------------------------------------- utilities
def _dotted(node: ast.AST) -> Optional[str]:
    """'jnp.zeros' for Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _import_aliases(tree: ast.Module) -> Dict[str, str]:
    """local name -> fully qualified module path, for top-level imports."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = \
                    a.name if a.asname else a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def _resolve(dotted: Optional[str], aliases: Dict[str, str]) -> str:
    """Expand the leading alias of a dotted path to its import target."""
    if not dotted:
        return ""
    head, _, rest = dotted.partition(".")
    base = aliases.get(head, head)
    return f"{base}.{rest}" if rest else base


_JNP_ROOTS = ("jax.numpy", "jax.lax", "jax.random")


def _is_jnp_call(call: ast.Call, aliases: Dict[str, str]) -> Optional[str]:
    q = _resolve(_dotted(call.func), aliases)
    if any(q == r or q.startswith(r + ".") for r in _JNP_ROOTS):
        return q
    return None


# ------------------------------------------------------------------ DLT001
def _rule_module_level_jnp(tree, src, path) -> List[LintViolation]:
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []

    def scan_import_time(nodes: Iterable[ast.AST]):
        for node in nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # decorators + default args evaluate at import; the body not
                scan_import_time(node.decorator_list)
                scan_import_time(d for d in node.args.defaults)
                scan_import_time(d for d in node.args.kw_defaults if d)
                continue
            if isinstance(node, ast.Lambda):
                continue  # body is deferred
            if isinstance(node, ast.Call):
                q = _is_jnp_call(node, aliases)
                if q:
                    out.append(LintViolation(
                        path, node.lineno, "DLT001",
                        f"'{q}(...)' runs at module import time — device "
                        "work at import initializes the backend early and "
                        "serializes startup; move it into a function"))
                    continue  # one finding per outermost offending call
            for child in ast.iter_child_nodes(node):
                scan_import_time([child])

    scan_import_time(tree.body)
    return out


# ------------------------------------------------------------------ DLT002
_TRANSFORMS = (
    "jax.jit", "jit", "jax.pmap", "pmap", "jax.vmap", "vmap",
    "jax.grad", "grad", "jax.value_and_grad", "value_and_grad",
    "jax.lax.scan", "lax.scan", "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop", "jax.lax.cond", "lax.cond",
    "jax.lax.map", "lax.map", "jax.checkpoint", "jax.remat",
    "jax.eval_shape", "shard_map", "jax.experimental.shard_map.shard_map",
)

_IMPURE = {
    "time.time": "wall clock", "time.perf_counter": "wall clock",
    "time.monotonic": "wall clock", "time.process_time": "wall clock",
    "datetime.datetime.now": "wall clock", "datetime.datetime.utcnow":
    "wall clock",
    "random.random": "host RNG", "random.randint": "host RNG",
    "random.uniform": "host RNG", "random.gauss": "host RNG",
    "random.choice": "host RNG", "random.shuffle": "host RNG",
    "random.sample": "host RNG", "random.randrange": "host RNG",
    "numpy.random": "host RNG",  # prefix match for np.random.*
}


def _impure_reason(q: str) -> Optional[str]:
    if q in _IMPURE:
        return _IMPURE[q]
    if q.startswith("numpy.random."):
        return "host RNG"
    return None


def _rule_impure_in_jit(tree, src, path) -> List[LintViolation]:
    aliases = _import_aliases(tree)

    # 1) names of functions handed to a tracing transform anywhere
    traced_names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            q = _resolve(_dotted(node.func), aliases)
            short = _dotted(node.func) or ""
            if q in _TRANSFORMS or short in _TRANSFORMS:
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(arg, ast.Name):
                        traced_names.add(arg.id)
                    elif isinstance(arg, ast.Attribute):
                        traced_names.add(arg.attr)

    def is_jit_decorated(fn) -> bool:
        for dec in fn.decorator_list:
            d = dec.func if isinstance(dec, ast.Call) else dec
            q = _resolve(_dotted(d), aliases)
            if q in _TRANSFORMS or (_dotted(d) or "") in _TRANSFORMS:
                return True
            # functools.partial(jax.jit, ...)
            if isinstance(dec, ast.Call) and q.endswith("partial"):
                for a in dec.args:
                    if _resolve(_dotted(a), aliases) in _TRANSFORMS:
                        return True
        return False

    out: List[LintViolation] = []
    seen_bodies: Set[int] = set()

    def scan_traced_body(fn: ast.AST, origin: str):
        if id(fn) in seen_bodies:
            return
        seen_bodies.add(id(fn))
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                q = _resolve(_dotted(node.func), aliases)
                reason = _impure_reason(q)
                if reason:
                    out.append(LintViolation(
                        path, node.lineno, "DLT002",
                        f"'{q}(...)' ({reason}) inside jit-traced "
                        f"'{origin}' — runs once at trace time and freezes "
                        "into the compiled program; thread it in as an "
                        "argument (or use jax.random)"))

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in traced_names or is_jit_decorated(node):
                scan_traced_body(node, node.name)
    for node in ast.walk(tree):  # lambdas passed inline to a transform
        if isinstance(node, ast.Call):
            q = _resolve(_dotted(node.func), aliases)
            short = _dotted(node.func) or ""
            if q in _TRANSFORMS or short in _TRANSFORMS:
                for arg in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(arg, ast.Lambda):
                        scan_traced_body(arg, "<lambda>")
    return out


# ------------------------------------------------------------------ DLT003
_CLOCKS = ("time.perf_counter", "time.time", "time.monotonic")
_SYNCS = ("block_until_ready", "device_get", "item", "asarray", "array",
          "float", "tolist")


def _is_bench_file(path: str) -> bool:
    base = os.path.basename(path)
    return ("bench" in base or "perf" in base or "profile" in base
            or f"{os.sep}tools{os.sep}" in path or path.startswith("tools/"))


def _rule_bench_sync(tree, src, path) -> List[LintViolation]:
    if not _is_bench_file(path):
        return []
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []

    def direct_body(fn):
        """All nodes of fn except nested function bodies."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            yield n
            stack.extend(ast.iter_child_nodes(n))

    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        clock_lines = []
        has_sync = False
        for node in direct_body(fn):
            if isinstance(node, ast.Call):
                q = _resolve(_dotted(node.func), aliases)
                if q in _CLOCKS:
                    clock_lines.append(node.lineno)
                name = (node.func.attr if isinstance(node.func, ast.Attribute)
                        else node.func.id if isinstance(node.func, ast.Name)
                        else "")
                if name in _SYNCS:
                    has_sync = True
        if len(clock_lines) >= 2 and not has_sync:
            out.append(LintViolation(
                path, min(clock_lines), "DLT003",
                f"function '{fn.name}' reads the clock {len(clock_lines)}x "
                "without a device sync (block_until_ready/np.asarray/"
                "float(...)) — async dispatch means the stopwatch measures "
                "nothing"))
    return out


# ------------------------------------------------------------------ DLT004
def _rule_lock_order(tree, src, path) -> List[LintViolation]:
    out: List[LintViolation] = []

    def lock_name(expr) -> Optional[str]:
        # `self.<attr>` where the attr smells like a lock
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and expr.value.id == "self" \
                and "lock" in expr.attr.lower():
            return expr.attr
        return None

    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        # (outer, inner) -> [(method, line)]
        edges: Dict[Tuple[str, str], List[Tuple[str, int]]] = {}

        # Statements are walked IN ORDER with a mutable held-set so an
        # explicit `self.x_lock.acquire()` persists across the following
        # sibling statements (incl. a try: body whose finally: releases)
        # and `release()` drops it again — the `with`-only walk missed
        # every acquire/release-sequenced ordering.
        def scan_explicit(node, held: List[str], method: str):
            for sub in ast.walk(node):
                if not (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in ("acquire", "release")):
                    continue
                ln = lock_name(sub.func.value)
                if ln is None:
                    continue
                if sub.func.attr == "acquire":
                    for h in held:
                        edges.setdefault((h, ln), []).append(
                            (method, sub.lineno))
                    held.append(ln)
                elif ln in held:
                    held.remove(ln)

        def collect(stmts, held: List[str], method: str):
            for node in stmts:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue  # nested defs run later, with unknown holds
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    acquired = []
                    for item in node.items:
                        ln = lock_name(item.context_expr)
                        if ln is not None:
                            for h in held + acquired:
                                edges.setdefault((h, ln), []).append(
                                    (method, node.lineno))
                            acquired.append(ln)
                    held.extend(acquired)
                    collect(node.body, held, method)
                    if acquired:
                        del held[-len(acquired):]
                    continue
                if isinstance(node, ast.Try):
                    collect(node.body, held, method)
                    for h in node.handlers:
                        collect(h.body, held, method)
                    collect(node.orelse, held, method)
                    collect(node.finalbody, held, method)
                    continue
                if isinstance(node, ast.If):
                    scan_explicit(node.test, held, method)
                    collect(node.body, held, method)
                    collect(node.orelse, held, method)
                    continue
                if isinstance(node, (ast.For, ast.AsyncFor)):
                    scan_explicit(node.iter, held, method)
                    collect(node.body, held, method)
                    collect(node.orelse, held, method)
                    continue
                if isinstance(node, ast.While):
                    scan_explicit(node.test, held, method)
                    collect(node.body, held, method)
                    collect(node.orelse, held, method)
                    continue
                scan_explicit(node, held, method)

        for meth in cls.body:
            if isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
                collect(meth.body, [], meth.name)

        reported = set()
        for (a, b), sites in edges.items():
            if (b, a) in edges and (b, a) not in reported and a != b:
                reported.add((a, b))
                m1, l1 = sites[0]
                m2, l2 = edges[(b, a)][0]
                out.append(LintViolation(
                    path, l1, "DLT004",
                    f"class '{cls.name}' acquires locks in inconsistent "
                    f"order: '{m1}' takes {a} -> {b} (line {l1}) but "
                    f"'{m2}' takes {b} -> {a} (line {l2}) — deadlock risk "
                    "under concurrent callers; pick one global order"))
    return out


# ------------------------------------------------------------------ DLT005
def _rule_serving_bn_fold(tree, src, path) -> List[LintViolation]:
    aliases = _import_aliases(tree)
    pi_lines: List[int] = []
    has_bn = False
    has_fold = False
    for node in ast.walk(tree):
        if isinstance(node, (ast.Name, ast.Attribute)):
            d = _dotted(node) or getattr(node, "attr", "") or \
                getattr(node, "id", "")
            if "fold_bn" in d:
                has_fold = True
        if not isinstance(node, ast.Call):
            continue
        q = _resolve(_dotted(node.func), aliases)
        tail = q.rsplit(".", 1)[-1] if q else ""
        if tail == "ParallelInference":
            pi_lines.append(node.lineno)
            # ParallelInference(..., fold_bn=True) folds internally; an
            # explicit literal False is NOT a fold — that is exactly the
            # unfolded serving site the rule exists to catch
            for kw in node.keywords:
                if kw.arg == "fold_bn" and not (
                        isinstance(kw.value, ast.Constant)
                        and kw.value.value is False):
                    has_fold = True
        elif tail == "BatchNormalization":
            has_bn = True
        elif "fold_bn" in tail:
            has_fold = True
    if not (pi_lines and has_bn) or has_fold:
        return []
    return [LintViolation(
        path, line, "DLT005",
        "model built with BatchNormalization is served through "
        "ParallelInference without BN folding — every dispatch re-applies "
        "the BN normalize (and a train=True call on this path would run "
        "BN-train semantics on request batches); fold it exactly into the "
        "conv weights with perf.fusion.fold_bn / "
        "ParallelInference(fold_bn=True)") for line in pi_lines]


# ------------------------------------------------------------------ DLT006
def _is_storage_file(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(seg in p for seg in ("checkpoint/", "storage/")) \
        or os.path.basename(p) in ("storage.py", "checkpoint.py")


_BROAD_EXC = ("Exception", "BaseException")


def _rule_swallowed_storage_error(tree, src, path) -> List[LintViolation]:
    if not _is_storage_file(path):
        return []
    out: List[LintViolation] = []

    def handler_is_broad(h: ast.ExceptHandler) -> bool:
        if h.type is None:  # bare except
            return True
        types = h.type.elts if isinstance(h.type, ast.Tuple) else [h.type]
        for t in types:
            d = _dotted(t) or ""
            if d.rsplit(".", 1)[-1] in _BROAD_EXC:
                return True
        return False

    # the CALLED METHOD itself must be a reporting primitive — matching a
    # substring anywhere in the dotted path would let `self.catalog.
    # refresh()` (…log…) silence the rule
    _REPORTERS = {"debug", "info", "warning", "warn", "error", "exception",
                  "critical", "log", "print", "_fail"}

    def handler_surfaces(h: ast.ExceptHandler) -> bool:
        """Re-raise, log, warn, or stash the bound exception somewhere."""
        bound = h.name
        for node in ast.walk(h):
            if isinstance(node, ast.Raise):
                return True
            if isinstance(node, ast.Call):
                d = (_dotted(node.func) or "").lower()
                if d.rsplit(".", 1)[-1] in _REPORTERS:
                    return True
            # ``self._write_err = e`` — deferred re-raise pattern
            if bound and isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == bound:
                return True
        return False

    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        for h in node.handlers:
            if handler_is_broad(h) and not handler_surfaces(h):
                what = ("bare except" if h.type is None else
                        f"except {_dotted(h.type) if not isinstance(h.type, ast.Tuple) else 'Exception'}")
                out.append(LintViolation(
                    path, h.lineno, "DLT006",
                    f"{what} in checkpoint/storage code swallows the error "
                    "without re-raising or logging — a durability fault "
                    "eaten here surfaces as a dead run at restore time; "
                    "narrow the handler, log it, or waive inline"))
    return out


# ------------------------------------------------------------------ DLT007
_METRIC_METHODS = ("counter", "gauge", "histogram")
_COUNTER_DICT_CTORS = ("dict", "Counter", "defaultdict", "OrderedDict")


def _is_registry_receiver(recv: Optional[str]) -> bool:
    if not recv:
        return False
    last = recv.split(".")[-1].lower()
    return "registry" in last or last in ("reg", "metrics")


def _rule_metric_registration(tree, src, path) -> List[LintViolation]:
    out: List[LintViolation] = []
    for node in ast.walk(tree):
        # (a) registry instrument calls must carry unit + help
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _METRIC_METHODS and \
                _is_registry_receiver(_dotted(node.func.value)):
            # signature: (name, unit, help, ...) — positionals count
            present = {("name", "unit", "help")[i]
                       for i in range(min(3, len(node.args)))}
            empty = set()
            for i, a in enumerate(node.args[:3]):
                if isinstance(a, ast.Constant) and a.value == "":
                    empty.add(("name", "unit", "help")[i])
            for kw in node.keywords:
                if kw.arg in ("unit", "help"):
                    present.add(kw.arg)
                    if isinstance(kw.value, ast.Constant) and \
                            kw.value.value == "":
                        empty.add(kw.arg)
            missing = sorted(({"unit", "help"} - present) | empty)
            if missing:
                out.append(LintViolation(
                    path, node.lineno, "DLT007",
                    f"metric registered via .{node.func.attr}(...) without "
                    f"{' and '.join(missing)} — every metric needs a unit "
                    "and help text (an unlabeled number on a dashboard is "
                    "a guess)"))
            continue
        # (b) bare counter dicts
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        bare = isinstance(value, ast.Dict) and not value.keys
        if isinstance(value, ast.Call):
            tail = (_dotted(value.func) or "").rsplit(".", 1)[-1]
            bare = tail in _COUNTER_DICT_CTORS and not value.args \
                and not value.keywords or tail == "defaultdict"
        if not bare:
            continue
        for t in targets:
            name = (t.attr if isinstance(t, ast.Attribute)
                    else t.id if isinstance(t, ast.Name) else "")
            low = name.lower()
            if low == "counters" or low.endswith("_counters"):
                out.append(LintViolation(
                    path, node.lineno, "DLT007",
                    f"bare counter dict '{name}' — register metrics in an "
                    "obs.MetricsRegistry with units and help text instead "
                    "(or absorb the surface via obs.absorb_* and waive "
                    "inline)"))
    return out


# ------------------------------------------------------------------ DLT008
def _is_bounded_buffer_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(seg in p for seg in ("serving/", "parallel/", "datasets/",
                                    "storage/", "checkpoint/",
                                    "retrieval/"))


def _rule_unbounded_queue(tree, src, path) -> List[LintViolation]:
    if not _is_bounded_buffer_path(path):
        return []
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _resolve(_dotted(node.func), aliases) != "queue.Queue":
            continue
        # maxsize is the single positional; a literal 0 (stdlib's
        # "infinite") is exactly as unbounded as omitting it
        bound = None
        if node.args:
            bound = node.args[0]
        for kw in node.keywords:
            if kw.arg == "maxsize":
                bound = kw.value
        unbounded = bound is None or (isinstance(bound, ast.Constant)
                                      and bound.value == 0)
        if unbounded:
            out.append(LintViolation(
                path, node.lineno, "DLT008",
                "unbounded queue.Queue() in a serving/parallel/data/"
                "storage path — a stalled consumer grows host memory "
                "without limit and producers wait forever; pass maxsize= "
                "with explicit full-queue semantics (shed/timeout), or "
                "waive inline"))
    return out


# ------------------------------------------------------------------ DLT009
def _rule_host_work_in_compression(tree, src, path) -> List[LintViolation]:
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []

    def in_scope_functions():
        """(fn, origin) for compression-path functions: name contains
        'compress', or any method of a class whose name contains
        'Compression'."""
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and "Compression" in node.name:
                for meth in ast.walk(node):
                    if isinstance(meth, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        yield meth, f"{node.name}.{meth.name}"
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and "compress" in node.name.lower():
                yield node, node.name

    def uses_device_math(fn) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, (ast.Attribute, ast.Name)):
                q = _resolve(_dotted(node), aliases)
                if q.startswith(("jax.numpy", "jax.lax")):
                    return True
        return False

    seen: Set[int] = set()
    for fn, origin in in_scope_functions():
        if id(fn) in seen or not uses_device_math(fn):
            continue
        seen.add(id(fn))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            q = _resolve(_dotted(node.func), aliases)
            hazard = None
            if q == "numpy" or q.startswith("numpy."):
                hazard = f"'{q}(...)' (host numpy)"
            elif q == "jax.device_get":
                hazard = "'jax.device_get(...)'"
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item":
                hazard = "'.item()'"
            if hazard:
                out.append(LintViolation(
                    path, node.lineno, "DLT009",
                    f"{hazard} inside gradient-compression path "
                    f"'{origin}' — compress/encode/decode runs inside the "
                    "traced train step, where host-side work forces a "
                    "host-device sync every step; keep the pass in jnp on "
                    "the gradient pytree (or waive inline for a "
                    "deliberately host-side helper)"))
    return out


# ------------------------------------------------------------------ DLT010
_FLOAT_CAST_TARGETS = {
    "jax.numpy.float32": "float32", "jax.numpy.float64": "float64",
    "numpy.float32": "float32", "numpy.float64": "float64",
}


def _rule_float_cast_in_quant(tree, src, path) -> List[LintViolation]:
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []

    def uses_device_math(fn) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, (ast.Attribute, ast.Name)):
                q = _resolve(_dotted(node), aliases)
                if q.startswith(("jax.numpy", "jax.lax")):
                    return True
        return False

    def in_scope_functions():
        """(fn, origin) for quant-path functions: any method of a class
        whose name contains 'Quantized' (quantized layer code is device
        code by construction), or a function whose name contains 'quant'
        that ALSO uses jnp/lax device math — pure-host helpers (bench
        data prep, CLI loaders) are exempt, the DLT009 precedent."""
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and "Quantized" in node.name:
                for meth in ast.walk(node):
                    if isinstance(meth, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        yield meth, f"{node.name}.{meth.name}"
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and "quant" in node.name.lower() \
                    and uses_device_math(node):
                yield node, node.name

    def cast_target(node: ast.Call) -> Optional[str]:
        """'float32'/'float64' when the call is a flagged float cast."""
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "astype":
            args = list(node.args) + [kw.value for kw in node.keywords
                                      if kw.arg in (None, "dtype")]
            for a in args:
                if isinstance(a, ast.Constant) and \
                        a.value in ("float32", "float64"):
                    return a.value
                t = _FLOAT_CAST_TARGETS.get(_resolve(_dotted(a), aliases))
                if t:
                    return t
            return None
        # a float64 CONSTRUCTOR call re-materializes the tensor in f64
        # (scalar float32 wraps like jnp.float32(1/s) stay exempt — that
        # is how the requantize multiplier is built)
        q = _resolve(_dotted(node.func), aliases)
        if q in ("jax.numpy.float64", "numpy.float64"):
            return "float64"
        return None

    seen: Set[int] = set()
    for fn, origin in in_scope_functions():
        if id(fn) in seen:
            continue
        seen.add(id(fn))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            t = cast_target(node)
            if t:
                out.append(LintViolation(
                    path, node.lineno, "DLT010",
                    f"{t} cast inside quantized-inference path "
                    f"'{origin}' — re-floating a tensor mid-path defeats "
                    "the int8 compute (dequant-per-element in the hot "
                    "loop) while every numeric test still passes; keep "
                    "tensors int8 until the single per-layer requantize "
                    "(or waive inline for a deliberate fp32 boundary)"))
    return out


# ------------------------------------------------------------------ DLT011
_GLOBAL_RNG_CALLS = {
    "random.shuffle", "random.sample", "random.choice", "random.random",
    "random.randint", "random.uniform",
    "numpy.random.shuffle", "numpy.random.permutation",
    "numpy.random.choice", "numpy.random.randint", "numpy.random.random",
    "numpy.random.rand", "numpy.random.randn", "numpy.random.seed",
}


def _is_data_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(seg in p for seg in ("datasets/", "parallel/"))


def _rule_unseeded_global_rng(tree, src, path) -> List[LintViolation]:
    if not _is_data_path(path):
        return []
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        q = _resolve(_dotted(node.func), aliases)
        if q in _GLOBAL_RNG_CALLS:
            out.append(LintViolation(
                path, node.lineno, "DLT011",
                f"'{q}(...)' draws from module-level RNG state in a "
                "datasets/parallel path — a deterministic-epoch hazard: "
                "fleet-true resume and any-world bitwise epochs need "
                "every shuffle to be a pure function of (seed, epoch), "
                "and global state also races across prefetch threads; "
                "use a seeded np.random.default_rng(seed) / "
                "random.Random(seed) instance (or waive inline for a "
                "deliberately non-deterministic path)"))
    return out


# ------------------------------------------------------------------ DLT012
def _is_hot_path_file(path: str) -> bool:
    p = path.replace(os.sep, "/")
    if any(seg in p for seg in ("serving/", "parallel/")):
        return True
    return p.endswith(("nn/multilayer.py", "nn/graph.py"))


_INTROSPECTION_CALLS = ("cost_analysis", "memory_analysis")


def _rule_compile_introspection_in_hot_path(tree, src, path
                                            ) -> List[LintViolation]:
    if not _is_hot_path_file(path):
        return []
    out: List[LintViolation] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        attr = node.func.attr
        hazard = None
        if attr in _INTROSPECTION_CALLS:
            hazard = f"'.{attr}()'"
        elif attr == "compile":
            recv = node.func.value
            if isinstance(recv, ast.Call) \
                    and isinstance(recv.func, ast.Attribute) \
                    and recv.func.attr == "lower":
                hazard = "'.lower(...).compile()'"
        if hazard:
            out.append(LintViolation(
                path, node.lineno, "DLT012",
                f"{hazard} in a serving/training hot path — XLA "
                "compilation/introspection costs seconds on a path "
                "budgeted in microseconds; these are autotune-time tools "
                "(perf/autotune.py, perf/planner.py) — thread their "
                "results in via a TuningRecord/plan, or waive inline for "
                "a deliberate offline call"))
    return out


# ------------------------------------------------------------------ DLT013
_RETRIEVAL_HOT_TOKENS = ("score", "topk", "probe")


def _is_retrieval_path(path: str) -> bool:
    return "retrieval/" in path.replace(os.sep, "/")


def _is_jit_decorated(fn, aliases) -> bool:
    """``@jax.jit`` or ``@functools.partial(jax.jit, ...)`` (the repo's
    static-argnames idiom)."""
    for dec in fn.decorator_list:
        if _resolve(_dotted(dec), aliases) == "jax.jit":
            return True
        if isinstance(dec, ast.Call):
            if _resolve(_dotted(dec.func), aliases) == "jax.jit":
                return True
            if _resolve(_dotted(dec.func), aliases) == "functools.partial" \
                    and dec.args \
                    and _resolve(_dotted(dec.args[0]), aliases) == "jax.jit":
                return True
    return False


def _rule_host_work_in_retrieval(tree, src, path) -> List[LintViolation]:
    if not _is_retrieval_path(path):
        return []
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []

    def uses_device_math(fn) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, (ast.Attribute, ast.Name)):
                q = _resolve(_dotted(node), aliases)
                if q.startswith(("jax.numpy", "jax.lax")):
                    return True
        return False

    def in_scope_functions():
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            name = node.name.lower()
            if (_is_jit_decorated(node, aliases)
                    or any(t in name for t in _RETRIEVAL_HOT_TOKENS)):
                if uses_device_math(node):
                    yield node

    # dedup on the CALL node, not the function: a hot-path function
    # nested inside another hot-path function is walked by both, and the
    # same np call must report once (ast.walk(tree) yields each
    # FunctionDef once, so a function-id set would be dead code)
    seen_calls: Set[int] = set()
    for fn in in_scope_functions():
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen_calls:
                continue
            q = _resolve(_dotted(node.func), aliases)
            hazard = None
            if q == "numpy" or q.startswith("numpy."):
                hazard = f"'{q}(...)' (host numpy)"
            elif q == "jax.device_get":
                hazard = "'jax.device_get(...)'"
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item":
                hazard = "'.item()'"
            if hazard:
                seen_calls.add(id(node))
                out.append(LintViolation(
                    path, node.lineno, "DLT013",
                    f"{hazard} inside retrieval hot-path function "
                    f"'{fn.name}' — the scoring path is one jitted "
                    "matmul+top_k per batch with ZERO host syncs; host "
                    "distance math or device readbacks here reintroduce "
                    "the per-query host round-trip the device index "
                    "exists to kill; keep the kernel in jnp (or waive "
                    "inline for a deliberately host-side helper)"))
    return out


# ------------------------------------------------------------------ DLT014
_PACK_TOKENS = ("pack", "unpack", "nibble", "adc", "pq")


def _is_pack_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return "retrieval/" in p or "quant/" in p


def _rule_host_nibble_unpack(tree, src, path) -> List[LintViolation]:
    if not _is_pack_path(path):
        return []
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []

    def uses_device_math(fn) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, (ast.Attribute, ast.Name)):
                q = _resolve(_dotted(node), aliases)
                if q.startswith(("jax.numpy", "jax.lax")):
                    return True
        return False

    def in_scope_functions():
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            name = node.name.lower()
            if any(t in name for t in _PACK_TOKENS) \
                    and uses_device_math(node):
                yield node

    # dedup on the CALL node (the DLT013 nested-function note)
    seen_calls: Set[int] = set()
    for fn in in_scope_functions():
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen_calls:
                continue
            q = _resolve(_dotted(node.func), aliases)
            hazard = None
            if q == "numpy" or q.startswith("numpy."):
                hazard = f"'{q}(...)' (host numpy)"
            elif q == "jax.device_get":
                hazard = "'jax.device_get(...)'"
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item":
                hazard = "'.item()'"
            if hazard:
                seen_calls.add(id(node))
                out.append(LintViolation(
                    path, node.lineno, "DLT014",
                    f"{hazard} inside packed-code function '{fn.name}' — "
                    "packed int4/PQ codes stay resident and unpack with "
                    "shift/mask INSIDE the jitted scorer (quant/pack.py "
                    "unpack_nibbles); host-side unpacking materializes "
                    "the table the packing shrank and syncs per "
                    "dispatch; keep the kernel in jnp (or waive inline "
                    "for a deliberately host-side build/test helper)"))
    return out


# ------------------------------------------------------------------ DLT015
def _is_pallas_path(path: str) -> bool:
    return "perf/pallas/" in path.replace(os.sep, "/")


def _rule_host_work_in_pallas_kernel(tree, src, path) -> List[LintViolation]:
    if not _is_pallas_path(path):
        return []
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []

    def _arg_names(fn) -> List[str]:
        a = fn.args
        names = [x.arg for x in (a.posonlyargs + a.args + a.kwonlyargs)]
        if a.vararg:
            names.append(a.vararg.arg)
        return names

    def kernel_bodies():
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name.endswith("_kernel") or any(
                    n.endswith("_ref") or n in ("refs", "ref")
                    for n in _arg_names(node)):
                yield node

    def _ref_names(fn) -> Set[str]:
        # block refs: *_ref parameters plus any *_ref name the body binds
        # (the ``*refs`` tuple-unpack idiom)
        names = {n for n in _arg_names(fn) if n.endswith("_ref")}
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and node.id.endswith("_ref"):
                names.add(node.id)
        return names

    # dedup on the offending node (the DLT013 nested-function note)
    seen: Set[int] = set()
    for fn in kernel_bodies():
        refs = _ref_names(fn)
        for node in ast.walk(fn):
            if id(node) in seen:
                continue
            hazard = fix = None
            if isinstance(node, ast.Call):
                q = _resolve(_dotted(node.func), aliases)
                if q == "numpy" or q.startswith("numpy."):
                    hazard = f"'{q}(...)' (host numpy)"
                elif q == "jax.device_get":
                    hazard = "'jax.device_get(...)'"
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item":
                    hazard = "'.item()'"
                if hazard:
                    fix = "keep the body in jnp/lax on the block refs"
            elif isinstance(node, ast.While):
                hazard = "'while' loop"
                fix = ("Python loops in a kernel body unroll at trace "
                       "time or fail to trace on traced bounds — use "
                       "lax.fori_loop/pl.when, or hoist the bound to a "
                       "static kernel parameter")
            elif isinstance(node, ast.For):
                it = node.iter
                is_static_range = (isinstance(it, ast.Call) and _resolve(
                    _dotted(it.func), aliases) == "range")
                if not is_static_range:
                    hazard = "'for' over a non-range iterable"
                    fix = ("only static ``for m in range(...)`` unrolls "
                           "belong in a kernel body; anything else is "
                           "host iteration over traced values")
            elif isinstance(node, ast.If):
                used = {n.id for n in ast.walk(node.test)
                        if isinstance(n, ast.Name)}
                if used & refs:
                    hazard = "'if' on a kernel block ref"
                    fix = ("Python branching on traced block values "
                           "cannot trace — use pl.when/jnp.where, or "
                           "lift the decision to a static kernel "
                           "parameter")
            if hazard:
                seen.add(id(node))
                out.append(LintViolation(
                    path, node.lineno, "DLT015",
                    f"{hazard} inside Pallas kernel body '{fn.name}' — "
                    "kernel bodies run per grid program on VMEM blocks; "
                    "interpret mode (CPU CI) executes this happily and "
                    "the bug detonates only when the TPU round "
                    f"Mosaic-compiles the same body; {fix} (or waive "
                    "inline for a deliberate exception)"))
    return out


# ------------------------------------------------------------------ DLT016
def _is_fleet_serving_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(seg in p for seg in ("fleet/", "serving/"))


# blocking client entry points → the 1-based positional slot that can
# carry the timeout (None: only the ``timeout=`` keyword can)
_BLOCKING_IO_CALLS = {
    "urllib.request.urlopen": 3,
    "http.client.HTTPConnection": 3,
    "http.client.HTTPSConnection": 3,
    "socket.create_connection": 2,
    "requests.get": None,
    "requests.post": None,
    "requests.put": None,
    "requests.delete": None,
    "requests.request": None,
}


def _rule_blocking_io_without_timeout(tree, src, path
                                      ) -> List[LintViolation]:
    """Outbound socket/HTTP-client calls in fleet/ + serving/ paths must
    carry an explicit timeout: the router fans one client request out to
    replicas, so a single hung upstream without a timeout wedges a
    handler thread forever — under a burst, ALL of them — and the
    default for every one of these stdlib calls is to block forever."""
    if not _is_fleet_serving_path(path):
        return []
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        q = _resolve(_dotted(node.func), aliases)
        if q not in _BLOCKING_IO_CALLS:
            continue
        if any(kw.arg == "timeout" for kw in node.keywords):
            continue
        slot = _BLOCKING_IO_CALLS[q]
        if slot is not None and len(node.args) >= slot:
            continue
        out.append(LintViolation(
            path, node.lineno, "DLT016",
            f"'{q}(...)' without an explicit timeout in a fleet/serving "
            "path — these calls block forever by default, so one hung "
            "replica wedges a router/server handler thread (and under a "
            "burst, all of them); pass timeout= (or waive inline for a "
            "deliberately unbounded wait)"))
    return out


# ------------------------------------------------------------------ DLT020
_DECODE_TOKENS = ("decode", "sample", "generate", "stream", "token")


def _is_serving_nn_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(seg in p for seg in ("serving/", "nn/"))


def _rule_per_token_host_transfer(tree, src, path) -> List[LintViolation]:
    """DLT020: host transfers inside loop bodies of decode/sampling
    functions in serving/ + nn/ paths. The decode tier's contract is one
    jitted dispatch advancing EVERY active session and one bulk readback
    per dispatch; ``device_get``/``.item()``/``np.*``/``.tolist()``
    inside the per-token loop turns that into sessions × tokens host
    syncs — the exact collapse continuous batching exists to kill."""
    if not _is_serving_nn_path(path):
        return []
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []

    def uses_device_math(fn) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, (ast.Attribute, ast.Name)):
                q = _resolve(_dotted(node), aliases)
                if q.startswith(("jax.numpy", "jax.lax", "jax.nn",
                                 "jax.random")):
                    return True
        return False

    def in_scope_functions():
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            name = node.name.lower()
            if any(t in name for t in _DECODE_TOKENS) \
                    and uses_device_math(node):
                yield node

    def hazard_of(node: ast.Call) -> Optional[str]:
        q = _resolve(_dotted(node.func), aliases)
        if q == "numpy" or q.startswith("numpy."):
            return f"'{q}(...)' (host numpy)"
        if q == "jax.device_get":
            return "'jax.device_get(...)'"
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("item", "tolist"):
            return f"'.{node.func.attr}()'"
        return None

    # dedup on the CALL node (the DLT013 nested-function note); nested
    # loops also walk inner statements twice — same guard covers both
    seen_calls: Set[int] = set()
    for fn in in_scope_functions():
        for loop in ast.walk(fn):
            if not isinstance(loop, (ast.For, ast.AsyncFor, ast.While)):
                continue
            for stmt in loop.body + loop.orelse:
                for node in ast.walk(stmt):
                    if not isinstance(node, ast.Call) \
                            or id(node) in seen_calls:
                        continue
                    hazard = hazard_of(node)
                    if hazard is None:
                        continue
                    seen_calls.add(id(node))
                    out.append(LintViolation(
                        path, node.lineno, "DLT020",
                        f"{hazard} inside a loop body of decode/sampling "
                        f"function '{fn.name}' — the decode tier makes "
                        "ONE jitted dispatch advance every active "
                        "session with ONE bulk readback per dispatch; a "
                        "host transfer inside the per-token loop "
                        "reintroduces sessions x tokens host syncs (the "
                        "per-call rnn_time_step collapse); hoist the "
                        "readback out of the loop (or waive inline for "
                        "a deliberately host-side helper)"))
    return out


# ------------------------------------------------- DLT017 (interprocedural)
# consequence phrasing per hazard kind, for the message
_DLT017_REASON = {
    "clock": ("wall clock", "runs once at trace time and freezes into the "
              "compiled program"),
    "rng": ("host RNG", "runs once at trace time and freezes into the "
            "compiled program"),
    "np": ("host numpy", "mixed host/device code in the traced closure — "
           "host math here materializes trace-time constants or forces a "
           "per-step host sync"),
    "item": ("device readback", "forces a host-device sync (and errors "
             "outright on a traced value)"),
    "device_get": ("device readback", "forces a host-device sync (and "
                   "errors outright on a traced value)"),
    "sync": ("host sync", "blocks on device completion inside the traced "
             "closure"),
}


def _repo_rule_host_work_from_jit(graph: "_cg.CallGraph"
                                  ) -> List[LintViolation]:
    """DLT017: re-apply the host-work checks over everything reachable
    from a traced entry, ≥1 call-hop away (the entry's own body is
    DLT002's). Each hazard reports once, with the shortest entry chain."""
    best: Dict[Tuple[str, int, str], Tuple[Tuple[str, ...], str]] = {}
    for entry in graph.entries():
        for qname, chain in graph.reachable_from(entry).items():
            if len(chain) < 2 or qname in graph.traced_entries:
                continue
            fn = graph.functions.get(qname)
            if fn is None:
                continue
            for hz in fn.hazards:
                if hz.kind == "np" and not fn.uses_device:
                    continue  # pure-host helper: trace-time constant by design
                key = (fn.path, hz.lineno, hz.detail)
                if key not in best or len(chain) < len(best[key][0]):
                    best[key] = (chain, hz.kind)
    out: List[LintViolation] = []
    for (path, lineno, detail), (chain, kind) in sorted(best.items()):
        label, consequence = _DLT017_REASON[kind]
        hops = len(chain) - 1
        out.append(LintViolation(
            path, lineno, "DLT017",
            f"'{detail}' ({label}) is reachable from traced entry "
            f"'{chain[0]}' via {' -> '.join(chain)} ({hops} call hop"
            f"{'s' if hops != 1 else ''} from the jit boundary) — "
            f"{consequence}; thread the value in as an argument or hoist "
            "the host work out of the traced path (or waive inline for a "
            "deliberately trace-time computation)"))
    return out


# ------------------------------------------------- DLT018 (interprocedural)
def _is_lock_io_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(seg in p for seg in ("fleet/", "serving/", "checkpoint/",
                                    "parallel/"))


def _repo_rule_cross_module_locks(graph: "_cg.CallGraph"
                                  ) -> List[LintViolation]:
    """DLT018: (a) opposite-order lock pairs anywhere in the repo, with
    held-sets propagated through resolved call edges (same-class pairs
    that DLT004 already sees from direct nesting are left to DLT004);
    (b) blocking I/O — direct or via a callee — while a lock is held, in
    serving/fleet/checkpoint/parallel paths."""
    out: List[LintViolation] = []

    # witness: (fn qname, file, line, via-callee-or-None)
    wit: Dict[Tuple[str, str], List[Tuple[str, str, int, Optional[str]]]] = {}
    for qname, acqs in graph.lock_acqs.items():
        fn = graph.functions[qname]
        for a in acqs:
            for h in a.held:
                if h != a.lock:
                    wit.setdefault((h, a.lock), []).append(
                        (qname, fn.path, a.lineno, None))
    for qname, edges in graph.edges.items():
        fn = graph.functions[qname]
        for e in edges:
            if not e.held:
                continue
            for lk in sorted(graph.acq_closure(e.callee)):
                for h in e.held:
                    if lk != h:
                        wit.setdefault((h, lk), []).append(
                            (qname, fn.path, e.lineno, e.callee))

    adj: Dict[str, Set[str]] = {}
    for (a, b) in wit:
        adj.setdefault(a, set()).add(b)

    def bfs_path(src: str, dst: str) -> Optional[List[str]]:
        prev: Dict[str, str] = {src: src}
        frontier = [src]
        while frontier:
            nxt = []
            for n in frontier:
                for m in sorted(adj.get(n, ())):
                    if m in prev:
                        continue
                    prev[m] = n
                    if m == dst:
                        path = [m]
                        while path[-1] != src:
                            path.append(prev[path[-1]])
                        return list(reversed(path))
                    nxt.append(m)
            frontier = nxt
        return None

    def describe(w) -> str:
        qname, fpath, line, via = w
        base = f"'{qname}' ({os.path.basename(fpath)}:{line})"
        return f"{base} via call to '{via}'" if via else base

    reported: Set[frozenset] = set()
    for (a, b) in sorted(wit):
        if (b, a) in wit:  # 2-cycle
            pair = frozenset((a, b))
            if pair in reported:
                continue
            reported.add(pair)
            owner_a, owner_b = a.rsplit(".", 1)[0], b.rsplit(".", 1)[0]
            direct_ab = any(w[3] is None for w in wit[(a, b)])
            direct_ba = any(w[3] is None for w in wit[(b, a)])
            if owner_a == owner_b and direct_ab and direct_ba:
                continue  # same class, both orders directly nested: DLT004's
            w1, w2 = wit[(a, b)][0], wit[(b, a)][0]
            out.append(LintViolation(
                w1[1], w1[2], "DLT018",
                f"locks '{a}' and '{b}' are acquired in opposite orders: "
                f"{describe(w1)} takes '{a}' then '{b}', but {describe(w2)} "
                f"takes '{b}' then '{a}' — cross-module deadlock risk under "
                "concurrent callers; pick one global order (or waive inline "
                "if the two orders are provably never concurrent)"))
        else:
            cyc = bfs_path(b, a)
            if not cyc:
                continue
            nodes = frozenset(cyc) | {a}
            if nodes in reported:
                continue
            reported.add(nodes)
            w1 = wit[(a, b)][0]
            ring = " -> ".join([a, b] + cyc[1:])
            out.append(LintViolation(
                w1[1], w1[2], "DLT018",
                f"lock-acquisition cycle {ring}: {describe(w1)} takes "
                f"'{a}' then '{b}' and the remaining edges close the loop "
                "— cross-module deadlock risk under concurrent callers; "
                "break one edge of the cycle (or waive inline if the "
                "orders are provably never concurrent)"))

    seen_io: Set[Tuple[str, int, str]] = set()
    for qname, ios in graph.io_held.items():
        fn = graph.functions[qname]
        if not _is_lock_io_path(fn.path):
            continue
        for what, lineno, held in ios:
            if not held or (fn.path, lineno, what) in seen_io:
                continue
            seen_io.add((fn.path, lineno, what))
            out.append(LintViolation(
                fn.path, lineno, "DLT018",
                f"blocking '{what}' while holding lock '{held[-1]}' in "
                f"'{qname}' — every thread that needs the lock convoys "
                "behind this wait; move the blocking call outside the "
                "critical section (or waive inline for a deliberately "
                "serialized wait)"))
    for qname, edges in graph.edges.items():
        fn = graph.functions[qname]
        if not _is_lock_io_path(fn.path):
            continue
        for e in edges:
            if not e.held:
                continue
            for what in sorted(graph.io_closure(e.callee)):
                if (fn.path, e.lineno, what) in seen_io:
                    continue
                seen_io.add((fn.path, e.lineno, what))
                out.append(LintViolation(
                    fn.path, e.lineno, "DLT018",
                    f"call to '{e.callee}' performs blocking '{what}' "
                    f"while '{qname}' holds lock '{e.held[-1]}' — every "
                    "thread that needs the lock convoys behind this wait; "
                    "move the call outside the critical section (or waive "
                    "inline for a deliberately serialized wait)"))
    return out


# ------------------------------------------------- DLT019 (interprocedural)
def _repo_rule_thread_lifecycle(graph: "_cg.CallGraph"
                                ) -> List[LintViolation]:
    """DLT019: a ``threading.Thread`` started without ``daemon=True`` and
    without a recorded ``join()``/stop path leaks on shutdown."""
    cls_joins: Dict[str, Set[str]] = {}
    cls_daemon: Dict[str, Set[str]] = {}
    mod_joins: Dict[str, bool] = {}
    for fn in graph.functions.values():
        if fn.joins:
            mod_joins[fn.module] = True
        if fn.cls:
            cls_joins.setdefault(fn.cls, set()).update(fn.joins)
            cls_daemon.setdefault(fn.cls, set()).update(fn.daemon_sets)

    out: List[LintViolation] = []
    for qname in sorted(graph.functions):
        fn = graph.functions[qname]
        for th in fn.thread_starts:
            if th.daemon in ("true", "dynamic"):
                continue  # explicit daemon choice (dynamic: caller decides)
            ok = False
            if th.assigned and th.direct:
                if th.assigned in fn.joins or th.assigned in fn.daemon_sets \
                        or th.assigned in fn.returns:
                    ok = True  # joined here, daemonized, or handed to caller
                elif th.assigned.startswith("self.") and fn.cls and (
                        th.assigned in cls_joins.get(fn.cls, ())
                        or th.assigned in cls_daemon.get(fn.cls, ())):
                    ok = True  # drain/stop path elsewhere in the class
            else:
                # pooled into a collection / comprehension: accept any join
                # in the same function, class, or module as the stop path
                if fn.joins or (fn.cls and cls_joins.get(fn.cls)) or \
                        mod_joins.get(fn.module):
                    ok = True
            if not ok:
                out.append(LintViolation(
                    fn.path, th.lineno, "DLT019",
                    f"threading.Thread started in '{qname}' without "
                    "daemon=True or a recorded join()/stop path — a "
                    "non-daemon thread nobody joins blocks interpreter "
                    "exit and leaks across fleet drain/restart; set "
                    "daemon=True, or keep the handle and join it on the "
                    "stop path (or waive inline for a deliberately "
                    "detached worker)"))
    return out


# ------------------------------------------------------------------ DLT021
def _is_lake_io_path(path: str) -> bool:
    p = path.replace(os.sep, "/")
    return any(seg in p for seg in ("checkpoint/cloud", "checkpoint/emulator",
                                    "tools/lake"))


_UNBOUNDED_READ_METHODS = ("read", "recv", "readline")


def _rule_unbounded_lake_io(tree, src, path) -> List[LintViolation]:
    """DLT021: the lake wire paths move attacker-sized bytes between
    processes, so every read is byte-bounded and every socket call
    carries a deadline (DLT016's scope extended to checkpoint/cloud,
    checkpoint/emulator and tools/lake). A zero-argument
    ``.read()``/``.recv()``/``.readline()`` trusts the peer to stop
    sending; a timeout-less connection trusts it to keep answering —
    the retry layer can only bound faults the client surfaces."""
    if not _is_lake_io_path(path):
        return []
    aliases = _import_aliases(tree)
    out: List[LintViolation] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        # (a) unbounded reads: method calls with no positional byte bound
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _UNBOUNDED_READ_METHODS
                and not node.args):
            out.append(LintViolation(
                path, node.lineno, "DLT021",
                f"'.{node.func.attr}()' without a byte bound in a lake "
                "wire path — an unbounded response/socket read lets one "
                "hostile or wedged peer allocate arbitrary host memory; "
                "pass an explicit size (validate Content-Length first, "
                "utils/http.parse_content_length) or waive inline for a "
                "provably bounded stream"))
            continue
        # (b) DLT016's blocking-call table, same check, lake scope
        q = _resolve(_dotted(node.func), aliases)
        if q not in _BLOCKING_IO_CALLS:
            continue
        if any(kw.arg == "timeout" for kw in node.keywords):
            continue
        slot = _BLOCKING_IO_CALLS[q]
        if slot is not None and len(node.args) >= slot:
            continue
        out.append(LintViolation(
            path, node.lineno, "DLT021",
            f"'{q}(...)' without an explicit timeout in a lake wire "
            "path — the stdlib default blocks forever, so one stalled "
            "object-store server hangs the training run instead of "
            "tripping the retry schedule; pass timeout= (or waive "
            "inline for a deliberately unbounded wait)"))
    return out


# ----------------------------------------------------------------- harness
_RULES = (
    _rule_module_level_jnp,
    _rule_impure_in_jit,
    _rule_bench_sync,
    _rule_lock_order,
    _rule_serving_bn_fold,
    _rule_swallowed_storage_error,
    _rule_metric_registration,
    _rule_unbounded_queue,
    _rule_host_work_in_compression,
    _rule_float_cast_in_quant,
    _rule_unseeded_global_rng,
    _rule_compile_introspection_in_hot_path,
    _rule_host_work_in_retrieval,
    _rule_host_nibble_unpack,
    _rule_host_work_in_pallas_kernel,
    _rule_blocking_io_without_timeout,
    _rule_per_token_host_transfer,
    _rule_unbounded_lake_io,
)


_REPO_RULES = (
    _repo_rule_host_work_from_jit,
    _repo_rule_cross_module_locks,
    _repo_rule_thread_lifecycle,
)

# content-hash caches so the tier-1 gate re-lints only what changed:
# per-file raw rule results, and the repo-rule results for a working set
_FILE_RAW_CACHE: Dict[str, Tuple[str, List[LintViolation]]] = {}
_REPO_RAW_CACHE: Dict[frozenset, List[LintViolation]] = {}


def clear_caches():
    """Drop every lint/call-graph cache (cold-run timing, tests)."""
    _FILE_RAW_CACHE.clear()
    _REPO_RAW_CACHE.clear()
    _cg.clear_cache()


def _waived(v: LintViolation, lines: List[str], file_waivers: Set[str]) -> bool:
    if v.rule in file_waivers:
        return True
    if 1 <= v.line <= len(lines):
        text = lines[v.line - 1]
        if "lint: disable" in text and (v.rule in text
                                        or text.rstrip().endswith("disable")):
            return True
    return False


def _parse_file_waivers(lines: List[str]) -> Set[str]:
    return {
        part.strip().split()[0].rstrip(")")
        for line in lines if "lint: disable-file=" in line
        for part in line.split("lint: disable-file=")[1].split(",")
        if part.strip()
    }


def _lint_file_raw(path: str, src: str) -> List[LintViolation]:
    """All per-file rule results, UNFILTERED by waivers (the audit needs
    the raw set to decide which waivers still suppress something)."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [LintViolation(path, e.lineno or 0, "DLT000",
                              f"syntax error: {e.msg}")]
    out: List[LintViolation] = []
    for rule in _RULES:
        out.extend(rule(tree, src, path))
    return out


def lint_file(path: str, src: Optional[str] = None) -> List[LintViolation]:
    """Per-file rules (DLT000-016, DLT020-021) on one file; waivers
    applied. The
    interprocedural families (DLT017-019) need the whole-repo graph and
    only run under :func:`lint_paths`."""
    if src is None:
        with open(path, encoding="utf-8") as f:
            src = f.read()
    lines = src.splitlines()
    return sorted(
        (v for v in _lint_file_raw(path, src)
         if not _waived(v, lines, _parse_file_waivers(lines))),
        key=lambda v: (v.file, v.line, v.rule))


def _read_and_raw(path: str) -> Tuple[str, List[LintViolation]]:
    """(source, raw per-file violations) with content-hash caching."""
    apath = os.path.abspath(path)
    with open(apath, encoding="utf-8") as f:
        src = f.read()
    sha = hashlib.sha1(src.encode("utf-8", "replace")).hexdigest()
    cached = _FILE_RAW_CACHE.get(apath)
    if cached is not None and cached[0] == sha:
        return src, cached[1]
    raw = _lint_file_raw(apath, src)
    _FILE_RAW_CACHE[apath] = (sha, raw)
    return src, raw


def _repo_raw(files: List[str]) -> List[LintViolation]:
    """Raw (unwaived) interprocedural findings over a file working set,
    cached on the frozenset of (path, content-hash)."""
    graph = _cg.build_graph(files)
    key = frozenset((s.path, s.sha) for s in graph.summaries)
    cached = _REPO_RAW_CACHE.get(key)
    if cached is None:
        cached = []
        for rule in _REPO_RULES:
            cached.extend(rule(graph))
        _REPO_RAW_CACHE.clear()  # one working set at a time is enough
        _REPO_RAW_CACHE[key] = cached
    return cached


def lint_paths(paths: Iterable[str]) -> List[LintViolation]:
    """Per-file rules on every file plus the interprocedural DLT017-019
    families over the call graph of the whole working set."""
    files = _cg.discover_files(paths)
    out: List[LintViolation] = []
    srcs: Dict[str, str] = {}
    for f in files:
        src, raw = _read_and_raw(f)
        apath = os.path.abspath(f)
        srcs[apath] = src
        lines = src.splitlines()
        out.extend(v for v in raw
                   if not _waived(v, lines, _parse_file_waivers(lines)))
    for v in _repo_raw(files):
        src = srcs.get(v.file)
        if src is None:  # finding in a file outside the lint set (unlikely)
            out.append(v)
            continue
        lines = src.splitlines()
        if not _waived(v, lines, _parse_file_waivers(lines)):
            out.append(v)
    return sorted(out, key=lambda v: (v.file, v.line, v.rule))


# ------------------------------------------------------------ waiver audit
@dataclasses.dataclass(frozen=True)
class StaleWaiver:
    """A ``lint: disable`` comment that no longer suppresses anything."""
    file: str
    line: int               # 0 for file-wide waivers
    rules: Tuple[str, ...]  # () = bare line-waiver with no rule list
    scope: str              # "inline" | "file"

    def __str__(self):
        what = ",".join(self.rules) or "<all>"
        where = f"{self.file}:{self.line}" if self.scope == "inline" \
            else self.file
        return (f"{where}: stale waiver ({what}) — no {self.scope}-scope "
                "finding left to suppress; delete it")


def audit_waivers(paths: Iterable[str]) -> List[StaleWaiver]:
    """Every waiver comment in the working set that suppresses NO raw
    finding (per-file or interprocedural). Stale waivers hide real
    regressions: the rule fires again one refactor later and the comment
    swallows it silently."""
    files = _cg.discover_files(paths)
    raw_by_file: Dict[str, List[LintViolation]] = {}
    for f in files:
        _, raw = _read_and_raw(f)
        raw_by_file.setdefault(os.path.abspath(f), []).extend(raw)
    for v in _repo_raw(files):
        raw_by_file.setdefault(v.file, []).append(v)

    out: List[StaleWaiver] = []
    for f in files:
        summ = _cg.summarize_file(f)
        raws = raw_by_file.get(summ.path, [])
        for line, rules in sorted(summ.inline_waivers.items()):
            hit = any(v.line == line and (not rules or v.rule in rules)
                      for v in raws)
            if not hit:
                out.append(StaleWaiver(summ.path, line, rules, "inline"))
        for rule in sorted(summ.file_waivers):
            if not any(v.rule == rule for v in raws):
                out.append(StaleWaiver(summ.path, 0, (rule,), "file"))
    return out


def DEFAULT_TARGETS(repo_root: str) -> List[str]:
    """The tier-1 lint surface: the package and the tools."""
    return [os.path.join(repo_root, "deeplearning4j_tpu"),
            os.path.join(repo_root, "tools")]

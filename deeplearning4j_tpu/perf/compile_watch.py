"""Compile/dispatch observability for jitted programs.

On TPU the difference between "fast" and "30x slower than it should be" is
usually invisible in the code: a recompile storm looks exactly like a slow
step loop. This module makes it countable. ``CompileWatch.wrap`` wraps any
``jax.jit`` callable so every call records one *dispatch* and — via the
jitted function's executable-cache size delta — any *compile* it triggered.
Tests and benches then assert "N batches, 1 compile" instead of guessing
from wall clock.

Counts aggregate per (watch, key) and into a process-wide ``GLOBAL`` watch.

What a compile COST is read from the events JAX itself emits: the process
has ONE ``jax.monitoring`` listener (``install_listener``, its two halves
registered once: JAX hands durations and plain events to different
registries), which files the seconds of tracing, lowering, the backend's
compile (or, on a hit of the persistent cache, the load of the executable)
and the cache's hits and misses against the watched program being called on
the thread (``PHASES``; what compiles outside any watched call goes to
``GLOBAL`` under ``UNWATCHED``, event by event: with no call to close over
them, a jit traced inside a jit adds both its events there).
``perf.compile_cache.cache_hits`` reads the same listener.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

#: seconds by phase of a program's compiles, by the ``jax.monitoring``
#: duration event each comes from. ``backend_s`` runs from the request for
#: an executable to having it: the XLA compile on a miss of the persistent
#: cache, ``cache_load_s`` (a part of it) on a hit.
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}
_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
#: the key under which ``GLOBAL`` files what compiled outside a watched call
UNWATCHED = "unwatched"


def _cache_size(fn) -> Optional[int]:
    """Executable-cache size of a jitted callable, or None when the JAX
    version doesn't expose it (fallback: shape-signature counting)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


class CompileWatch:
    """Per-key compile/dispatch counters. Thread-safe (the inference worker
    dispatches from its own thread). Besides compile/dispatch pairs, freeform
    integer ``counters`` record one-off trace-time events (e.g. the attention
    layer falling back from the Pallas flash kernel to the dense path)."""

    def __init__(self, name: str = ""):
        self.name = name
        self._lock = threading.Lock()
        self._compiles: Dict[str, int] = {}
        self._dispatches: Dict[str, int] = {}
        self._counters: Dict[str, int] = {}  # lint: disable=DLT007 (pre-obs surface; absorbed into the registry by obs.absorb_compile_watch)
        self._phases: Dict[str, Dict[str, float]] = {}

    # ------------------------------------------------------------ recording
    def _record(self, key: str, compiles: int, dispatches: int):
        with self._lock:
            self._compiles[key] = self._compiles.get(key, 0) + compiles
            self._dispatches[key] = self._dispatches.get(key, 0) + dispatches

    def _record_phases(self, key: str, phases: Dict[str, float]):
        with self._lock:
            mine = self._phases.setdefault(key, {})
            for name, value in phases.items():
                mine[name] = mine.get(name, 0) + value

    def bump(self, counter: str, by: int = 1):
        """Increment a freeform event counter."""
        with self._lock:
            self._counters[counter] = self._counters.get(counter, 0) + int(by)

    def counter(self, counter: str) -> int:
        with self._lock:
            return self._counters.get(counter, 0)

    def counters(self, prefix: str = "") -> Dict[str, int]:
        with self._lock:
            return {k: v for k, v in self._counters.items()
                    if k.startswith(prefix)}

    def wrap(self, fn, key: str) -> "_WatchedFunction":
        """Wrap a jitted callable; every call records into this watch AND
        the process-wide GLOBAL watch."""
        install_listener()
        return _WatchedFunction(fn, key, sinks=(self, GLOBAL))

    # -------------------------------------------------------------- queries
    def compiles(self, key: Optional[str] = None) -> int:
        with self._lock:
            if key is None:
                return sum(self._compiles.values())
            return self._compiles.get(key, 0)

    def dispatches(self, key: Optional[str] = None) -> int:
        with self._lock:
            if key is None:
                return sum(self._dispatches.values())
            return self._dispatches.get(key, 0)

    def compile_phases(self, key: Optional[str] = None) -> Dict[str, float]:
        """Seconds by phase (``PHASES``) and the persistent cache's
        ``cache_hits`` / ``cache_misses`` of the compiles of program
        ``key``; ``None``: {program: ...} for every program that compiled."""
        with self._lock:
            if key is None:
                return {k: dict(v) for k, v in self._phases.items()}
            return dict(self._phases.get(key, {}))

    def reset(self):
        with self._lock:
            self._compiles.clear()
            self._dispatches.clear()
            self._counters.clear()
            self._phases.clear()

    def as_dict(self) -> dict:
        with self._lock:
            out = {
                "compiles": sum(self._compiles.values()),
                "dispatches": sum(self._dispatches.values()),
                "by_key": {k: {"compiles": self._compiles.get(k, 0),
                               "dispatches": self._dispatches.get(k, 0)}
                           for k in sorted(set(self._compiles)
                                           | set(self._dispatches))},
            }
            if self._counters:
                out["counters"] = dict(self._counters)
            return out


GLOBAL = CompileWatch("global")

# Watches of the watched call currently tracing/executing on THIS thread.
# Layer code that wants to record a trace-time event against "whichever
# model is being traced right now" (e.g. the attention flash-kernel path
# choice) calls bump_active(): the event lands on the owning model's watch
# when the trace runs inside a wrapped call, and on GLOBAL always — so
# per-model stats never misattribute another model's traces.
_active = threading.local()


def bump_active(counter: str, by: int = 1) -> None:
    sinks = getattr(_active, "sinks", None) or (GLOBAL,)
    for sink in sinks:
        sink.bump(counter, by)
    if GLOBAL not in sinks:
        GLOBAL.bump(counter, by)


# Dispatch observers: callables invoked after every watched call with
# (key, fn, args, kwargs, compiles). analysis.trace_check registers one to
# attribute recompiles and closure-captured constants to live dispatches.
# Observer errors are swallowed — observability must never break the step.
_observers: list = []


def add_dispatch_observer(cb) -> None:
    _observers.append(cb)


def remove_dispatch_observer(cb) -> None:
    try:
        _observers.remove(cb)
    except ValueError:
        pass


class _WatchedFunction:
    """Callable proxy over a jitted function. Compiles are detected from the
    function's executable-cache growth; when that API is unavailable, from
    first-sight of the call's (shape, dtype) signature — same answer for
    shape-driven recompiles, which are the ones bucketing kills."""

    def __init__(self, fn, key: str, sinks):
        self._fn = fn
        self._key = key
        self._sinks = sinks
        self._seen_sigs = set()
        self._sig_lock = threading.Lock()

    @staticmethod
    def _signature(args, kwargs):
        import jax
        parts = []
        for leaf in jax.tree_util.tree_leaves((args, kwargs)):
            shape = getattr(leaf, "shape", None)
            if shape is not None:
                parts.append((tuple(shape), str(getattr(leaf, "dtype", ""))))
            else:
                parts.append((type(leaf).__name__,))
        return tuple(parts)

    def __call__(self, *args, **kwargs):
        before = _cache_size(self._fn)
        prev = getattr(_active, "sinks", None)
        prev_call = getattr(_active, "call", None)
        _active.sinks = self._sinks
        _active.call = call = _CompilePhases()
        try:
            out = self._fn(*args, **kwargs)
        finally:
            _active.sinks, _active.call = prev, prev_call
        after = _cache_size(self._fn)
        if before is not None and after is not None:
            compiled = max(0, after - before)
        else:
            sig = self._signature(args, kwargs)
            with self._sig_lock:
                compiled = 0 if sig in self._seen_sigs else 1
                self._seen_sigs.add(sig)
        phases = call.totals()
        for sink in self._sinks:
            sink._record(self._key, compiled, 1)
            if phases:
                sink._record_phases(self._key, phases)
        if compiled:
            # an event at the step where it happened, and a mark on the
            # enclosing train.dispatch: a trace or a crash ring then shows
            # WHICH step recompiled, not only that one did, and what the
            # compile's time went on (PHASES; ``cache_hit``: the executable
            # came from the persistent cache)
            from deeplearning4j_tpu.obs.trace import get_tracer
            cost = {name: phases.get(name, 0.0) for name in PHASES.values()}
            cost["cache_hit"] = int(phases.get("cache_hits", 0) > 0
                                    and not phases.get("cache_misses", 0))
            tracer = get_tracer()
            tracer.event("compile", program=self._key, **cost)
            enclosing = tracer.current()
            if enclosing is not None:
                enclosing.set(compiled=1, **cost)
        for cb in list(_observers):
            try:
                cb(self._key, self._fn, args, kwargs, compiled)
            except Exception:
                pass
        return out

    def __getattr__(self, name):  # lower/trace/cache introspection pass through
        return getattr(self._fn, name)


# ------------------------------------------------- the process's one listener
class _CompilePhases:
    """What the listener heard during one watched call, on its thread."""

    def __init__(self):
        self._spans: Dict[str, list] = {}
        self._counts: Dict[str, int] = {}

    def add_seconds(self, phase: str, seconds: float):
        # an event arrives when its section ends: one that lasted
        # ``seconds`` holds the events of its phase that ended inside it (a
        # jit traced inside a jit reports both). Keep the outermost. Events
        # come in the order of their ends, so those inside this one are the
        # list's tail: tracing a step fires thousands of them (every
        # ``jnp`` call is a jit), and a scan of the whole list for each
        # cost seconds of set-up
        now = time.perf_counter()
        spans = self._spans.setdefault(phase, [])
        while spans and spans[-1][0] >= now - seconds:
            spans.pop()
        spans.append((now, seconds))

    def count(self, what: str):
        self._counts[what] = self._counts.get(what, 0) + 1

    def totals(self) -> Dict[str, float]:
        """{} when the call compiled nothing."""
        out: Dict[str, float] = {phase: sum(s for _, s in spans)
                                 for phase, spans in self._spans.items()}
        out.update(self._counts)
        return out


def _on_duration(name: str, seconds: float, **_):
    phase = PHASES.get(name)
    if phase is None:
        return
    call = getattr(_active, "call", None)
    if call is not None:
        call.add_seconds(phase, seconds)
    else:
        GLOBAL._record_phases(UNWATCHED, {phase: seconds})


def _on_event(name: str, **_):
    what = _COUNTS.get(name)
    if what is None:
        return
    call = getattr(_active, "call", None)
    if call is not None:
        call.count(what)
    else:
        GLOBAL._record_phases(UNWATCHED, {what: 1})


_listener_lock = threading.Lock()
_listener_installed = False


def install_listener() -> None:
    """Register the process's one ``jax.monitoring`` listener (idempotent;
    called when the first program is wrapped and when the persistent cache
    is enabled, so importing this module has no side effect)."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listener_installed = True


def cache_hits() -> int:
    """Hits of the persistent compilation cache this process has seen since
    the listener was installed (compiles answered from disk)."""
    return int(sum(p.get("cache_hits", 0)
                   for p in GLOBAL.compile_phases().values()))

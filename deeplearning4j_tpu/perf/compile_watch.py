"""Compile/dispatch observability for jitted programs.

On TPU the difference between "fast" and "30x slower than it should be" is
usually invisible in the code: a recompile storm looks exactly like a slow
step loop. This module makes it countable. ``CompileWatch.wrap`` wraps any
``jax.jit`` callable so every call records one *dispatch* and — via the
jitted function's executable-cache size delta — any *compile* it triggered.
Tests and benches then assert "N batches, 1 compile" instead of guessing
from wall clock.

Counts aggregate per (watch, key) and into a process-wide ``GLOBAL`` watch;
a ``jax.monitoring`` listener additionally counts backend compile events
for code paths that never go through ``wrap`` (best-effort: the event
stream's granularity varies across JAX versions, so exact assertions should
use wrapped functions).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional


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

    # ------------------------------------------------------------ recording
    def _record(self, key: str, compiles: int, dispatches: int):
        with self._lock:
            self._compiles[key] = self._compiles.get(key, 0) + compiles
            self._dispatches[key] = self._dispatches.get(key, 0) + dispatches

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

    def reset(self):
        with self._lock:
            self._compiles.clear()
            self._dispatches.clear()
            self._counters.clear()

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
        _active.sinks = self._sinks
        try:
            out = self._fn(*args, **kwargs)
        finally:
            _active.sinks = prev
        after = _cache_size(self._fn)
        if before is not None and after is not None:
            compiled = max(0, after - before)
        else:
            sig = self._signature(args, kwargs)
            with self._sig_lock:
                compiled = 0 if sig in self._seen_sigs else 1
                self._seen_sigs.add(sig)
        for sink in self._sinks:
            sink._record(self._key, compiled, 1)
        if compiled:
            # an event at the step where it happened, and a mark on the
            # enclosing train.dispatch: a trace or a crash ring then shows
            # WHICH step recompiled, not only that one did
            from deeplearning4j_tpu.obs.trace import get_tracer
            tracer = get_tracer()
            tracer.event("compile", program=self._key)
            enclosing = tracer.current()
            if enclosing is not None:
                enclosing.set(compiled=1)
        for cb in list(_observers):
            try:
                cb(self._key, self._fn, args, kwargs, compiled)
            except Exception:
                pass
        return out

    def __getattr__(self, name):  # lower/trace/cache introspection pass through
        return getattr(self._fn, name)


# --------------------------------------------------- backend event listener
_backend_compile_events = 0
_backend_lock = threading.Lock()
_listener_installed = False


def _install_listener():
    global _listener_installed
    if _listener_installed:
        return
    import jax

    def _on_event(name, **kwargs):
        if "compile" in name:
            global _backend_compile_events
            with _backend_lock:
                _backend_compile_events += 1

    jax.monitoring.register_event_listener(_on_event)
    _listener_installed = True


def backend_compile_events() -> int:
    """Process-wide count of backend compile events (best-effort; install
    happens on first query so importing this module stays side-effect-free
    until observability is actually wanted)."""
    _install_listener()
    with _backend_lock:
        return _backend_compile_events

"""The repo's one span tracer, on the profiler's clock, + the synced bench
Stopwatch.

Spans answer the question metrics can't: *why was step 812 slow* — was the
host waiting on data, staging the batch, dispatching, or running
listeners? Every span is ALSO a ``jax.profiler.TraceAnnotation``, entered
and left with the span whether the tracer is enabled or not. With no
profiler session that costs a flag test in C++; with one (a benchmark's
``--trace 1`` run, ``optimize.listeners.ProfilerListener``) the span sits
on the host plane of the ``xplane.pb``, on the clock the device planes use,
so an idle gap on the device can be put down to a span of the program with
nothing switched on.

The tracer itself is explicit-clock (injectable ``clock``; the
overhead-guard test counts clock calls instead of trusting wall time) and
DISABLED by default: ``span()`` on a disabled tracer allocates the
annotation and nothing else — no clock read, no record, no sink dispatch.
Enabled, a finished span's record carries ``name``, ``id``, ``parent``
(the id of the span open on that thread when it began), ``thread``,
``start`` (on the duration clock), ``dur_ms``, ``wall`` and ``attrs``; a
span that names no ``step`` of its own takes its parent's, so all spans of
one training step share one. Self time is a span's duration minus its
children's. Spans are host-side only and must never enter jit-traced code
(DLT002: a clock read inside a traced function freezes at trace time), and
NO span adds a wait for the device (``checkpoint.drain`` names the one a
save's ``device_get`` makes anyway): the fit loops' ``train.iteration`` (one
turn of the loop) is the step time once the device is the bottleneck,
because the runtime's back-pressure then holds the host inside
``train.dispatch``;
device time per step proper comes from the device planes of a profiler
trace. Operators without a profiler read ``train_iteration_ms`` (p50/p95).

The fit loops' span tree (the turn and its wait: nn/engine.py's
``run_epochs``, the one loop under every fit; the batch's own spans: its
``Network._fit_batch``; parallel/trainer.py's hand-over, perf/prefetch.py,
checkpoint/manager.py)::

    train.iteration                 one turn of the loop
      train.data_wait               next() of the stream, above prefetch
        prefetch.place              issuing batch N+1's device_put
      train.step_host               the loop's own work on its batch
        train.stage                 host->device hand-over of THIS batch
        train.dispatch              rng split + the jitted step's call
        train.post                  score handle, counters; sampled=1: the
                                    feature slice a listener reads
        train.listeners             iteration_done of the listeners
        checkpoint.step_end         every turn with a manager; on a save:
          checkpoint.save           what the save costs the calling thread
            checkpoint.snapshot     self time: the device-to-host copy
              checkpoint.drain      the wait for the queued steps to end
            checkpoint.enqueue      the hand-over: the writer's lag, if any
            checkpoint.barrier      multi-process only

    checkpoint_writer.write         the writer thread's work on a snapshot
      checkpoint_writer.serialize   npz + zip, in memory
      checkpoint_writer.hash        sha256 of the payload
      checkpoint_writer.put         the storage backend: write, fsync, rename
      checkpoint_writer.journal     guard, retention, manifest

``checkpoint.save`` opens whoever calls ``CheckpointManager.save`` (the
step trigger, an epoch's end, the early-stopping saver, a user); all spans
of one save, on both threads, carry its ``seq`` and the ``step`` its
checkpoint holds (the steps done: under a fit loop the turn's own step + 1).
``checkpoint.drain`` is a ``block_until_ready`` of the trees the snapshot
is about to ``device_get``, which would make the same wait one line later:
it adds no synchronisation and gives the wait a name. The writer's spans
carry a prefix of their own on purpose: a reader that puts device idle time
down to the training thread's spans by the ``checkpoint.`` prefix must not
find a seconds-long span of another thread under it. With
``async_write=False`` (and on the sharded path) they open on the calling
thread, inside ``checkpoint.save``.

Finished spans and instant events are dispatched to *sinks* (the crash
flight recorder's ring, a JSONL event log) and — when the tracer carries a
registry — observed into an auto-registered ``<span>_ms`` histogram, so
the per-step phase breakdown shows up in the Prometheus scrape for free.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import threading
import time
from typing import Callable, List, Optional

from jax.profiler import TraceAnnotation

from deeplearning4j_tpu.obs.registry import (DEFAULT_BUCKETS_MS,
                                             STEP_BUCKETS_MS)

log = logging.getLogger(__name__)

#: spans whose ``<span>_ms`` histogram takes other buckets than the
#: registry's default: the fit loops' turn is the step time, and a step's
#: p95 wants a step's resolution
SPAN_BUCKETS_MS = {"train.iteration": STEP_BUCKETS_MS}

__all__ = ["Tracer", "get_tracer", "configure_tracer", "Stopwatch"]


class _Annotation(TraceAnnotation):
    """A disabled tracer's span: the profiler annotation (begun when
    made) under the span interface, and nothing else."""

    __slots__ = ()

    def end(self):
        self.__exit__(None, None, None)

    cancel = end

    def set(self, **attrs):
        self.set_metadata(**attrs)


def _inherit_step(attrs: dict, above: Optional["_Span"]):
    """A span or event that names no ``step`` takes the enclosing span's:
    all records of one training step then share one."""
    if (above is not None and "step" not in attrs
            and "step" in above.attrs):
        attrs["step"] = above.attrs["step"]


class _Span:
    __slots__ = ("tracer", "name", "attrs", "id", "parent", "_ann", "_t0",
                 "_wall", "_done")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        stack = tracer._stack()
        above = stack[-1] if stack else None
        _inherit_step(attrs, above)
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.id = next(tracer._ids)
        self.parent = None if above is None else above.id
        self._done = False
        stack.append(self)
        self._ann = TraceAnnotation(name, **attrs)
        self._wall = time.time()
        self._t0 = tracer.clock()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def set(self, **attrs):
        """Attributes learned while the span is open (``compiled=1``)."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def _close(self) -> bool:
        if self._done:
            return False
        self._done = True
        self._ann.__exit__(None, None, None)
        # itself only, wherever it sits: a span ended late (a generator
        # closed by the collector) must not unseat live ones
        with contextlib.suppress(ValueError):
            self.tracer._stack().remove(self)
        return True

    def end(self):
        dur_ms = (self.tracer.clock() - self._t0) * 1000.0
        if self._close():
            self.tracer._dispatch({
                "kind": "span", "name": self.name, "id": self.id,
                "parent": self.parent, "thread": threading.get_ident(),
                "start": self._t0, "dur_ms": round(dur_ms, 4),
                "wall": self._wall, "attrs": self.attrs})

    def cancel(self):
        """Leave the span without a record (a probe that found nothing)."""
        self._close()


class Tracer:
    """See module docstring.

    ``clock`` is the duration clock (default ``time.perf_counter``);
    wall-clock timestamps for the event log come from ``time.time``.
    ``registry`` (a ``obs.registry.MetricsRegistry``) makes every span
    also an observation in a ``<name>_ms`` histogram (dots become
    underscores)."""

    def __init__(self, enabled: bool = False,
                 clock: Callable[[], float] = time.perf_counter,
                 registry=None):
        self.enabled = bool(enabled)
        self.clock = clock
        self.registry = registry
        self._sinks: List[Callable[[dict], None]] = []
        self._sink_lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    # ---------------------------------------------------------------- sinks
    def add_sink(self, sink: Callable[[dict], None]):
        with self._sink_lock:
            if sink not in self._sinks:
                self._sinks.append(sink)
        return sink

    def remove_sink(self, sink):
        with self._sink_lock:
            try:
                self._sinks.remove(sink)
            except ValueError:
                pass

    def _dispatch(self, record: dict):
        if self.registry is not None and record["kind"] == "span":
            try:
                name = record["name"].replace(".", "_")
                self.registry.histogram(
                    f"{name}_ms", unit="ms",
                    help=f"duration of span '{record['name']}' "
                         "(auto-registered by the tracer)",
                    buckets=SPAN_BUCKETS_MS.get(record["name"],
                                                DEFAULT_BUCKETS_MS)
                ).observe(record["dur_ms"])
            except Exception as e:
                log.debug("span histogram observe failed (%s: %s)",
                          type(e).__name__, e)
        with self._sink_lock:
            sinks = list(self._sinks)
        for sink in sinks:
            try:
                sink(record)
            except Exception as e:  # observability never breaks the step
                log.debug("trace sink failed (%s: %s)", type(e).__name__, e)

    # ----------------------------------------------------------------- API
    def span(self, name: str, **attrs):
        """Context manager timing a host-side section; always a profiler
        annotation too. Disabled tracer: the annotation alone (no clock
        read, no record, no sink call)."""
        if not self.enabled:
            return _Annotation(name, **attrs)
        return _Span(self, name, attrs)

    def current(self) -> Optional[_Span]:
        """The innermost span open on this thread; None when there is
        none or the tracer is disabled."""
        if not self.enabled:
            return None
        stack = self._stack()
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def attach(self, span: Optional[_Span]):
        """Make ``span`` (another thread's ``current()``) the parent of
        the spans this thread opens inside: a step that a watchdog runs on
        a worker thread stays in its turn's tree. ``None`` does nothing."""
        if span is None:
            yield
            return
        stack = self._stack()
        stack.append(span)
        try:
            yield
        finally:
            with contextlib.suppress(ValueError):
                stack.remove(span)

    def event(self, name: str, **attrs):
        """Instant event (no duration): an instant annotation always, and
        a record into the sinks when enabled."""
        above = self.current()
        _inherit_step(attrs, above)
        _Annotation(name, **attrs).end()
        if not self.enabled:
            return
        self._dispatch({"kind": "event", "name": name,
                        "id": next(self._ids),
                        "parent": None if above is None else above.id,
                        "thread": threading.get_ident(),
                        "start": self.clock(), "wall": time.time(),
                        "dur_ms": 0.0, "attrs": attrs})

    def wrap_iter(self, iterable, name: str, turn: Optional[str] = None,
                  step: Optional[Callable[[], int]] = None):
        """Time each ``next()`` of ``iterable`` as a ``name`` span — how
        the fit loops measure data-wait without restructuring. With
        ``turn``, every item's whole turn of the caller's loop (the
        ``next()`` and the loop's body, up to the following ``next()``) is
        a ``turn`` span whose first child is the wait. ``step`` is read at
        each turn's start and lands on both as ``step=``. The exhausted
        probe is not a data wait, and a turn whose loop died under it is
        not a turn: both are dropped, so N items → N spans."""
        it = iter(iterable)
        while True:
            attrs = {} if step is None else {"step": step()}
            outer = None if turn is None else self.span(turn, **attrs)
            wait = self.span(name, **attrs)
            try:
                item = next(it)
            except BaseException as e:
                wait.cancel()
                if outer is not None:
                    outer.cancel()
                if isinstance(e, StopIteration):
                    return
                raise
            wait.end()
            try:
                yield item
            except GeneratorExit:
                if outer is not None:
                    outer.cancel()
                raise
            if outer is not None:
                outer.end()


# ---------------------------------------------------------- global default
_global_lock = threading.Lock()
_global: Optional[Tracer] = None


def get_tracer() -> Tracer:
    """The process-wide tracer (disabled until configured)."""
    global _global
    with _global_lock:
        if _global is None:
            _global = Tracer(enabled=False)
        return _global


def configure_tracer(enabled: Optional[bool] = None, clock=None,
                     registry=None) -> Tracer:
    """Reconfigure the global tracer in place (handles held by
    instrumented code stay valid). Passing ``registry`` also turns span →
    histogram observation on; ``configure_tracer(enabled=True,
    registry=get_registry())`` is the standard \"turn telemetry on\"
    call."""
    t = get_tracer()
    if enabled is not None:
        t.enabled = bool(enabled)
    if clock is not None:
        t.clock = clock
    if registry is not None:
        t.registry = registry
    return t


class Stopwatch:
    """Synced stopwatch for benches and tools (the DLT003 discipline in
    one place). ``stop(sync=x)`` calls ``jax.block_until_ready(x)`` BEFORE
    reading the clock, so an async-dispatched result cannot fake a fast
    measurement; call ``stop()`` bare only when the measured call already
    synced (a host-side join, a function that fetches values itself).

    Usage::

        sw = Stopwatch().start()
        out = step(x)
        dt = sw.stop(out)          # blocks on `out`, then stops the clock

    or as a context manager (no sync — for already-synced bodies)::

        with Stopwatch() as sw:
            run_and_fetch()
        print(sw.seconds)
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._t0: Optional[float] = None
        self.seconds: float = 0.0

    def start(self) -> "Stopwatch":
        self._t0 = self._clock()
        return self

    def stop(self, sync=None) -> float:
        """Optionally block on ``sync`` (any pytree of arrays), then stop.
        Returns (and stores in ``seconds``) the elapsed time."""
        if sync is not None:
            import jax
            jax.block_until_ready(sync)
        if self._t0 is None:
            raise RuntimeError("Stopwatch.stop() before start()")
        self.seconds = self._clock() - self._t0
        return self.seconds

    def __enter__(self) -> "Stopwatch":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

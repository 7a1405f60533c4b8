"""What a fit loop is fed from, and the clock around it.

``PoolFeed`` hands out a fixed pool of seeded host batches again and again
until a deadline (the window) or a count (set-up's first steps), so a run
does the same work for every seed and never waits for data to be made.
``TimedStream`` wraps whatever iterator the fit loop is finally given
(the program's ``DevicePrefetchIterator`` over the feed) and times every
``next()``: the share of the window the loop spent waiting for its input,
staging to the device included. Between two ``next()`` it holds a
``bench.fit_step`` span open, so that a traced run can tell idle time under
the loop's own work from idle time under the wait for input. ``TraceSlice`` turns the profiler on for a
short steady slice in the middle of the window of a ``--trace 1`` run."""

from __future__ import annotations

import time
from typing import Callable, Optional, Sequence


def clock() -> float:
    return time.perf_counter()


class PoolFeed:
    def __init__(self, pool: Sequence, start: int = 0,
                 limit: Optional[int] = None,
                 deadline: Optional[float] = None):
        if limit is None and deadline is None:
            raise ValueError("an endless feed needs a deadline or a limit")
        self._pool, self._at = pool, start
        self._limit, self._deadline = limit, deadline
        self.handed_out = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self._limit is not None and self.handed_out >= self._limit:
            raise StopIteration
        if self._deadline is not None and clock() >= self._deadline:
            raise StopIteration
        item = self._pool[(self._at + self.handed_out) % len(self._pool)]
        self.handed_out += 1
        return item


class TraceSlice:
    """Profiler on from ``begin_s`` after ``arm()`` for ``length_s``; driven
    by ``tick()`` from the loop's own thread, between two steps."""

    def __init__(self, out_dir: str, begin_s: float, length_s: float):
        self.out_dir, self.begin_s, self.length_s = out_dir, begin_s, length_s
        self._t0: Optional[float] = None
        self._on_at: Optional[float] = None
        self.done = False
        self.overhead_s = 0.0     # host time inside start_trace / stop_trace

    def arm(self) -> None:
        self._t0 = clock()

    def tick(self) -> None:
        if self._t0 is None or self.done:
            return
        now = clock() - self._t0
        if self._on_at is None:
            if now >= self.begin_s:
                import jax
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0    # host spans are our own
                t = clock()
                jax.profiler.start_trace(self.out_dir,
                                         profiler_options=options)
                self.overhead_s += clock() - t
                self._on_at = clock() - self._t0
        elif now - self._on_at >= self.length_s:
            self.finish()

    def finish(self) -> None:
        if self._on_at is not None and not self.done:
            import jax
            t = clock()
            jax.profiler.stop_trace()
            self.overhead_s += clock() - t
            self.done = True


def span(name: str):
    """A host span on the profiler's clock (``bench.<name>``); costs next
    to nothing while the profiler is off."""
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


class TimedStream:
    def __init__(self, inner, on_batch: Optional[Callable[[], None]] = None):
        self._inner, self._on_batch = inner, on_batch
        self.wait_s = 0.0
        self.batches = 0

    def __iter__(self):
        it = iter(self._inner)
        while True:
            t0 = clock()
            with span("input_wait"):
                try:
                    item = next(it)
                except StopIteration:
                    return
            self.wait_s += clock() - t0
            self.batches += 1
            if self._on_batch is not None:
                self._on_batch()
            with span("fit_step"):      # the loop's own work on this batch
                yield item

"""The program's own spans out of a profiler trace.

Every span of the program's tracer (``deeplearning4j_tpu/obs/trace.py``) is
also a ``jax.profiler.TraceAnnotation``, so a ``--trace 1`` run finds the fit
loops' span tree on the host plane of its ``xplane.pb``, on the clock the
device planes use, with nothing switched on in the program:

    train.iteration > train.data_wait > prefetch.place
                    > train.step_host > train.stage, train.dispatch,
                                        train.post, train.listeners,
                                        checkpoint.step_end > checkpoint.snapshot
    compile (an instant, inside the train.dispatch that compiled)

This module reads them (names, intervals, the ``step`` stat), nests them by
interval per thread, gives self times, and hands
``harness.trace.idle_by_host_span`` a ``Trace`` whose host spans are the
program's, so that the gap-splitting code there (innermost span wins) says
under which span of the program the device idled. A trace of a program
that has no such span (an older commit) reads as ``None``, never as 0."""

from __future__ import annotations

import bisect
import dataclasses
import os
from typing import Dict, List, Optional

from harness import trace as tracing

PREFIXES = ("train.", "prefetch.", "checkpoint.")
INSTANTS = ("compile",)
# the gap-splitting sorts every span for every gap: hand it the gaps in
# runs of this many, each with the spans that overlap the run
_CHUNK = 256


@dataclasses.dataclass(eq=False)
class Span:
    name: str
    start: float                 # seconds, the trace's clock
    end: float
    thread: str
    stats: Dict[str, object]
    parent: Optional["Span"] = None
    children: List["Span"] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def step(self) -> Optional[int]:
        """The span's own ``step`` stat, or its nearest ancestor's."""
        at: Optional[Span] = self
        while at is not None:
            if "step" in at.stats:
                return int(at.stats["step"])
            at = at.parent
        return None

    def self_seconds(self) -> float:
        """Duration minus the part of it that child spans cover."""
        covered = tracing.union([(c.start, c.end) for c in self.children])
        return self.seconds - tracing.total(
            tracing.clip(covered, self.start, self.end))

    def child_seconds(self, name: str) -> float:
        return sum(c.seconds for c in self.children if c.name == name)


def nest(spans: List[Span]) -> List[Span]:
    """Set ``parent`` and ``children`` by interval, thread by thread: a
    span's parent is the innermost span of its thread that holds it whole.
    Returns the spans sorted by start."""
    for s in spans:
        s.parent, s.children = None, []
    by_thread: Dict[str, List[Span]] = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    for group in by_thread.values():
        open_: List[Span] = []
        for s in sorted(group, key=lambda s: (s.start, -s.end)):
            while open_ and not (open_[-1].start <= s.start
                                 and s.end <= open_[-1].end):
                open_.pop()
            if open_:
                s.parent = open_[-1]
                open_[-1].children.append(s)
            open_.append(s)
    return sorted(spans, key=lambda s: s.start)


def read(path: str) -> List[Span]:
    """The program's spans of one ``*.xplane.pb`` (or of the directory that
    holds one), nested. Empty when the program wrote none."""
    import jax

    if os.path.isdir(path):
        path = tracing.find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    spans: List[Span] = []
    for plane in data.planes:
        if plane.name != tracing.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if not (name.startswith(PREFIXES) or name in INSTANTS):
                    continue
                start = float(ev.start_ns) * 1e-9
                spans.append(Span(name, start,
                                  start + float(ev.duration_ns) * 1e-9,
                                  line.name, dict(ev.stats)))
    return nest(spans)


class ProgramSpans:
    """The nested spans of one traced run, and the device's idle time split
    over them (worked out once, on first use)."""

    def __init__(self, spans: List[Span]):
        self.spans = spans
        self._idle: Optional[Dict[str, float]] = None

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def idle_by_name(self, trace: tracing.Trace) -> Dict[str, float]:
        """Idle seconds of the first chip inside the trace's window, by the
        name of the innermost program span that covered them; what none
        covered is ``host_unattributed``."""
        if self._idle is None:
            self._idle = idle_by_span_name(trace, self.spans)
        return self._idle


def idle_by_span_name(trace: tracing.Trace,
                      spans: List[Span]) -> Dict[str, float]:
    """``harness.trace.idle_by_host_span`` with the program's spans in the
    benchmark's place. It is handed the busy intervals of the first chip in
    runs of ``_CHUNK`` (each run ends on the interval the next begins with,
    so every gap is in exactly one) with the spans that overlap the run:
    the same sums as one call over everything, in a time that does not grow
    with gaps times spans."""
    w = tracing.window(trace)
    chips = [d for d in trace.devices if d.ops]
    if w is None or not chips:
        return {}
    busy = tracing.clip(tracing.union(
        [(s, e) for _, s, e in chips[0].ops]), *w)
    # of two spans that begin together the shorter is the inner one: put it
    # first, where the gap-splitting's stable "later start wins" keeps it
    host = sorted(((s.name, s.start, s.end) for s in spans
                   if s.end > s.start), key=lambda sp: (sp[1], sp[2]))
    starts = [h[1] for h in host]
    longest = max((h[2] - h[1] for h in host), default=0.0)
    sums: Dict[str, float] = {}
    for lo in range(0, max(len(busy) - 1, 1), _CHUNK):
        run = busy[lo:lo + _CHUNK + 1]
        begin, end = run[0][0], run[-1][1]
        near = [h for h in host[bisect.bisect_left(starts, begin - longest):
                                bisect.bisect_left(starts, end)]
                if h[2] > begin]
        part = tracing.Trace(
            [tracing.DeviceTimeline(chips[0].index,
                                    [("busy", s, e) for s, e in run], [])],
            near)
        for name, secs in tracing.idle_by_host_span(part, n=len(near) + 1):
            sums[name] = sums.get(name, 0.0) + secs
    return sums


def of(ctx: dict) -> Optional[ProgramSpans]:
    """The traced run's program spans, read once per run (kept on ``ctx``):
    from ``<root>/.bench_trace/<cell>/``, as ``run.py`` names the directory.
    ``None`` when the trace holds no span of the program."""
    if "program_spans" not in ctx:
        cell = ctx["cell"]
        spans = read(os.path.join(cell.root, ".bench_trace", cell.name))
        ctx["program_spans"] = ProgramSpans(spans) if spans else None
    return ctx["program_spans"]


# which spans are the loop's own work on a batch, and which its input path
LOOP = ("train.step_host", "train.stage", "train.dispatch", "train.post",
        "train.listeners")
INPUT = ("train.data_wait",)


def idle_ms_per_step(ctx: dict, names, prefix: str) -> Optional[float]:
    """Device idle time per step under the spans ``names`` or those whose
    name starts with ``prefix``: what two metrics read."""
    program = of(ctx)
    steps = tracing.steps(ctx["trace"])
    if program is None or not steps:
        return None
    idle = program.idle_by_name(ctx["trace"])
    secs = sum(v for k, v in idle.items()
               if k in names or k.startswith(prefix))
    return 1000.0 * secs / steps

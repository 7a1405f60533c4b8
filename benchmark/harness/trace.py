"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers
the per-layer metrics read. Needs nothing but JAX's own
``jax.profiler.ProfileData``.

What a TPU trace looks like (looked at by hand on a v5e, jax 0.9.0): one
plane per chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one
event per executed HLO operation and whose line ``XLA Modules`` holds one
event per executed program; one plane ``/host:CPU`` with a line per host
thread, where ``jax.profiler.TraceAnnotation`` spans appear under the name
they were given. All planes share one clock (nanoseconds)."""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# HLO operations that move data between chips
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
_SHAPE = re.compile(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]")

Interval = Tuple[float, float]          # (start_s, end_s)


@dataclasses.dataclass
class DeviceTimeline:
    index: int
    ops: List[Tuple[str, float, float]]       # (name, start_s, end_s)
    modules: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTimeline]
    host_spans: List[Tuple[str, float, float]]   # benchmark's annotations


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> List[Tuple[str, float, float]]:
    out = []
    for ev in line.events:
        start = float(ev.start_ns) * 1e-9
        out.append((ev.name, start, start + float(ev.duration_ns) * 1e-9))
    return out


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Read one ``*.xplane.pb`` (or a directory that holds one)."""
    import jax

    if os.path.isdir(path):
        path = find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    modules = _events(line)
            devices.append(DeviceTimeline(int(m.group(1)), ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e[0].startswith(span_prefix))
    devices.sort(key=lambda d: d.index)
    host.sort(key=lambda e: e[1])
    return Trace(devices, host)


# ------------------------------------------------------------------ intervals
def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What [lo, hi] leaves once the merged ``busy`` intervals are out."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


# -------------------------------------------------------------------- numbers
def window(trace: Trace) -> Optional[Interval]:
    """From the first device operation's start to the last one's end, over
    all chips: the traced slice as the device saw it. Host time before the
    first and after the last operation (starting and stopping the
    profiler) is outside."""
    starts = [op[1] for d in trace.devices for op in d.ops]
    ends = [op[2] for d in trace.devices for op in d.ops]
    if not starts:
        return None
    return min(starts), max(ends)


def busy_seconds(trace: Trace) -> Tuple[float, float]:
    """(seconds in which an operation ran, averaged over the chips;
    length of the window). (0, 0) when no operation ran on a device."""
    w = window(trace)
    if w is None:
        return 0.0, 0.0
    per_chip = [total(clip(union([(s, e) for _, s, e in d.ops]), *w))
                for d in trace.devices if d.ops]
    return sum(per_chip) / len(per_chip), w[1] - w[0]


def steps(trace: Trace) -> int:
    """Executions of the program that took most device time (the train
    step, the decode step), counted on the chip that ran it most often
    whole inside the window."""
    best = 0
    for d in trace.devices:
        by_name: Dict[str, List[float]] = {}
        for name, s, e in d.modules:
            by_name.setdefault(name, []).append(e - s)
        if by_name:
            top = max(by_name.values(), key=sum)
            best = max(best, len(top))
    return best


def short_name(op: str) -> str:
    """An operation's name in the trace is its whole HLO line (a thousand
    characters for a fusion). Keep the instruction's own name and the
    largest array among its results: ``%fusion.12 bf16[128,56,56,256]``."""
    head, sep, rest = op.partition(" = ")
    if not sep:
        return op[:120]
    if rest.startswith("("):             # a tuple of results: to its close
        depth = end = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        results = rest[:end + 1]
    else:
        results = rest.split(" ", 1)[0]
    best, size = "", -1
    for dtype, dims in _SHAPE.findall(results):
        count = 1
        for d in dims.split(","):
            count *= int(d) if d else 1
        if count > size:
            best, size = f"{dtype}[{dims}]", count
    return f"{head} {best}".strip()


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations with most device time, seconds averaged over
    the chips, under the (shortened) names the trace gives them."""
    chips = [d for d in trace.devices if d.ops]
    sums: Dict[str, float] = {}
    for d in chips:
        for name, s, e in d.ops:
            key = short_name(name)
            sums[key] = sums.get(key, 0.0) + (e - s)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / len(chips)] for name, secs in ranked]


def collective_seconds(trace: Trace) -> float:
    """Device time of operations that move data between chips, on the chip
    that spent most on them (the slowest chip sets the step)."""
    worst = 0.0
    for d in trace.devices:
        worst = max(worst, total(union(
            [(s, e) for name, s, e in d.ops if COLLECTIVE.match(name)])))
    return worst


def idle_by_host_span(trace: Trace, n: int = 10,
                      other: str = "host_unattributed") -> List[List]:
    """Idle time of the first chip inside the window, split by which of
    the benchmark's host spans (``bench.<what>``) covered it; where two
    cover the same instant the later-started (inner) one takes it. At most
    ``n`` entries, longest first."""
    w = window(trace)
    chips = [d for d in trace.devices if d.ops]
    if w is None or not chips:
        return []
    d = chips[0]
    idle = gaps(clip(union([(s, e) for _, s, e in d.ops]), *w), *w)
    sums: Dict[str, float] = {}
    spans = trace.host_spans
    for gs, ge in idle:
        covered: List[Interval] = []
        # inner spans first: later start wins
        for name, s, e in sorted(spans, key=lambda sp: -sp[1]):
            if e <= gs or s >= ge:
                continue
            piece = clip([(s, e)], gs, ge)
            free = total(piece) - total(clip(union(covered), *piece[0]))
            if free > 0:
                sums[name] = sums.get(name, 0.0) + free
                covered.append(piece[0])
        rest = (ge - gs) - total(union(covered))
        if rest > 0:
            sums[other] = sums.get(other, 0.0) + rest
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs] for name, secs in ranked]

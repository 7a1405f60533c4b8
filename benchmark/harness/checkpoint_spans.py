"""A save out of a profiler trace: the checkpoint's spans on the training
thread and on the writer thread, beside the device's idle gaps.

``CheckpointManager`` (``deeplearning4j_tpu/checkpoint/manager.py``) opens,
for every save, one tree on the thread that asked for it and one on the
thread that writes it, all spans of one save under one ``seq`` (and the
``step`` its checkpoint holds). Like every span of the program's tracer
they are ``jax.profiler.TraceAnnotation``s, so a ``--trace 1`` run finds
them on the host plane with nothing switched on::

    train.iteration > train.step_host > checkpoint.step_end   every turn
      checkpoint.save (seq, step, bytes, queued, sharded)     a save's stall
        checkpoint.snapshot (bytes)      self time: the device-to-host copy
          checkpoint.drain               the wait for the queued steps
        checkpoint.enqueue (queued)      the writer's lag, as the loop feels it
        checkpoint.barrier (what)        multi-process only
    checkpoint_writer.write (seq, step, bytes, waited_ms)     another thread
      checkpoint_writer.serialize        npz + zip, in memory
      checkpoint_writer.hash             sha256 of the payload
      checkpoint_writer.put              write, fsync, rename
      checkpoint_writer.journal          guard, retention, manifest

``harness.program_spans`` reads ``train.`` / ``prefetch.`` / ``checkpoint.``
from every thread and tells threads apart by their line's NAME; the writer's
spans carry another prefix so that it never sees them (a seconds-long span
of another thread would take idle time from ``train.iteration`` there).
This module reads both prefixes, tells threads apart by their line's PLACE
in the plane (two Python threads are both named ``python``), takes the
thread of ``train.iteration`` (or, where no loop ran, of
``checkpoint.save``) for the training thread, and puts idle time down to
ITS spans only.

A save counts when its ``checkpoint.save`` span lies whole inside the
traced slice as the device saw it (``harness.trace.window``); a span that
the profiler's start or stop cut is not in the trace at all, and one that
ends after the device's last operation has an aftermath nobody saw. A
slice with no whole save reads as ``None``, never as 0, and so does the
trace of a program that has no such span (an older commit)."""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from harness import trace as tracing
from harness.program_spans import Span, nest

WRITER_PREFIX = "checkpoint_writer."
PREFIXES = ("train.", "checkpoint.", WRITER_PREFIX)
SAVE, SNAPSHOT, ENQUEUE = ("checkpoint.save", "checkpoint.snapshot",
                           "checkpoint.enqueue")
WRITE = "checkpoint_writer.write"
PHASES = ("checkpoint_writer.serialize", "checkpoint_writer.hash",
          "checkpoint_writer.put", "checkpoint_writer.journal")


def read(path: str) -> List[Span]:
    """The fit loops' and the checkpoint's spans of one ``*.xplane.pb`` (or
    of the directory that holds one), nested, each thread under a name of
    its own (``<line name>#<place of the line>``)."""
    import jax

    if os.path.isdir(path):
        path = tracing.find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    spans: List[Span] = []
    for plane in data.planes:
        if plane.name != tracing.HOST_PLANE:
            continue
        for at, line in enumerate(plane.lines):
            for ev in line.events:
                if not ev.name.startswith(PREFIXES):
                    continue
                start = float(ev.start_ns) * 1e-9
                spans.append(Span(ev.name, start,
                                  start + float(ev.duration_ns) * 1e-9,
                                  f"{line.name}#{at}", dict(ev.stats)))
    return nest(spans)


def _mean_ms(spans: List[Span]) -> float:
    return 1000.0 * sum(s.seconds for s in spans) / len(spans)


class Saves:
    """The whole saves of one traced slice and what each number of the
    ``ckpt.*`` metrics is made from. ``spans`` are nested (``read``);
    ``trace`` holds the device's operations."""

    def __init__(self, spans: List[Span], trace: tracing.Trace):
        self.window = tracing.window(trace)
        loop = ([s.thread for s in spans if s.name == "train.iteration"]
                or [s.thread for s in spans if s.name == SAVE])
        # the training thread: the one that ran most turns of the loop
        self.thread = max(set(loop), key=loop.count) if loop else None
        self.saves = [s for s in spans if s.name == SAVE
                      and s.thread == self.thread and self._whole(s)]
        self.writes = [s for s in spans if s.name == WRITE
                       and self._whole(s)]
        # when the writer was at work: a write that an edge of the slice
        # cut is not in the trace, its children inside the slice are
        self.writing = tracing.union([(s.start, s.end) for s in spans
                                      if s.name.startswith(WRITER_PREFIX)])
        chips = [d for d in trace.devices if d.ops]
        self.idle: List[tracing.Interval] = []
        if self.window is not None and chips:
            busy = tracing.clip(tracing.union(
                [(s, e) for _, s, e in chips[0].ops]), *self.window)
            self.idle = tracing.gaps(busy, *self.window)

    def _whole(self, s: Span) -> bool:
        return (self.window is not None and self.window[0] <= s.start
                and s.end <= self.window[1])

    def children(self, name: str) -> List[Span]:
        return [c for s in self.saves for c in s.children if c.name == name]

    # ----------------------------------------------------------- the metrics
    def stall_ms(self) -> Optional[float]:
        return _mean_ms(self.saves) if self.saves else None

    def _ms_per_save(self, seconds) -> Optional[float]:
        """``seconds`` (of spans that belong to the whole saves), summed,
        as ms a save."""
        return 1000.0 * sum(seconds) / len(self.saves) if self.saves else None

    def enqueue_wait_ms(self) -> Optional[float]:
        return self._ms_per_save(c.seconds for c in self.children(ENQUEUE))

    def write_ms(self) -> Optional[float]:
        return _mean_ms(self.writes) if self.writes else None

    def write_phases_ms(self) -> Dict[str, float]:
        """Mean ms a whole write spent in each of its children."""
        return {name: 1000.0 * sum(c.seconds for w in self.writes
                                   for c in w.children if c.name == name)
                / len(self.writes) for name in PHASES} if self.writes else {}

    def snapshot_gb_per_s(self) -> Optional[float]:
        """Bytes copied over the snapshots' SELF time: the copy without
        the drain."""
        snaps = self.children(SNAPSHOT)
        secs = sum(s.self_seconds() for s in snaps)
        nbytes = sum(int(s.stats.get("bytes", 0)) for s in snaps)
        if not nbytes or secs <= 0:
            return None
        return nbytes / secs / 1e9

    def copy_ms(self) -> Optional[float]:
        """Mean ms a save spent in its snapshots' self time."""
        return self._ms_per_save(s.self_seconds()
                                 for s in self.children(SNAPSHOT))

    def drain_ms(self) -> Optional[float]:
        """Mean ms a save waited for the steps the host had queued."""
        return self._ms_per_save(s.child_seconds("checkpoint.drain")
                                 for s in self.children(SNAPSHOT))

    def device_idle(self) -> Optional[Dict[str, float]]:
        """Seconds of the first chip's idle gaps, by what they are put down
        to. A gap that overlaps a whole save goes to that save WHOLE, from
        the device's last operation to its next (the refill after the copy
        is the save's doing), split into the part before the span, under
        it and after it. Of the other gaps, what lies under an open writer
        span (the writer holding the interpreter) is ``writer``, the rest
        ``other``: the idle time a run without saves has too."""
        if not self.saves:
            return None
        out = {"before": 0.0, "under": 0.0, "after": 0.0, "writer": 0.0,
               "other": 0.0}
        for gs, ge in self.idle:
            save = next((s for s in self.saves
                         if s.start < ge and gs < s.end), None)
            if save is None:
                under = tracing.total(tracing.clip(self.writing, gs, ge))
                out["writer"] += under
                out["other"] += (ge - gs) - under
                continue
            out["before"] += max(0.0, min(ge, save.start) - gs)
            out["under"] += min(ge, save.end) - max(gs, save.start)
            out["after"] += max(0.0, ge - max(gs, save.end))
        return out

    def device_idle_ms(self) -> Optional[float]:
        idle = self.device_idle()
        return idle and self._ms_per_save(
            idle[part] for part in ("before", "under", "after"))

    def lines(self) -> List[str]:
        """What the readers print on earlier lines of a traced run."""
        if not self.saves:
            return [f"ckpt: no whole {SAVE} span in the slice "
                    f"({len(self.writes)} whole writes)"]
        idle, n = self.device_idle(), len(self.saves)
        window_s = self.window[1] - self.window[0]
        mine = idle["before"] + idle["under"] + idle["after"]
        rest = idle["writer"] + idle["other"]
        out = [
            f"ckpt: {n} whole save(s) in a slice of {window_s:.3f} s, seq "
            f"{[s.stats.get('seq') for s in self.saves]}, step "
            f"{[s.stats.get('step') for s in self.saves]}, queued "
            f"{[s.stats.get('queued') for s in self.saves]}",
            f"ckpt: a save's stall {self.stall_ms():.3f} ms = drain "
            f"{self.drain_ms():.3f} + copy {self.copy_ms():.3f} + enqueue "
            f"{self.enqueue_wait_ms():.3f} + the rest",
            f"ckpt: device idle a save {1000.0 * mine / n:.3f} ms = before "
            f"the span {1000.0 * idle['before'] / n:.3f} + under it "
            f"{1000.0 * idle['under'] / n:.3f} + after it "
            f"{1000.0 * idle['after'] / n:.3f}",
            f"ckpt: idle in the slice {1000.0 * (mine + rest):.3f} ms = the "
            f"saves' {1000.0 * mine:.3f} + under an open writer span and no "
            f"save {1000.0 * idle['writer']:.3f} + the remainder "
            f"{1000.0 * idle['other']:.3f} "
            f"({100.0 * idle['other'] / window_s:.3f}% of the slice)"]
        if self.writes:
            phases = ", ".join(f"{k.rpartition('.')[2]} {v:.3f}"
                               for k, v in self.write_phases_ms().items())
            waited = [w.stats.get("waited_ms") for w in self.writes]
            out.append(f"ckpt: {len(self.writes)} whole write(s), "
                       f"{self.write_ms():.3f} ms each: {phases}; waited in "
                       f"the queue (ms) {waited}")
        return out


def of(ctx: dict) -> Saves:
    """The traced run's saves, read once a run (kept on ``ctx``) from
    ``<root>/.bench_trace/<cell>/``, as ``run.py`` names the directory;
    the first reader to ask prints ``lines()``."""
    if "checkpoint_saves" not in ctx:
        cell = ctx["cell"]
        spans = read(os.path.join(cell.root, ".bench_trace", cell.name))
        ctx["checkpoint_saves"] = Saves(spans, ctx["trace"])
        for line in ctx["checkpoint_saves"].lines():
            print(line, flush=True)
    return ctx["checkpoint_saves"]

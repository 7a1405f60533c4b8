"""``fit_iterator``'s training with an asynchronous checkpoint every N steps:
the same set-up, pool, first steps, fit call and reference check (imported,
not copied), and on top of them

* a ``CheckpointManager`` over a fresh temporary directory outside the
  checkout (local storage, fsync and rename: the default backend), handed
  to the same ``fit`` call; set-up ends with one ``save(wait=True)``, which
  warms the writer thread and the directory and puts the step trigger's
  watermark on the window's first step, so the window's first save falls on
  its step N;
* a window that ENDS ON A SAVE: the feed hands out batches until the
  deadline and then on to the next multiple of N, so every window holds
  k x N steps and exactly k saves, and the count of saves cannot differ
  from run to run. The clock stops as in the plain cell, after
  ``block_until_ready`` of the parameters; ``flush()`` runs after it;
* the guarantee, as part of ``correct``: every save requested in the
  window is committed, the newest journal entry holds the window's last
  step, its payload's sha256 verifies on read-back, and the parameters
  restored from it are bitwise the live ones. A save that was requested
  and is not durable counts in ``failed``.

Traffic parameters beside ``fit_iterator``'s: ``save_every_n_steps``,
``queue_depth``, ``keep_last``."""

from __future__ import annotations

import atexit
import hashlib
import math
import os
import shutil
import tempfile

from harness import feed, loader

base = loader.import_file(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "fit_iterator.py"), "driver")


class CadenceFeed:
    """The pool's batches again and again until ``deadline``, and then on
    to the next multiple of ``every`` handed out: the window's end."""

    def __init__(self, pool, start: int, deadline: float, every: int):
        self._pool, self._at = pool, start
        self._deadline, self._every = deadline, every
        self.handed_out = 0

    def __iter__(self):
        return self

    def __next__(self):
        if (self.handed_out % self._every == 0
                and feed.clock() >= self._deadline):
            raise StopIteration
        item = self._pool[(self._at + self.handed_out) % len(self._pool)]
        self.handed_out += 1
        return item


class _Saving:
    """The trainer the fit call goes to, with the manager handed to it."""

    def __init__(self, trainer, manager):
        self._trainer, self._manager = trainer, manager

    def fit(self, stream):
        return self._trainer.fit(stream, checkpoint_manager=self._manager)


def _filesystem(path: str) -> str:
    """The mount that holds ``path``, as /proc/mounts names it: what a
    save's ``put`` is written to."""
    best = ("", "unknown")
    try:
        with open("/proc/mounts", encoding="utf-8") as f:
            for line in f:
                _, mount, kind = line.split()[:3]
                if (os.path.commonpath([path, mount]) == mount
                        and len(mount) > len(best[0])):
                    best = (mount, kind)
    except (OSError, ValueError):
        pass
    return f"{best[1]} at {best[0] or '?'}"


def setup(cell, devices, seed: int, say=print):
    import jax
    from deeplearning4j_tpu.checkpoint import CheckpointManager

    s = base.setup(cell, devices, seed, say)
    tr = s.tr
    s.directory = tempfile.mkdtemp(prefix="bench_ckpt_")
    atexit.register(shutil.rmtree, s.directory, ignore_errors=True)
    t = feed.clock()
    s.manager = CheckpointManager(
        directory=s.directory, save_every_n_steps=tr["save_every_n_steps"],
        async_write=True, queue_depth=tr["queue_depth"],
        keep_last=tr["keep_last"], save_updater=True)
    s.trainer = _Saving(s.trainer, s.manager)
    s.manager.save(s.net, wait=True)
    jax.block_until_ready(s.net.params)
    first, = s.manager.checkpoints()
    say(f"set-up's save: {first['size']} bytes, {feed.clock() - t:.2f} s "
        f"with its flush, into {s.directory} ({_filesystem(s.directory)})")
    return s


def run_window(s, seconds: float, trace_slice=None) -> dict:
    import jax
    from deeplearning4j_tpu.checkpoint.manager import CheckpointError

    cm, every = s.manager, s.tr["save_every_n_steps"]
    it0, asked0, done0 = s.net.iteration, cm.saves_requested, cm.saves_committed
    if trace_slice is not None:
        trace_slice.arm()
    t0 = feed.clock()
    source = CadenceFeed(s.pool, s.tr["check_steps"] + s.tr["warmup_steps"],
                         t0 + seconds, every)
    stream = s.fit(source, trace_slice.tick if trace_slice else None)
    jax.block_until_ready(s.net.params)
    elapsed = feed.clock() - t0
    if trace_slice is not None:
        trace_slice.finish()
    s.write_error = None
    try:
        cm.flush()
    except CheckpointError as e:
        s.write_error = e.__cause__ or e
    flush_s = feed.clock() - t0 - elapsed
    steps = s.net.iteration - it0
    asked, durable = cm.saves_requested - asked0, cm.saves_committed - done0
    s.saves = {"due": steps // every, "requested": asked,
               "committed": durable}
    last = float(s.net.score())
    compiles = s.net.compile_watch.compiles("train") - s.compiles_before
    items = steps * s.batch
    return {"end_to_end": {"train_items_per_s": items / elapsed},
            "items": items, "elapsed_s": elapsed, "attempted": steps,
            # a loss that is not finite poisons every later step; a save
            # asked for and not durable is an operation that failed
            "failed": (0 if math.isfinite(last) else steps)
            + max(asked, s.saves["due"]) - durable,
            "steps": steps, "input_wait_s": stream.wait_s,
            "last_loss": last, "compiles_in_window": compiles,
            "saves_requested": asked, "saves_committed": durable,
            "flush_s": flush_s}


def _guarantee(s, say) -> bool:
    """After ``flush()``: what the manager promised of the window's saves,
    as far as a run can show it. No step follows the last save, so the
    checkpoint holds the live parameters."""
    import jax
    import numpy as np

    cm = s.manager
    rows = []
    if s.write_error is not None:
        rows.append(("a background write failed", repr(s.write_error), False))
    rows.append(("saves committed = requested = steps / cadence",
                 s.saves, len(set(s.saves.values())) == 1))
    entries = cm.checkpoints()
    newest = entries[-1] if entries else {}
    rows.append(("newest journal entry's step is the window's last",
                 f"{newest.get('step')} / {s.net.iteration}",
                 newest.get("step") == s.net.iteration))
    path = os.path.join(s.directory, newest.get("file", ""))
    sha = None
    if os.path.isfile(path):
        with open(path, "rb") as f:
            sha = hashlib.sha256(f.read()).hexdigest()
    rows.append(("the payload's sha256 verifies on read-back",
                 str(sha)[:16], sha is not None
                 and sha == newest.get("sha256")))
    restored = cm.restore_latest()
    same = restored is not None
    if same:
        live = jax.tree_util.tree_leaves_with_path(
            (s.net.params, s.net.state, s.net.opt_state))
        back = jax.tree_util.tree_leaves_with_path(
            (restored.params, restored.state, restored.opt_state))
        same = ([p for p, _ in live] == [p for p, _ in back]
                and all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
                        for (_, a), (_, b) in zip(live, back)))
        same = same and restored.iteration == s.net.iteration
    rows.append(("the restored parameters, state and updater state are "
                 "bitwise the live ones", same, same))
    del restored
    for what, value, ok in rows:
        say(f"check guarantee: {what}: {value} ok={ok}")
    return all(ok for _, _, ok in rows)


def check(s, say=print):
    """The guarantee while the program's state is still there, then the
    plain cell's reference check on the first steps."""
    try:
        kept = _guarantee(s, say)
    finally:
        s.manager.close(wait=False)
        shutil.rmtree(s.directory, ignore_errors=True)
    s.manager = None
    ok, rows = base.check(s, say)
    return bool(ok and kept), rows


control = base.control

#!/usr/bin/env python
"""Run the framework linter (analysis/lint.py) over the repo.

Usage:
    python tools/run_lint.py [options] [path ...]

With no paths lints the tier-1 surface: ``deeplearning4j_tpu/`` and
``tools/``. Exits 1 on any violation (or, with
``--audit-waivers``, on any stale waiver) — the same contract
``tests/test_lint.py`` enforces in CI.

Options:
    --json            machine-readable output: one object with
                      ``violations`` (rule/file/line/message, plus
                      ``chain`` — the resolved call chain — for
                      interprocedural findings) and, with
                      ``--audit-waivers``, ``stale_waivers``.
    --rule DLT0XX     only report the named rule(s); repeatable, and a
                      comma-separated list works too. Filters REPORTING
                      only — the whole-repo call graph is still built so
                      interprocedural rules stay sound.
    --changed-only    only report findings in files changed vs git HEAD
                      (staged, unstaged, or untracked) for fast local
                      runs. The graph is still built over the full
                      targets, so a changed caller is checked against
                      unchanged callees and vice versa.
    --audit-waivers   additionally flag ``# lint: disable=...`` comments
                      that no longer suppress any finding (stale waivers
                      hide the next real regression).

Per-file rules DLT001-016 and the interprocedural families DLT017-019
(host-work-reachable-from-jit, cross-module lock analysis, thread
lifecycle) are documented in ``analysis/lint.py``; the README carries the
full rule table. Waive a finding inline with ``# lint: disable=DLT00X``
(or file-wide with ``# lint: disable-file=...``) and a short
justification.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.analysis.lint import (  # noqa: E402
    DEFAULT_TARGETS, audit_waivers, lint_paths)

_CHAIN_RE = re.compile(r"via ([^(]+?) \(\d+ call hop")
_RULE_RE = re.compile(r"^DLT\d{3}$")


def _chain_of(message: str):
    """The resolved call chain embedded in a DLT017 message, or None."""
    m = _CHAIN_RE.search(message)
    if not m:
        return None
    return [part.strip() for part in m.group(1).split("->")]


def _changed_files(repo_root: str):
    """Absolute paths changed vs HEAD (staged+unstaged) plus untracked."""
    out = set()
    for args in (["git", "diff", "--name-only", "HEAD"],
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            proc = subprocess.run(args, cwd=repo_root, capture_output=True,
                                  text=True, timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            return None
        if proc.returncode != 0:
            return None
        for line in proc.stdout.splitlines():
            line = line.strip()
            if line:
                out.add(os.path.abspath(os.path.join(repo_root, line)))
    return out


def main(argv) -> int:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    as_json = False
    changed_only = False
    audit = False
    rules = set()
    targets = []
    args = list(argv[1:])
    while args:
        a = args.pop(0)
        if a == "--json":
            as_json = True
        elif a == "--changed-only":
            changed_only = True
        elif a == "--audit-waivers":
            audit = True
        elif a == "--rule":
            if not args:
                print("--rule needs an argument (e.g. --rule DLT017)",
                      file=sys.stderr)
                return 2
            rules.update(r.strip() for r in args.pop(0).split(",") if r)
        elif a.startswith("--rule="):
            rules.update(r.strip() for r in a.split("=", 1)[1].split(",")
                         if r)
        elif a.startswith("-"):
            print(f"unknown option: {a}", file=sys.stderr)
            return 2
        else:
            targets.append(a)
    for r in rules:
        if not _RULE_RE.match(r):
            print(f"--rule expects DLT0XX ids, got: {r}", file=sys.stderr)
            return 2

    targets = targets or DEFAULT_TARGETS(repo_root)
    violations = lint_paths(targets)
    stale = audit_waivers(targets) if audit else []

    if changed_only:
        changed = _changed_files(repo_root)
        if changed is None:
            print("--changed-only: git unavailable, reporting everything",
                  file=sys.stderr)
        else:
            violations = [v for v in violations
                          if os.path.abspath(v.file) in changed]
            stale = [s for s in stale if os.path.abspath(s.file) in changed]
    if rules:
        violations = [v for v in violations if v.rule in rules]

    if as_json:
        payload = {
            "violations": [
                {"rule": v.rule, "file": v.file, "line": v.line,
                 "message": v.message, "chain": _chain_of(v.message)}
                for v in violations],
            "count": len(violations),
        }
        if audit:
            payload["stale_waivers"] = [
                {"file": s.file, "line": s.line, "rules": list(s.rules),
                 "scope": s.scope} for s in stale]
        print(json.dumps(payload, indent=2))
    else:
        for v in violations:
            print(v)
        for s in stale:
            print(s)
        n = len(violations)
        print(f"lint: {n} violation{'s' if n != 1 else ''} in "
              f"{len(targets)} target(s)"
              + (f", {len(stale)} stale waiver(s)" if audit else ""))
    return 1 if violations or stale else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

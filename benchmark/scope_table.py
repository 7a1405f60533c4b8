"""Device time of a traced run by the scope its operations came from: the
table behind the ``*.device_ms_per_step`` metrics, for reading by hand.

    python benchmark/scope_table.py .bench_trace/<cell> [depth]

Needs the ``step_hlo.txt`` a token-model driver leaves beside the trace
(the text of the executable that ran; the table's second line says how much
of the traced time is of instructions that the text names).
Every operation of the first chip is put under its layer's class
(``<Class>:<vertex>`` in its ``op_name``, all vertices of a class together,
forward and backward apart) and the first ``depth`` - 1 inner scopes
(``kda.scan``); the rest under its first path element. Loops
(``while``) contain their bodies' operations: both are listed, so the rows
do not add up to the busy time."""

import collections
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (_HERE, os.path.dirname(_HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_LAYER = re.compile(r"[A-Za-z]+:[\w.]+")
_SCOPE = re.compile(r"[a-z]+\.[a-z_]+")


def key_of(op_name: str, depth: int) -> str:
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        found = _LAYER.search(part)
        if found or _SCOPE.fullmatch(part):
            inner = [q for q in parts[i + 1:] if _SCOPE.fullmatch(q)]
            way = "bwd " if "transpose(" in part else "fwd "
            head = found.group(0).split(":")[0] if found else part
            return way + "/".join([head] + inner[:depth - 1])
    return parts[1] if len(parts) > 1 else (parts[0] or "(no op_name)")


def main(argv) -> int:
    from harness import hlo_ops, trace

    trace_dir = argv[1]
    depth = int(argv[2]) if len(argv) > 2 else 2
    with open(os.path.join(trace_dir, "step_hlo.txt"), encoding="utf-8") as f:
        text = f.read()
    by_name = hlo_ops.scopes(text)
    named = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", text, re.M))
    tr = trace.load(trace_dir)
    steps = trace.steps(tr) or 1
    ops = [d for d in tr.devices if d.ops][0].ops
    sums, counts = collections.Counter(), collections.Counter()
    for name, s, e in ops:
        key = key_of(by_name.get(hlo_ops.instruction_of(name), ""), depth)
        if hlo_ops.instruction_of(name).startswith("while"):
            key += " [while]"
        sums[key] += e - s
        counts[key] += 1
    busy, window = trace.busy_seconds(tr)
    print(f"steps {steps}, busy {1e3 * busy / steps:.2f} ms a step, "
          f"window {window:.3f} s")
    # is the text the traced executable's? Its instructions are the trace's
    total = sum(e - s for _, s, e in ops)
    known = sum(e - s for name, s, e in ops
                if hlo_ops.instruction_of(name) in named)
    print(f"{100 * known / total:.2f}% of the operations' time is of "
          f"instructions the text names ({len(named)} of them)")
    for key, secs in sums.most_common(40):
        print(f"{1e3 * secs / steps:10.3f} ms a step  {counts[key] // steps:6d}"
              f" ops a step  {key}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

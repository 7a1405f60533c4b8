"""Device time by the OWNER of each operation: the whole step, split with
nothing left over.

The program names an owner for every operation it emits
(``deeplearning4j_tpu/obs/owners.py``: a layer's class under
``<LayerClass>:<name>``, ``optim`` under ``optim.update``, ``grad.compress``,
``params.cast``, ``loss``), and ``owner_of(op_name)`` there is the one rule
that reads an ``op_name`` back; this module hard-codes no scope. What it adds
is the way from a trace event to an ``op_name`` where the compiler made the
instruction itself and gave it none:

* the event names an instruction of ``program_view["hlo_text"]``; its
  ``op_name`` decides where it has an owner;
* where it has none (a fusion XLA built across the optimizer's leaves, a
  ``conditional``, a ``while`` it rewrote), the owners of the instructions of
  the computations it calls (``calls=``, ``to_apply=``, ``body=``,
  ``condition=``, a ``conditional``'s branch computations, and theirs in
  turn): one owner among them is the instruction's, several make it
  ``mixed``; instructions there that have no owner themselves (parameters,
  the compiler's own converts and copies) have no say;
* an instruction that only MOVES a value (``MOVERS``: a ``copy``, the
  scheduler's prefetches ``copy-start`` / ``copy-done`` and ``async-start``
  / ``async-done`` of a slice, a ``bitcast``, a ``get-tuple-element``) and
  calls nothing that names an owner belongs to whoever made the value: it
  follows its operands, through further movers, to instructions that have
  an owner;
* with nothing to follow it is ``unowned``.

A ``while`` or a ``conditional`` and the operations of its body overlap in
time on the chip's ``XLA Ops`` line, and so do neighbours by a rounding.
Each instant belongs to the operation that started last of those running in
it (``self_intervals``: the innermost), and an owner's time is the UNION of
its operations' own intervals: the owners then add up to the busy time
exactly, and no row is counted inside another (adding a ``while`` row to
its body's rows was the mistake of PERF.md's editions of PRs 31-32). A driver
keeps the text; where there is none (the ResNet50 cells), or the program
has no ``owner_of`` (an older commit), the readers report nothing.

    python benchmark/harness/owners.py .bench_trace/<cell> [rows]

prints the table of a traced run by hand: owners, then the largest
operations nobody owns with the source line that emitted them."""

from __future__ import annotations

import collections
import os
import re
import sys
from typing import Callable, Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":          # run by hand: find the harness package
    _BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_BENCH, os.path.dirname(_BENCH)):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from harness import hlo_ops
from harness import trace as tracing

UNOWNED = "unowned"
MIXED = "mixed"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation"
                     r"|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
#: instructions that only move a value: where nothing else names an owner
#: they take their operands'
MOVERS = frozenset(("copy", "copy-start", "copy-done", "async-start",
                    "async-done", "bitcast", "get-tuple-element"))

Interval = Tuple[float, float]


def program_owner_of() -> Optional[Callable[[Optional[str]], Optional[str]]]:
    """The program's rule, or None where the program has none."""
    try:
        from deeplearning4j_tpu.obs.owners import owner_of
    except ImportError:
        return None
    return owner_of


Instruction = collections.namedtuple(
    "Instruction", "name op_name called line opcode operands")


def _closing(text: str, at: int) -> int:
    """Index of the parenthesis that closes the one at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i
    return len(text) - 1


def _opcode_and_operands(rest: str) -> Tuple[str, List[str]]:
    """Of ``<shape> <opcode>(<operands>), <attributes>``: a tuple's shape is
    in parentheses, a tile in a layout has some of its own."""
    if rest.startswith("("):
        rest = rest[_closing(rest, 0) + 1:].lstrip()
    else:
        rest = rest.partition(" ")[2]
    at = rest.find("(")
    if at < 0:
        return rest.strip(), []
    return rest[:at], _OPERAND.findall(rest[at:_closing(rest, at) + 1])


def parse(hlo_text: str) -> Tuple[Dict[str, Instruction], Dict[str, List[str]]]:
    """({instruction name: Instruction}, {computation name: its
    instructions' names}) of a compiled module's text."""
    instructions: Dict[str, Instruction] = {}
    computations: Dict[str, List[str]] = {}
    inside: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        if inside is None:
            header = _COMPUTATION.match(line)
            if header is not None:
                inside = computations.setdefault(header.group(1), [])
            continue
        if line.startswith("}"):
            inside = None
            continue
        named = _NAME.match(line)
        if named is None:
            continue
        op = _OP_NAME.search(line)
        called = _CALLED.findall(line)
        branches = _BRANCHES.search(line)
        if branches is not None:
            called += [c.strip().lstrip("%")
                       for c in branches.group(1).split(",") if c.strip()]
        name = named.group(1)
        instructions[name] = Instruction(
            name, op.group(1) if op else "", called, line,
            *_opcode_and_operands(line[named.end():].lstrip()))
        inside.append(name)
    return instructions, computations


def owners(hlo_text: str, owner_of) -> Dict[str, str]:
    """{instruction name: its owner, ``MIXED`` or ``UNOWNED``} for every
    instruction of the text (module docstring for the rule)."""
    instructions, computations = parse(hlo_text)
    found: Dict[str, frozenset] = {}
    moved: Dict[str, frozenset] = {}

    def named_by(ins: Instruction) -> frozenset:
        """The owner its ``op_name`` names, else the owners that the
        computations it calls name."""
        own = owner_of(ins.op_name)
        if own is not None:
            return frozenset((own,))
        return frozenset().union(*(owners_in(c) for c in ins.called))

    def owners_in(computation: str) -> frozenset:
        if computation not in found:
            found[computation] = frozenset()         # a cycle ends here
            found[computation] = frozenset().union(*(
                named_by(instructions[name])
                for name in computations.get(computation, ())))
        return found[computation]

    def makers_of(name: str) -> frozenset:
        """Owners of what made the value that instruction ``name`` holds:
        its own, or where it is a mover without one, its operands'."""
        if name not in moved:
            ins = instructions.get(name)
            named = named_by(ins) if ins is not None else frozenset()
            if not named and ins is not None and ins.opcode in MOVERS:
                moved[name] = frozenset()
                named = frozenset().union(*map(makers_of, ins.operands))
            moved[name] = named
        return moved[name]

    result = {}
    for name in instructions:
        named = makers_of(name)
        result[name] = (UNOWNED if not named else MIXED if len(named) > 1
                        else next(iter(named)))
    return result


def self_intervals(ops: Sequence[Tuple[str, float, float]]
                   ) -> List[Tuple[str, List[Interval]]]:
    """Each operation of one chip's line with its OWN part of the time: the
    instants in which it is, of the operations running, the one that started
    last (a ``while`` less its body's operations; where two neighbours
    overlap by a rounding, the later one). Every instant in which an
    operation ran is in exactly one of them. In the order of their starts."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    cuts = sorted({t for _, start, end in ops for t in (start, end)})
    own: List[List[Interval]] = [[] for _ in ops]
    running: List[int] = []          # by start; ended ones leave lazily
    upcoming = 0
    for at, until in zip(cuts, cuts[1:]):
        while upcoming < len(order) and ops[order[upcoming]][1] <= at:
            running.append(order[upcoming])
            upcoming += 1
        while running and ops[running[-1]][2] <= at:
            running.pop()
        if running:
            mine = own[running[-1]]
            if mine and mine[-1][1] == at:
                mine[-1] = (mine[-1][0], until)
            else:
                mine.append((at, until))
    return [(ops[i][0], own[i]) for i in order]


def _first_chip_ops(ctx):
    chips = [d for d in ctx["trace"].devices if d.ops]
    return chips[0].ops if chips else None


def _owner_by_instruction(ctx) -> Optional[Dict[str, str]]:
    view = hlo_ops.program_view(ctx)
    owner_of = program_owner_of()
    if not view or not view.get("hlo_text") or owner_of is None:
        return None
    by_instruction = view.get("_owners")
    if by_instruction is None:
        by_instruction = view["_owners"] = owners(view["hlo_text"], owner_of)
    return by_instruction


def seconds_by_owner(ctx) -> Optional[Dict[str, float]]:
    """{owner: seconds of the traced slice in which, on the first chip, the
    innermost running operation was the owner's}; an operation whose
    instruction the text does not name is ``UNOWNED``. None where the
    driver kept no HLO text, the program has no owner rule or the trace no
    operation."""
    by_instruction = _owner_by_instruction(ctx)
    ops = _first_chip_ops(ctx)
    if by_instruction is None or ops is None:
        return None
    mine: Dict[str, List[Interval]] = collections.defaultdict(list)
    for name, own in self_intervals(ops):
        mine[by_instruction.get(hlo_ops.instruction_of(name), UNOWNED)] += own
    return {owner: tracing.total(tracing.union(intervals))
            for owner, intervals in mine.items()}


def ms_per_step(ctx, *which: str) -> Optional[float]:
    """Device milliseconds a step of the owners ``which`` together."""
    by_owner = seconds_by_owner(ctx)
    steps = tracing.steps(ctx["trace"])
    if by_owner is None or not steps:
        return None
    return 1e3 * sum(by_owner.get(owner, 0.0) for owner in which) / steps


# ------------------------------------------------------------- read by hand
def _table(text: str, heading: str) -> Dict[int, str]:
    at = text.find("\n" + heading + "\n")
    rows: Dict[int, str] = {}
    if at < 0:
        return rows
    for line in text[at + len(heading) + 2:].splitlines():
        row = re.match(r"^(\d+) (.*)$", line)
        if row is None:
            break
        rows[int(row.group(1))] = row.group(2)
    return rows


def source_lines(hlo_text: str) -> Callable[[str], str]:
    """instruction line -> ``file:line(function)`` of the innermost frame
    that emitted it (the text's ``stack_frame_id`` tables), "" without."""
    files, functions = _table(hlo_text, "FileNames"), _table(hlo_text,
                                                             "FunctionNames")
    locations, frames = _table(hlo_text, "FileLocations"), _table(
        hlo_text, "StackFrames")

    def where(line: str) -> str:
        frame = _FRAME.search(line)
        at = frames.get(int(frame.group(1))) if frame else None
        loc = re.search(r"file_location_id=(\d+)", at or "")
        spot = re.search(r"file_name_id=(\d+) function_name_id=(\d+) "
                         r"line=(\d+)", locations.get(
                             int(loc.group(1)), "") if loc else "")
        if spot is None:
            return ""
        name = files.get(int(spot.group(1)), "?").strip('"')
        return (f"{os.path.basename(name)}:{spot.group(3)}"
                f"({functions.get(int(spot.group(2)), '?').strip(chr(34))})")

    return where


def main(argv) -> int:
    owner_of = program_owner_of()
    if owner_of is None:
        print("this checkout's program has no owner_of")
        return 1
    trace_dir = argv[1]
    rows = int(argv[2]) if len(argv) > 2 else 25
    with open(os.path.join(trace_dir, "step_hlo.txt"), encoding="utf-8") as f:
        text = f.read()
    instructions, _ = parse(text)
    by_instruction = owners(text, owner_of)
    tr = tracing.load(trace_dir)
    steps = tracing.steps(tr) or 1
    ops = _first_chip_ops({"trace": tr})
    if ops is None:
        print("this trace holds no device operation")
        return 1
    busy, window = tracing.busy_seconds(tr)
    print(f"steps {steps}, busy {1e3 * busy / steps:.3f} ms a step, "
          f"window {window:.3f} s")
    sums, counts = collections.Counter(), collections.Counter()
    loose, loose_n = collections.Counter(), collections.Counter()
    for name, own in self_intervals(ops):
        ins = hlo_ops.instruction_of(name)
        owner = by_instruction.get(ins, UNOWNED)
        sums[owner] += tracing.total(own)
        counts[owner] += 1
        if owner in (UNOWNED, MIXED):
            loose[ins] += tracing.total(own)
            loose_n[ins] += 1
    print(f"{1e3 * sum(sums.values()) / steps:10.3f} ms a step  all owners")
    for owner, secs in sums.most_common():
        print(f"{1e3 * secs / steps:10.3f} ms a step  {counts[owner] // steps:6d}"
              f" ops a step  {owner}")
    where = source_lines(text)
    kinds = collections.Counter()
    for ins, secs in loose.items():
        kinds[re.sub(r"[.\d]+$", "", ins)] += secs
    print("--- nobody's, by kind of instruction (ms a step)")
    for kind, secs in kinds.most_common(rows):
        print(f"{1e3 * secs / steps:10.3f}  {kind}")
    print("--- nobody's, the largest (ms a step, runs a step, owner, "
          "instruction, op_name, source)")
    for ins, secs in loose.most_common(rows):
        known = instructions.get(ins)
        print(f"{1e3 * secs / steps:10.3f} {loose_n[ins] / steps:6.1f}  "
              f"{by_instruction.get(ins, UNOWNED):8s} {ins:36s} "
              f"{(known.op_name if known else '(not in the text)')[-60:]:60s} "
              f"{where(known.line) if known else ''}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""Readings that a cell's correctness limits are set from, in ONE process
on the chip: the program's first steps against the reference over many
seeds (the sound runs' largest), and the control (the reference in the
precision below the configuration's, in the program's place) over a few
(the control's smallest). No measured window; the benchmark's own runs
never run the control.

    python benchmark/check_limits.py --workload <cell> --seeds 12 --control-seeds 3 [--first-seed N]

Prints one JSON object per seed and a summary: per number, the program's
largest gap and the control's smallest."""

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
for _p in (_HERE, _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_483_700)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from harness import device, loader
    from deeplearning4j_tpu.perf.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    cell = loader.resolve_cell(_ROOT, args.workload, rehearse=args.rehearse)
    devices = device.take_devices(cell.chips, rehearse=args.rehearse)
    quiet = lambda *a: None
    program, control = {}, {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        session = cell.driver.setup(cell, devices, seed, quiet)
        _, rows = cell.driver.check(session, quiet)
        line = {"seed": seed, "program": {r["what"]: r["value"] for r in rows}}
        for r in rows:
            program.setdefault(r["what"], []).append(r["value"])
        if i < args.control_seeds:
            ok, rows = cell.driver.control(session, quiet)
            line["control"] = {r["what"]: r["value"] for r in rows}
            line["control_correct"] = ok
            for r in rows:
                control.setdefault(r["what"], []).append(r["value"])
        print(json.dumps(line), flush=True)
    print(json.dumps({"summary": {
        what: {"program_largest": max(vals),
               "control_smallest": min(control.get(what, [float("nan")])),
               "limit_in_cell_file": _limit(cell, what)}
        for what, vals in program.items()}}), flush=True)
    return 0


def _limit(cell, what: str):
    limits = cell.cell["limits"]
    if what.startswith("loss.step"):
        return limits["loss"][int(what[len("loss.step"):]) - 1]
    key, _, kind = what.partition(".")
    return limits[f"{key}_{kind.split('_')[0]}"]


if __name__ == "__main__":
    sys.exit(main())

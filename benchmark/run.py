"""One run of one benchmark cell: a new process that loads, warms up,
measures for ``--seconds`` and prints ONE JSON object as its last line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (a profiler trace of a short steady slice of the window,
reduced by ``benchmark/harness/trace.py``). With no TPU, or fewer chips
than the cell asks for, it fails and prints no result. ``--rehearse`` runs
the same control flow at the tiny shapes each file names, on whatever
backend there is, and prints no metric at all. Everything but the result
goes on earlier lines."""

import time
_T0 = time.perf_counter()        # set-up is counted from process start

import argparse
import json
import os
import shutil
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
for _p in (_HERE, _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def say(*args) -> None:
    print(*args, flush=True)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, any backend, no metric printed")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from harness import device, feed, loader, peaks, trace

    manifest = loader.load_manifest(_ROOT)
    cell = loader.resolve_cell(_ROOT, args.workload, rehearse=args.rehearse)
    seconds = (args.seconds if args.seconds is not None
               else float(manifest["run_seconds"]))

    # the program's own placement rule: JAX_COMPILATION_CACHE_DIR if set,
    # else the fixed <checkout>/.jax_cache
    from deeplearning4j_tpu.perf.compile_cache import (cache_hits,
                                                       enable_compilation_cache)
    say(f"compile cache: {enable_compilation_cache()}")
    devices = device.take_devices(cell.chips, rehearse=args.rehearse)
    info = device.describe(devices)
    say(f"cell {cell.name}: config {cell.cell['config']}, traffic "
        f"{cell.cell['traffic']}, {cell.chips} chip(s), seed {args.seed}, "
        f"{seconds} s, device {info}")

    session = cell.driver.setup(cell, devices, args.seed, say)
    setup_s = time.perf_counter() - _T0
    say(f"set-up {setup_s:.3f} s, compile cache hits {cache_hits()}")

    trace_dir = trace_slice = None
    if args.trace:
        trace_dir = os.path.join(_ROOT, ".bench_trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        length = min(float(cell.traffic.get("trace_slice_s", 3.0)),
                     seconds / 3.0)
        trace_slice = feed.TraceSlice(trace_dir, seconds / 3.0, length)
    raw = cell.driver.run_window(session, seconds, trace_slice)
    # what the profiler's start and stop held the host for is not the
    # program's time: per-layer rates are taken net of it
    raw["profiler_s"] = trace_slice.overhead_s if trace_slice else 0.0
    info["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    say(f"memory on the first chip: {devices[0].memory_stats()}")
    say("window: " + json.dumps(raw))
    say(f"compiles inside the window: {raw['compiles_in_window']} (must be 0)")

    t_check = time.perf_counter()
    ok, _ = cell.driver.check(session, say)
    say(f"reference check took {time.perf_counter() - t_check:.3f} s "
        "(after the window, in no metric)")
    correct = bool(ok and raw["compiles_in_window"] == 0
                   and raw["failed"] == 0 and raw["attempted"] > 0)

    result = {"correct": correct, "attempted": raw["attempted"],
              "failed": raw["failed"], "metrics": {}, "device": info}
    if args.rehearse:
        result["rehearse"] = True
        info.pop("memory_peak_bytes")
    elif args.trace:
        tr = trace.load(trace_dir)
        busy_s, window_s = trace.busy_seconds(tr)
        if busy_s <= 0:
            raise RuntimeError("the trace shows no operation on the device")
        info["busy_s"], info["window_s"] = busy_s, window_s
        ctx = {"cell": cell, "raw": raw, "trace": tr, "chips": cell.chips,
               "peaks": peaks.peaks_for(info["kind"])}
        for m in cell.per_layer:
            value = cell.layer_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = {"device_ops": trace.top_ops(tr, 10),
                               "idle_gaps": trace.idle_by_host_span(tr, 10)}
    else:
        measured = {**raw["end_to_end"], "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": measured[m["name"]],
                                            "unit": m["unit"]}
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

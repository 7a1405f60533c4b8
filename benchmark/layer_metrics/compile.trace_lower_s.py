"""Seconds of set-up that the watched programs' compiles spent BEFORE the
backend: tracing the step function to a jaxpr and lowering it to an MLIR
module (Mosaic kernels are built here), summed over the programs the
network compiled (``train`` and whatever else went through its
``CompileWatch``), from the gauges ``jit_compile_trace_s_<program>`` and
``jit_compile_lower_s_<program>`` that ``obs.absorb_compile_watch``
publishes off the process's one ``jax.monitoring`` listener
(``perf/compile_watch.py``). Paid on every run, warm or cold: the
persistent cache is keyed on the lowered module. What compiled outside a
watched program (the seeded draw, the reference) is ``unwatched`` and left
out; a compile inside the window would count, and fails the run. None
where the program publishes no such gauge. SOURCE: program_counter."""

LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"

PHASES = ("trace_s", "lower_s")


def read(ctx, phases=PHASES):
    from deeplearning4j_tpu.obs import get_registry

    total = None
    for name, metric in get_registry().as_dict().items():
        for phase in phases:
            head = f"jit_compile_{phase}_"
            if name.startswith(head) and name != head + "unwatched":
                total = (total or 0.0) + metric["value"]
    return total

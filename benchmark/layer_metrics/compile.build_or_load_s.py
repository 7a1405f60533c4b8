"""Seconds of set-up between the watched programs' requests for an
executable and having it: the XLA compile where the persistent cache missed
(a cold run), the load of the stored executable where it hit (a warm run:
``jit_compile_cache_load_s_<program>`` is that part). Summed over the
programs the network compiled, from the gauges
``jit_compile_backend_s_<program>`` (``compile.trace_lower_s`` has the
rest of the story and the same source). None where the program publishes
no such gauge. SOURCE: program_counter."""

LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return ctx["cell"].layer_reader("compile.trace_lower_s")(
        ctx, phases=("backend_s",))

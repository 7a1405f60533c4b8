"""Share of the window the fit loop spent inside ``next()`` of the iterator
it was given (the program's device prefetcher over the benchmark's feed:
staging to the device included), or, where the driver hands arrays to the
call itself, in that hand-over. Timed by the driver on the host clock; the
time the profiler's own start and stop held the host is taken out."""

LAYER = "fit loops"
UNIT = "%"
MOVES = "train_items_per_s"


def read(ctx):
    raw = ctx["raw"]
    if "input_wait_s" not in raw:
        return None
    return 100.0 * raw["input_wait_s"] / (raw["elapsed_s"] - raw["profiler_s"])

"""Scheduler tick (serve/engine.py): window seconds over engine ticks,
from the benchmark's own host clock around `step()`."""


def read(ctx):
    if not ctx["ticks"]:
        return None
    return 1e3 * ctx["window_s"] / ctx["ticks"]

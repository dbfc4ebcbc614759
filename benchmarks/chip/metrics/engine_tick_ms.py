"""Scheduler tick (serve/engine.py): mean duration of the window's
`engine.step` spans, from the program's own host clock. Unlike
`tick_ms` it leaves out the time the engine sat empty."""
from harness import program as P


def read(ctx):
    spans = P.window(ctx)
    if spans is None:
        return None
    d = [s.end_ns - s.start_ns for s in spans if s.name == "engine.step"]
    return sum(d) / len(d) / 1e6

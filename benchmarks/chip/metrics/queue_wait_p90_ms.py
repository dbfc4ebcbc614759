"""Scheduler tick (serve/engine.py): 90th percentile of the
`engine.queue` spans (submission, or re-queueing after a preemption,
to admission) that end in a window tick, from the program's own host
clock."""
from harness import program as P
from harness.latency import percentile


def read(ctx):
    spans = P.window(ctx)
    waits = [s.end_ns - s.start_ns for s in spans or ()
             if s.name == "engine.queue"]
    if not waits:
        return None
    return percentile(waits, 90) / 1e6

"""Jitted steps (serve/serve_step.py, serve/sharded/serve_step.py): rows
with work over rows walked, summed over the window's step dispatches
(prefill, decode, and verify where speculation runs), in %. Each
`engine.*.dispatch` span counts its `rows` and the `batch` it ran at."""
from harness import program as P


def read(ctx):
    spans = P.window(ctx)
    calls = [s.attrs for s in spans or () if s.name.endswith(".dispatch")]
    if not calls:
        return None
    return 100.0 * sum(c["rows"] for c in calls) / sum(c["batch"]
                                                       for c in calls)

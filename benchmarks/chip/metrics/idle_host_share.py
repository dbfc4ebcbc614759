"""Device (TPU): device-idle time (from the union of operations on the
first device plane) that falls inside an `engine.step` span, put on
the trace's clock tick by tick, over the traced window, in %. The part
of `idle_share` the host's own work in a tick holds the chip back;
the rest is the engine sitting empty between ticks."""
from harness import program as P
from harness import trace as T


def read(ctx):
    spans = P.window(ctx)
    if spans is None:
        return None
    lo, hi = T.span_of(ctx["trace"])
    return 100.0 * P.idle_inside_ticks_ns(ctx, spans) / (hi - lo)

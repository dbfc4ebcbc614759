"""The serving program's own spans (`repro.serve.tracing`), reduced here.

The program only records raw spans in a ring; every number the readers
report is computed from them in this file, so the arithmetic of a
metric belongs to the benchmark, not to the program it measures.

Only the window's ticks count: the `engine.step` spans numbered
0 .. ticks-1 of the engine that served the window (the engine's first
tick is the window's first: warm-up calls the jitted steps directly,
and the drain past the close comes after the last). A tick missing
from the ring fails the run rather than reading part of the window.
The benchmark's files also run over checkouts of the program older than
the tracer: there every reader reads nothing, and none fails.

Device-clock alignment is per tick: tick k's spans are shifted by the
difference between the start of the k-th `bench.step` span in the
trace and the start of its `engine.step` span."""
from __future__ import annotations

import bisect
import importlib.util

from harness import trace as T

# waits that open before their tick and are no host work inside it
NOT_PHASES = ("engine.queue",)


def window(ctx) -> list | None:
    """The spans of the window's ticks, or None where there are none to
    read (a program without spans, or a window without ticks)."""
    if importlib.util.find_spec("repro.serve.tracing") is None:
        return None                 # a checkout older than the tracer
    from repro.serve import tracing
    n = ctx["ticks"]
    spans = tracing.spans()
    steps = [s for s in spans if s.name == "engine.step"]
    if not n or not steps:
        return None
    engine = steps[-1].engine
    out = [s for s in spans if s.engine == engine and s.tick is not None
           and s.tick < n]
    missing = set(range(n)) - {s.tick for s in out if s.name == "engine.step"}
    if missing:
        raise RuntimeError(f"{len(missing)} of the window's {n} ticks are "
                           f"missing from the program's span ring (first "
                           f"{min(missing)})")
    return out


def shifts(ctx, spans) -> dict[int, int]:
    """Per tick, the ns to add to a program span to put it on the
    trace's clock."""
    bench = sorted(s for name, s, _ in ctx["trace"]["host"]
                   if name == "bench.step")
    n = ctx["ticks"]
    if len(bench) != n:
        raise RuntimeError(f"the trace holds {len(bench)} bench.step spans "
                           f"for the window's {n} ticks")
    start = {s.tick: s.start_ns for s in spans if s.name == "engine.step"}
    return {k: bench[k] - start[k] for k in range(n)}


def idle(ctx) -> list[tuple[float, float]]:
    """Idle intervals of the first device plane inside the traced
    window (`span_of`), sorted."""
    tr = ctx["trace"]
    lo, hi = T.span_of(tr)
    busy = T.union((s, s + d) for _, s, d in
                   T.device_line(tr, T.planes(tr)[0],
                                 ctx["patterns"]["ops_line"]))
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [g for g in out if g[1] > g[0]]


def cover(intervals):
    """For sorted disjoint `intervals`, a function giving the length of
    [a, b) they cover."""
    ends = [e for _, e in intervals]

    def within(a: float, b: float) -> float:
        i = bisect.bisect_right(ends, a)
        tot = 0.0
        while i < len(intervals) and intervals[i][0] < b:
            s, e = intervals[i]
            tot += max(0.0, min(e, b) - max(s, a))
            i += 1
        return tot
    return within


def phases(spans) -> tuple[list, dict]:
    """The spans that are host work in a tick, and each one's children
    among them by parent id."""
    own = [s for s in spans if s.name not in NOT_PHASES]
    kids: dict[int, list] = {}
    for s in own:
        kids.setdefault(s.parent, []).append(s)
    return own, kids


def idle_inside_ticks_ns(ctx, spans) -> float:
    """Device-idle ns inside the window's `engine.step` spans, aligned
    onto the trace's clock."""
    within = cover(idle(ctx))
    sh = shifts(ctx, spans)
    return sum(within(s.start_ns + sh[s.tick], s.end_ns + sh[s.tick])
               for s in spans if s.name == "engine.step")


def idle_by_phase(ctx, spans) -> dict[str, float]:
    """Device-idle seconds inside the window's ticks, each put down to
    the innermost program span open over it (its self time: its
    interval less its children's); `outside engine.step` is the idle
    time of the window between ticks."""
    within = cover(idle(ctx))
    sh = shifts(ctx, spans)
    own, kids = phases(spans)
    out: dict[str, float] = {}
    for s in own:
        d = sh[s.tick]
        self_ns = within(s.start_ns + d, s.end_ns + d) - sum(
            within(c.start_ns + d, c.end_ns + d) for c in kids.get(s.id, ()))
        out[s.name] = out.get(s.name, 0.0) + self_ns / 1e9
    lo, hi = T.span_of(ctx["trace"])
    inside = sum(out.values())
    out["outside engine.step"] = within(lo, hi) / 1e9 - inside
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))

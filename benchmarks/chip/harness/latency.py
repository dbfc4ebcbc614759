"""End-to-end arithmetic over the window's host-clock stamps.

Every latency runs from the request's due time, not from when it was
handed to the engine, so a long tick that delays submissions counts.
A first token that comes after the window's close (the run waits for
it, harness/serve.py) counts at its real time; a request that never
got one counts at its age when the wait stopped, so a stall raises
the tail instead of leaving the sample. Gaps and rates read only the
tokens up to the close."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default rule)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_samples(due: dict, first: dict, close: float,
                 end: float | None = None) -> list[float]:
    """Seconds from due time to first token, for every request due at
    or before `close`; one with no first token by `end` (the end of the
    wait past the close; `close` if there was none) counts at its age
    then."""
    end = close if end is None else end
    return [(first[u] if u in first else end) - t
            for u, t in due.items() if t <= close]


def gap_samples(stamps: dict, finished: dict, close: float) -> list[float]:
    """Every gap between consecutive tokens up to `close` of every
    request, plus, for a request still generating at `close` (no finish
    time in `finished`, or a later one), the open gap since its last
    token."""
    out = []
    for u, ts in stamps.items():
        ts = [t for t in ts if t <= close]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
        if ts and finished.get(u, float("inf")) > close:
            out.append(close - ts[-1])
    return out


def window_rate(stamps: dict, t0: float, close: float) -> float:
    """Tokens emitted in [t0, close] over the window's seconds."""
    n = sum(1 for ts in stamps.values() for t in ts if t0 <= t <= close)
    return n / (close - t0)

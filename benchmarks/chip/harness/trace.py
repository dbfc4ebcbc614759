"""Profiler trace: capture, trim, and reduce to intervals and sums.

A trace is kept in a plain form of our own, so that the reduction can
be checked on a small recorded trace:

    {"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

`devices` holds the lines of each device plane that `patterns.json`
names; `host` holds the benchmark's own spans (names "bench.*")."""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile


class Capture:
    """Starts and stops the profiler at the window's edges, into a
    directory under TMPDIR that `load` reads and removes."""

    def __init__(self):
        self.dir = None

    def __call__(self, opening: bool) -> None:
        import jax
        if opening:
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.dir)
        else:
            jax.profiler.stop_trace()

    def load(self, patterns: dict) -> dict:
        from jax.profiler import ProfileData
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            return from_profile(ProfileData.from_file(files[0]), patterns)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def from_profile(pd, patterns: dict) -> dict:
    dev = re.compile(patterns["device_plane"])
    lines = set(patterns["device_lines"])
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if dev.search(plane.name):
            out["devices"][plane.name] = {
                ln.name: [[e.name, e.start_ns, e.duration_ns]
                          for e in ln.events]
                for ln in plane.lines if ln.name in lines}
        if plane.name.startswith("/host"):
            for ln in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in ln.events
                                   if e.name.startswith("bench."))
    if not out["devices"]:
        raise RuntimeError(f"no plane of the trace matches "
                           f"{patterns['device_plane']!r}")
    return out


# ----------------------------------------------------------- reduction

def union(intervals) -> list[tuple[float, float]]:
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events) -> float:
    return sum(e - s for s, e in union((s, s + d) for _, s, d in events))


def matching(events, pattern: str):
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def device_line(trace: dict, plane: str, line: str) -> list:
    return trace["devices"][plane].get(line, [])


def planes(trace: dict) -> list[str]:
    return sorted(trace["devices"])


def span_of(trace: dict) -> tuple[float, float]:
    """First start and last end of the benchmark's host spans: the
    traced window on the trace's clock."""
    h = trace["host"]
    if not h:
        raise RuntimeError("the trace holds none of the benchmark's spans")
    return min(s for _, s, _ in h), max(s + d for _, s, d in h)


def sum_matching(trace: dict, line: str, pattern: str) -> tuple[float, int]:
    """(total ns, count) of events matching `pattern` on `line` of every
    device plane."""
    tot, n = 0.0, 0
    for p in planes(trace):
        evs = matching(device_line(trace, p, line), pattern)
        tot += sum(d for _, _, d in evs)
        n += len(evs)
    return tot, n


def leaves(events) -> list:
    """The events that contain no other event of their line: a loop or
    a call whose body's operations are events of their own is left
    out, so that no time is counted twice."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(evs)
            if not (i + 1 < len(evs) and evs[i + 1][1] < e[1] + e[2])]


def op_name(event_name: str) -> str:
    """An operation's instruction name (`%fusion.12`) without its HLO."""
    return event_name.split(" = ", 1)[0]


def top_ops(trace: dict, line: str, k: int = 10) -> list:
    """The k operations that took most device time, summed over the
    device planes, in seconds."""
    acc: dict[str, float] = {}
    for p in planes(trace):
        for name, _, d in leaves(device_line(trace, p, line)):
            name = op_name(name)
            acc[name] = acc.get(name, 0.0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d / 1e9] for n, d in top]


def idle_gaps(trace: dict, line: str, k: int = 10) -> list:
    """The k longest idle gaps of the first device plane inside the
    traced window, each named by the innermost benchmark span open at
    its middle, in seconds."""
    lo, hi = span_of(trace)
    busy = union((s, s + d) for _, s, d in
                 device_line(trace, planes(trace)[0], line))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:k]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        open_ = [(d, n) for n, t, d in trace["host"] if t <= mid <= t + d]
        out.append([min(open_)[1] if open_ else "none", (e - s) / 1e9])
    return out


def busy_share(trace: dict, line: str) -> tuple[float, float]:
    """(mean busy seconds over the device planes, window seconds) within
    the traced window."""
    lo, hi = span_of(trace)
    tot = 0.0
    for p in planes(trace):
        clipped = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                   for n, s, d in device_line(trace, p, line)
                   if s + d > lo and s < hi]
        tot += busy_ns(clipped)
    return tot / len(planes(trace)) / 1e9, (hi - lo) / 1e9

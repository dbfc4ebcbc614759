"""Mesh (distribution/collectives.py): device milliseconds of the
collectives on chip 0's operations line, per step call of the window.
A synchronous collective counts whole; of an asynchronous pair only the
`-done` part counts, the wait that the chip could not hide behind other
work (patterns.json's `collective` matches those and no `-start`). On
one chip there is no exchange and nothing to read."""
from harness import trace as T


def read(ctx):
    calls = len(ctx["record"]["prefill"]) + len(ctx["record"]["decode"])
    if ctx["chips"] < 2 or not calls:
        return None
    pat, tr = ctx["patterns"], ctx["trace"]
    evs = T.matching(T.device_line(tr, T.planes(tr)[0], pat["ops_line"]),
                     pat["collective"])
    if not evs:
        raise RuntimeError(f"{calls} sharded step calls were made, but no "
                           f"event of the trace matches "
                           f"{pat['collective']!r}")
    return sum(d for _, _, d in evs) / 1e6 / calls

"""Helpers the per-layer metric readers share."""
from __future__ import annotations

from harness import trace as T


def step_ms(ctx, which: str, calls: int):
    """Device milliseconds per call of the jitted step program matched
    by patterns[which], on the first device plane; None when the window
    made no such call."""
    if not calls:
        return None
    pat = ctx["patterns"]
    tr = ctx["trace"]
    plane = T.planes(tr)[0]
    evs = T.matching(T.device_line(tr, plane, pat["modules_line"]), pat[which])
    if not evs:
        raise RuntimeError(f"{calls} {which} calls were made, but no event "
                           f"of the trace matches {pat[which]!r}")
    return sum(d for _, _, d in evs) / 1e6 / len(evs)


def kernel_roofline(ctx, which: str, flops: float, nbytes: float):
    """Share (%) of the kernel's roofline: the least time the chip could
    take for the algorithm's FLOPs and bytes, over the device time of
    the kernel's events summed over every device plane."""
    if flops <= 0 and nbytes <= 0:
        return None
    pat = ctx["patterns"]
    ns, n = T.sum_matching(ctx["trace"], pat["ops_line"], pat[which])
    if not n:
        raise RuntimeError(f"the window ran the {which}, but no event of "
                           f"the trace matches {pat[which]!r}")
    pk = ctx["peaks"]
    least = max(flops / pk["bf16_flops_per_s"],
                nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)

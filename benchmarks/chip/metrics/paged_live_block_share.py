"""Paged kernels (kernels/paged_attention, kernels/paged_prefill): page
blocks the kernels' walk computes over the blocks a walk of every row's
whole table spans, summed over the window's step dispatches (prefill,
decode, and verify where speculation runs), in %. Each paged
`engine.*.dispatch` span counts its `blocks` and `slots`; a program
whose spans lack them reads nothing."""
from harness import program as P


def read(ctx):
    spans = P.window(ctx)
    calls = [s.attrs for s in spans or ()
             if s.name.endswith(".dispatch") and "blocks" in s.attrs]
    if not calls:
        return None
    return 100.0 * sum(c["blocks"] for c in calls) / sum(c["slots"]
                                                         for c in calls)

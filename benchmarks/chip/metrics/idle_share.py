"""Device (TPU): 1 - (union of device operation intervals) / traced
window, averaged over the chips used, in %."""
from harness import trace as T


def read(ctx):
    busy, window = T.busy_share(ctx["trace"], ctx["patterns"]["ops_line"])
    return 100.0 * (1.0 - busy / window)

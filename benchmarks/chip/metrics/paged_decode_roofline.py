"""Paged kernels (kernels/paged_attention): share of the decode kernel's
roofline, max(FLOPs / peak, bytes / HBM bandwidth) over its device time.
FLOPs and bytes come from the positions each decoded row walked."""
from harness.readers import kernel_roofline


def read(ctx):
    pos = [p for call in ctx["record"]["decode"] for p in call]
    f, b = ctx["block"].decode_kernel_cost(ctx["D"], pos, ctx["kv_bytes"])
    return kernel_roofline(ctx, "paged_decode_kernel", f, b)

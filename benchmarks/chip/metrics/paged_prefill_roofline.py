"""Paged kernels (kernels/paged_prefill): share of the prefill kernel's
roofline, max(FLOPs / peak, bytes / HBM bandwidth) over its device time.
FLOPs and bytes come from each chunk row's start and length."""
from harness.readers import kernel_roofline


def read(ctx):
    rows = [r for call in ctx["record"]["prefill"] for r in call]
    f, b = ctx["block"].prefill_kernel_cost(ctx["D"], rows, ctx["kv_bytes"])
    return kernel_roofline(ctx, "paged_prefill_kernel", f, b)

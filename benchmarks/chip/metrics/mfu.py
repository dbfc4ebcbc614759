"""Model step (models/transformer.py): model FLOPs of the tokens the
traced window processed (prefill and decode, attention at each token's
context), over window seconds x chips x the bf16 peak, in %."""

def read(ctx):
    rec = ctx["record"]
    rows = [r for call in rec["prefill"] for r in call]
    pos = [p for call in rec["decode"] for p in call]
    if not rows and not pos:
        return None
    f = ctx["block"].model_flops(ctx["D"], rows, pos)
    return 100.0 * f / (ctx["window_s"] * ctx["chips"]
                        * ctx["peaks"]["bf16_flops_per_s"])

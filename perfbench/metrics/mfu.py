"""Whole step: the matmul FLOPs the algorithm requires in the traced
window (train forward and backward, Eq.-7 forwards over the real pool rows,
validation and test passes; padding and recomputation not counted) over
the window, the chips and the chip's bf16 peak."""


def read(ctx):
    lo, hi = ctx["window"]
    secs = (hi - lo) * 1e-9
    flops = ctx["work"]["flops"]
    if not flops or secs <= 0 or ctx["peak"] is None:
        return None
    return 100.0 * flops / (secs * ctx["chips"] * ctx["peak"]["flops_per_s"])

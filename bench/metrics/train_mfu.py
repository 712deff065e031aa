"""Whole training step's share of the bf16 peak of the cell's chips: the
FLOPs the window's tokens need (the family's count, bench/families/: 6N
with published heads plus causal attention within packed segments) over
the window and the peak of every chip the cell runs on."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["flops"] / ctx["window_s"] / peak

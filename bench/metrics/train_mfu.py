"""Whole training step's share of the chip's bf16 peak: the FLOPs the
window's tokens need (bench/work.py: 6N with published heads plus causal
attention within packed segments) over the window and the peak."""


def read(ctx):
    if ctx["kind"] != "train":
        return None
    return 100.0 * ctx["flops"] / ctx["window_s"] / ctx["peaks"]["bf16_flops_per_s"]

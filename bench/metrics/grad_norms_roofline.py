"""The block gradient-norm kernel's share of its roofline: one read of
every gradient element per step over peak HBM bandwidth, against the
device time of the ``block_grad_sq_norms`` kernel's events on every chip
of the cell (the trace's kernel time is per chip): norms that each chip
repeats count once."""

KERNELS = ("block_grad_sq_norms",)


def read(ctx):
    trace = ctx.get("trace")
    if ctx["kind"] != "train" or trace is None:
        return None
    t = trace.kernel_s(*KERNELS)
    if t <= 0:
        return None
    least = ctx["grad_norm_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (t * ctx["chips"])

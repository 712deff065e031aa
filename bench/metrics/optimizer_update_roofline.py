"""The masked AdamW update's share of its roofline: the least bytes of the
update of the blocks each step selected (read p, g, m, v; write p, m, v)
over peak HBM bandwidth, against the device time of the update kernel's
events (dense ``masked_adamw`` or ``banked_masked_adamw``) on every chip
of the cell (the trace's kernel time is per chip): an update that each
chip repeats counts once."""

KERNELS = ("masked_adamw",)


def read(ctx):
    trace = ctx.get("trace")
    if ctx["kind"] != "train" or trace is None:
        return None
    t = trace.kernel_s(*KERNELS)
    if t <= 0 or not ctx["optimizer_update_bytes"]:
        return None
    least = ctx["optimizer_update_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (t * ctx["chips"])

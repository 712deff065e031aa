"""Device time per window step of the flash attention kernels: every
event whose name holds ``flash_attention`` (the forward, its recomputation
in the backward of a rematerialised block, and the dq and dkv kernels).
A program without the kernel on its path has no such events and reads
nothing."""

KERNELS = ("flash_attention",)


def read(ctx):
    trace = ctx.get("trace")
    if ctx["kind"] != "train" or trace is None:
        return None
    t = trace.kernel_s(*KERNELS)
    if t <= 0:
        return None
    return 1000.0 * t / ctx["steps"]

"""Device time per window step of the collectives between chips: the
trace's all-reduce, reduce-scatter and all-gather events (their start and
done halves too), per chip. A cell on one chip has none and reads
nothing."""

KERNELS = ("all-reduce", "reduce-scatter", "all-gather")


def read(ctx):
    trace = ctx.get("trace")
    if ctx["kind"] != "train" or trace is None:
        return None
    t = trace.kernel_s(*KERNELS)
    if t <= 0:
        return None
    return 1000.0 * t / ctx["steps"]

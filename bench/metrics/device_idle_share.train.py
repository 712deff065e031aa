"""Share of the traced training window in which no operation ran on the
device (1 - union of operation intervals / window)."""


def read(ctx):
    if ctx["kind"] != "train" or "trace" not in ctx:
        return None
    return 100.0 * ctx["trace"].idle_share

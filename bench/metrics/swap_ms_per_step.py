"""Host time of the banked selection-change boundary per step in the
window: the program's SwapStats ``swap`` histogram (resolve and commit, or
the whole synchronous swap), its growth over the window per step."""


def read(ctx):
    swap = ctx.get("swap")
    if not swap or not swap["steps"]:
        return None
    return swap["swap_us"] / 1000.0 / swap["steps"]

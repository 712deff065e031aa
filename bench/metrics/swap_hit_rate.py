"""Share of the window's selection-change boundaries that the swap
planner's background dispatch had staged correctly (SwapStats
``predicted_hits`` over ``boundaries``, counted over the window)."""


def read(ctx):
    swap = ctx.get("swap")
    if not swap or not swap["boundaries"]:
        return None
    return 100.0 * swap["predicted_hits"] / swap["boundaries"]

"""Rate of the banked boundary's moment copies: the bytes the program's
``swap_admit`` (store to device) and ``swap_evict`` (device to store)
spans moved, over their time, from its trace of the window. Each span
holds the copies and the host store's reads or writes: ``swap_admit``
waits for every copy it starts, ``swap_evict`` starts once the evicted
rows' gathers have run. A plain rate in 1e9 bytes a second, not a share
of a peak."""

import program_trace


def read(ctx):
    tr = program_trace.last(ctx)
    if tr is None:
        return None
    spans = tr.spans("swap_admit") + tr.spans("swap_evict")
    moved = sum(sum(s["args"].get("bytes", ())) for s in spans)
    seconds = sum(s["dur"] for s in spans) / 1e6
    if not moved or seconds <= 0:
        return None
    return moved / seconds / 1e9

"""Programs the process compiled or loaded from the persistent cache
inside the traced window: the program's ``compile`` instants, one per
count of its ``jax/compiles`` counter. The window should hold none."""

import program_trace


def read(ctx):
    from repro import obs
    tr = program_trace.last(ctx)
    if tr is None or "compiles" not in obs.metrics.snapshot().get("jax", {}):
        return None
    return sum(1 for e in tr.events()
               if e["ph"] == "i" and e["name"] == "compile")

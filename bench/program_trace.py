"""The program's own trace of a traced window, for per-layer metrics that
read its spans, instants and counters.

``bench/harness.py:Trace`` turns ``repro.obs`` tracing on for the window
and off after it; ``repro.obs.last_tracer()`` then holds what the program
recorded. A program without that call (or an untraced run) gives None, so
the metrics that read it leave their line.
"""
from __future__ import annotations


def last(ctx):
    """The program's tracer of the window, or None."""
    if "trace" not in ctx:
        return None
    from repro import obs
    last_tracer = getattr(obs, "last_tracer", None)
    return last_tracer() if last_tracer is not None else None


def ms_per_step(ctx, name: str):
    """Host milliseconds per window step inside the spans ``name``, or
    None where the program recorded none."""
    tr = last(ctx)
    spans = tr.spans(name) if tr is not None else []
    if not spans or not ctx["steps"]:
        return None
    return sum(s["dur"] for s in spans) / 1e3 / ctx["steps"]

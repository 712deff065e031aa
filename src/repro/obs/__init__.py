"""Unified telemetry: process-global metrics registry + gated span tracing.

Two layers with different cost contracts:

* **Metrics registry** (``obs.metrics``) — ALWAYS ON. Counters, gauges, and
  bounded histograms are host-side Python with sub-microsecond record cost
  (the same order as the ad-hoc stat dicts they replaced). Subsystems
  register instruments under a subsystem label and everything exports as
  one JSON document via ``obs.snapshot()``.

* **Span tracing + selection telemetry** — OFF by default. ``obs.span``
  returns a shared no-op context manager until ``obs.enable()`` installs a
  ``Tracer``; instrumentation sites that would force a host sync (reading
  a device mask, per-request timestamps into trace tracks) guard on
  ``obs.enabled()``. Disabled mode therefore adds **no host syncs** —
  step trajectories are bit-identical with obs on or off (pinned in
  tests). The ``obs_overhead`` row of the CPU ``benchmarks/`` compares
  obs-on with obs-off steps on XLA:CPU, a regression guard of that
  harness and not a device measurement; PERF.md gives what tracing costs
  on the chip.

``disable`` keeps the tracer it removes (``last_tracer``), so a finished
run's spans can be read after tracing is off: ``Tracer.spans`` pairs them.
Once ``count_compiles`` has run (the trainer calls it), the registry also
holds ``jax/compiles``, one per program the process compiles or loads from
JAX's persistent cache, with a ``compile`` instant on the timeline when
tracing is on.

Typical wiring (see train/trainer.py, core/swap.py, serve/engine.py):

    hist = obs.metrics.histogram("step_time_us", subsystem="train")
    with obs.timed(hist, "phase_a"):      # histogram always, span if on
        ...
    with obs.span("decode_chunk"):         # no-op when disabled
        ...
    obs.metrics.register("stats", engine_stats_callable, subsystem="serve")

Launchers expose ``--trace PATH`` (Chrome trace-event JSON, loadable in
Perfetto / chrome://tracing) and ``--metrics-json PATH``
(``obs.snapshot()``, rendered by ``launch/inspect.py``).
"""
from __future__ import annotations

import time

from repro.obs.registry import (Counter, Gauge, Histogram,  # noqa: F401
                                MetricsRegistry)
from repro.obs.selection import SelectionTrace  # noqa: F401
from repro.obs.trace import (NOOP_SPAN, Tracer,  # noqa: F401
                             validate_trace, validate_trace_file)

# the process-global registry: always on, cheap, snapshot-exportable
metrics = MetricsRegistry()

_tracer: Tracer | None = None
_last: Tracer | None = None
_selection: SelectionTrace | None = None


def enabled() -> bool:
    """True when span tracing (and selection telemetry) is active."""
    return _tracer is not None


def enable(*, jax_profiler: bool = False, selection: bool = True,
           max_events: int = 1_000_000) -> Tracer:
    """Install a fresh ``Tracer`` (and, by default, a fresh
    ``SelectionTrace``). Idempotent in spirit: calling again replaces the
    active tracer so each run exports a self-contained trace."""
    global _tracer, _selection
    _tracer = Tracer(jax_profiler=jax_profiler, max_events=max_events)
    _selection = SelectionTrace() if selection else None
    return _tracer


def disable() -> None:
    global _tracer, _last, _selection
    if _tracer is not None:
        _last = _tracer
    _tracer = None
    _selection = None


def tracer() -> Tracer | None:
    return _tracer


def last_tracer() -> Tracer | None:
    """The tracer ``disable`` last removed: its events stay readable."""
    return _last


def selection_trace() -> SelectionTrace | None:
    return _selection


def span(name: str, args: dict | None = None):
    """Duration span context manager; the disabled path returns a shared
    no-op singleton (one global read + one ``is None`` check)."""
    tr = _tracer
    return NOOP_SPAN if tr is None else tr.span(name, args)


def instant(name: str, args: dict | None = None) -> None:
    tr = _tracer
    if tr is not None:
        tr.instant(name, args)


class _Timed:
    """Times its body with ``perf_counter`` and records the elapsed
    microseconds into ``hist`` — always; additionally emits a trace span
    when tracing is on. The one timing source of truth for phase timings
    (SwapStats et al. are views over these histograms)."""

    __slots__ = ("_hist", "_name", "_args", "_span", "_t0")

    def __init__(self, hist: Histogram, name: str, args: dict | None = None):
        self._hist = hist
        self._name = name
        self._args = args
        self._span = None

    def __enter__(self):
        tr = _tracer
        if tr is not None:
            self._span = tr.span(self._name, self._args)
            self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt_us = (time.perf_counter() - self._t0) * 1e6
        self._hist.record(dt_us)
        if self._span is not None:
            self._span.__exit__(*exc)
            self._span = None
        return False


def timed(hist: Histogram, name: str, args: dict | None = None) -> _Timed:
    return _Timed(hist, name, args)


def snapshot() -> dict:
    """One JSON-able document: every registered metric by subsystem, plus
    the selection telemetry under ``"selection"`` when enabled."""
    doc = metrics.snapshot()
    if _selection is not None and len(_selection):
        doc["selection"] = _selection.snapshot()
    return doc


def export_trace(path: str) -> None:
    if _tracer is None:
        raise RuntimeError("obs.export_trace: tracing is not enabled "
                           "(call obs.enable() before the run)")
    _tracer.export(path)


# JAX records this duration around every program it compiles or loads from
# the persistent cache, with the cache on or off (jax/_src/interpreters/
# pxla.py, ``dispatch.BACKEND_COMPILE_EVENT``): one event per program
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile(event: str, duration: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    metrics.counter("compiles", subsystem="jax").inc()
    tr = _tracer
    if tr is not None:
        tr.instant("compile", {"fun": kw.get("fun_name"), "s": duration})


_counting_compiles = False


def count_compiles() -> None:
    """Start ``jax/compiles`` (once per process; later calls do nothing).
    The trainer calls it, so importing ``repro.obs`` adds no JAX listener."""
    global _counting_compiles
    if _counting_compiles:
        return
    import jax
    metrics.counter("compiles", subsystem="jax")
    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    _counting_compiles = True

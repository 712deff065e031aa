"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* device busy time: the union of the intervals in which an operation ran
  on a chip, averaged over the chips; idle is the rest of the window;
* time per operation, summed by a stable name: the HLO instruction's name
  without its ``%`` and trailing ``.N`` instance number, so
  ``%fusion.12 = ...`` and ``%fusion.7 = ...`` count together and a Pallas
  kernel counts under the name it was given. Loops and calls (``while``,
  ``conditional``, ``call``) hold other operations, so they count towards
  busy time but not as operations of their own;
* idle gaps, each labelled by the innermost host span from ``labels`` that
  was open at the gap's midpoint (``"none"`` where none was).

The window is the host annotation named ``bench_window`` (the benchmark opens
one around its measured window); events are clipped to it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

_INSTANCE = re.compile(r"(\.\d+)+$")


@dataclass(frozen=True)
class Span:
    name: str
    start: float   # ns, on the trace's common clock
    end: float


@dataclass
class Summary:
    window_s: float
    busy_s: float                                   # mean over chips
    op_s: dict = field(default_factory=dict)        # stable name -> s/chip
    gaps: list = field(default_factory=list)        # (label, start, end)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, *substrings: str) -> float:
        """Device seconds (per chip) of operations whose stable name holds
        any of ``substrings``."""
        return sum(t for n, t in self.op_s.items()
                   if any(s in n for s in substrings))

    def top_ops(self, n: int = 10) -> list:
        return sorted(([k, v] for k, v in self.op_s.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_by_label(self, n: int = 10) -> list:
        tot = defaultdict(float)
        for label, s, e in self.gaps:
            tot[label] += (e - s) / 1e9
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]


_CONTAINERS = ("while", "conditional", "call")


def stable_name(name: str) -> str:
    return _INSTANCE.sub("", name.split(" = ", 1)[0].lstrip("%"))


def merge(spans) -> list:
    """Union of intervals as sorted, disjoint (start, end) pairs."""
    out = []
    for s, e in sorted((sp.start, sp.end) for sp in spans):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(spans, lo, hi):
    return [Span(sp.name, max(sp.start, lo), min(sp.end, hi)) for sp in spans
            if sp.end > lo and sp.start < hi]


def _label(mid: float, host: list) -> str:
    best = None
    for sp in host:
        if sp.start <= mid < sp.end:
            if best is None or sp.start >= best.start:
                best = sp
    return best.name if best is not None else "none"


def reduce(device_ops: list, host: list, window: tuple,
           labels=()) -> Summary:
    """``device_ops``: one list of operation spans per chip; ``host``: host
    annotation spans; ``window``: (start, end) in ns."""
    lo, hi = window
    if not hi > lo:
        raise ValueError(f"empty trace window {window}")
    host = [sp for sp in host if sp.name in labels]
    op_ns = defaultdict(float)
    busy = 0.0
    gaps = []
    for chip in device_ops:
        spans = _clip(chip, lo, hi)
        for sp in spans:
            name = stable_name(sp.name)
            if name not in _CONTAINERS:
                op_ns[name] += sp.end - sp.start
        merged = merge(spans)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_label((s + e) / 2, host), s, e))
    n = max(1, len(device_ops))
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
                   op_s={k: v / n / 1e9 for k, v in op_ns.items()},
                   gaps=gaps)


# ------------------------------------------------------------ xplane input


def _spans(line) -> list:
    return [Span(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str, ops_line: str = "XLA Ops",
         window_name: str = "bench_window") -> tuple:
    """(device_ops per chip, host spans, window) from an ``.xplane.pb``.
    Device planes are ``/device:TPU:<n>``; each contributes its
    ``ops_line`` line. Host spans are every event of the host planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == ops_line:
                    device.append(_spans(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_spans(line))
    marks = [sp for sp in host if sp.name == window_name]
    if not marks:
        raise ValueError(f"{path}: no host span named {window_name!r}")
    window = (min(sp.start for sp in marks), max(sp.end for sp in marks))
    return device, host, window

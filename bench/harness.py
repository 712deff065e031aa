"""What every cell shares: finding a cell's files by name, the device guard,
the compile cache, per-layer metric readers, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Its files are
found by name, so a later change adds a cell, a traffic mix, a model
configuration or a per-layer metric by adding a file:

    bench/workloads/<cell>.json     system settings and correctness limits,
                                    and the data-parallel degree
    bench/traffic/<traffic>.json    the mix or job, read by bench/traffic.py
    bench/configs/<config>.json     published sizes, as run; its
                                    ``"reference"`` names the family module
    bench/families/<family>.py      all that is architecture-specific (see
                                    bench/families/dense_decoder.py)
    bench/metrics/<metric>.py       ``read(ctx) -> float | None``
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
import os
import sys
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    root: Path
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)    # metric entries

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @functools.cached_property
    def family(self):
        """The configuration's family module, loaded on first use (after
        the compile cache is placed: it imports JAX)."""
        return load_module(self.root / self.config["reference"])


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _read_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"known: {[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    bench_dir = root / "bench"
    return Cell(
        name=name, root=root, config_name=entry["config"],
        traffic_name=entry["traffic"], chips=int(entry["chips"]),
        config=_read_json(root / conf["file"]),
        traffic=_read_json(bench_dir / "traffic" / f"{entry['traffic']}.json"),
        workload=_read_json(bench_dir / "workloads" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


def load_module(path: Path):
    """The Python module at ``path``, loaded under a name of its own."""
    path = Path(path)
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


def read_per_layer(cell: Cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], cell.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ device


def place_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout.
    Set before JAX is imported, so the program's own placement takes it."""
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return path


def require_chips(need: int) -> list:
    """The first ``need`` TPU devices; exits without a result otherwise.
    There is no CPU fallback in the measurement path."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU (JAX's first device is {devs[0].platform!r})")
    if len(devs) < need:
        sys.exit(f"bench: the cell needs {need} chips, found {len(devs)}")
    return devs[:need]


def device_report(devs) -> dict:
    """Platform and kind as JAX reports them, the count of the cell's
    chips, and the peak bytes in use on the fullest of them."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = _read_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"bench/peaks.json has no peaks for device kind "
                       f"{device_kind!r}")
    return table["devices"][device_kind]


class Trace:
    """A ``jax.profiler`` trace of the window (``--trace 1``), with the
    program's ``repro.obs`` spans bridged into it, written under the
    checkout's git-ignored ``.bench_out/`` and removed once reduced."""

    def __init__(self, cell: Cell, on: bool):
        self.on = on
        self.dir = cell.root / ".bench_out" / "trace" / cell.name

    def start(self) -> None:
        if not self.on:
            return
        import shutil

        import jax

        from repro import obs
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        obs.enable(jax_profiler=True, selection=False)

    def stop(self) -> None:
        if not self.on:
            return
        import jax

        from repro import obs
        obs.disable()
        jax.profiler.stop_trace()

    def reduce(self, labels):
        """The window's ``trace_reduce.Summary``."""
        import shutil

        import trace_reduce
        path = next(self.dir.rglob("*.xplane.pb"))
        dev_ops, host, win = trace_reduce.load(str(path))
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace_reduce.reduce(dev_ops, host, win, labels)


def traced_metrics(cell: Cell, ctx: dict, summary, device: dict):
    """Per-layer metrics and ``breakdown`` of a traced run; ``device``
    gains the busy and window seconds."""
    ctx["trace"] = summary
    device["busy_s"] = summary.busy_s
    device["window_s"] = summary.window_s
    return read_per_layer(cell, ctx), {"device_ops": summary.top_ops(),
                                       "idle_gaps": summary.idle_by_label()}


def end_to_end(cell: Cell, values: dict) -> dict:
    """The cell's end-to-end metrics from ``values`` by name."""
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


# ------------------------------------------------------------------ result


@dataclass
class Check:
    """One number compared with the reference, and its limit: the most it
    may read, or with ``at_least`` the least."""
    name: str
    value: float
    limit: float
    at_least: bool = False

    @property
    def ok(self) -> bool:
        if not math.isfinite(self.value):
            return False
        return (self.value >= self.limit if self.at_least
                else self.value <= self.limit)


def result_line(*, checks: list, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: dict | None = None) -> str:
    """The run's last line of standard output; the numbers compared come
    last, each beside its limit, and also go to standard error."""
    for c in checks:
        print(f"check {c.name} {c.value!r} "
              f"{'at least' if c.at_least else 'limit'} {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    line = {"correct": bool(checks) and all(c.ok for c in checks),
            "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value,
                               ("at_least" if c.at_least else "limit"):
                               c.limit} for c in checks}
    return json.dumps(line)

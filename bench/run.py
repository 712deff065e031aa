#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Run from the root of a checkout. The cell's files are found by its name in
BENCHMARK.json (see bench/harness.py). With ``--trace 0`` the last line of
standard output carries the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window. Every run
checks what the timed path produced against the plain reference; the
numbers compared and their limits are the last lines of standard error and
the last key of the result line. Without a TPU, or with fewer chips than
the cell asks for, it exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

import harness  # noqa: E402


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def main(argv=None, root: Path = harness.ROOT, devices=None) -> int:
    """``devices``: the devices to take the cell's chips from; by default
    the machine's TPU chips, or an exit without a result."""
    args = parser().parse_args(argv)
    if args.seed < 0:
        sys.exit("bench: --seed must be a non-negative integer")
    cell = harness.load_cell(args.workload, root)
    if devices is None:
        harness.place_compile_cache(root)
        devs = harness.require_chips(cell.chips)
    elif len(devices) < cell.chips:
        sys.exit(f"bench: the cell needs {cell.chips} chips, found "
                 f"{len(devices)}")
    else:
        devs = list(devices)[:cell.chips]
    if cell.kind != "train":
        sys.exit(f"bench: unknown traffic kind {cell.kind!r}")
    import train as driver
    line = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, devs)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

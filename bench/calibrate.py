#!/usr/bin/env python3
"""Readings that set a training cell's correctness limits, on the chip, in
one process (compiled programs are shared across seeds):

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 [--fault-seeds 1,2,3] [--restore-seeds 1,2,3] \
        [--exchange-seeds 1,2,3]

* program: the numbers a run compares, for the program as the cell runs
  it, from its first steps;
* control: the reference put in the program's place in fp8, the precision
  below the configuration's bfloat16, against the float32 reference;
* half_batch: the program with half of each batch's rows left out of the
  loss, the mean taken over the rest;
* no_exchange: on a cell that splits its batch over chips, the program
  with every row but the first chip's left out of the loss: the gradient
  one chip has without the exchange between chips;
* restore_lost: the program with the banked boundary's write-back of
  evicted blocks left out, so blocks that come back to the bank restart
  from zero moments.

A state left unchanged reads 1 on ``change_gap`` and ``grad_gap`` by
construction and is not run. Each reading is one JSON line; the limits in
``bench/workloads/<cell>.json`` are set from them (PERF.md gives both).
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

import harness  # noqa: E402


def rows_kept(step, parts: int):
    """``step`` with all but the first ``1 / parts`` of each batch's rows
    left out of the loss: half a batch at 2; at the data-parallel degree,
    the first chip's rows alone."""
    import numpy as np

    def wrapped(state, batch):
        mask = np.array(batch["loss_mask"])
        mask[mask.shape[0] // parts:] = 0
        return step(state, {**batch, "loss_mask": mask})
    wrapped.__dict__.update(getattr(step, "__dict__", {}))
    return wrapped


@contextlib.contextmanager
def restore_lost():
    """The banked boundary without its write-back of evicted blocks."""
    from repro.core import masked_adamw as ma
    keep = ma.writeback_evictions
    ma.writeback_evictions = lambda plans, banks, store: dict(store)
    try:
        yield
    finally:
        ma.writeback_evictions = keep


def reading(cell, seed: int, kind: str, devs) -> dict:
    import train
    if kind == "control":
        batches = train.first_batches(train.make_feed(cell, seed))
        ref = train.reference_readings(cell, seed, batches)
        got = train.reference_readings(cell, seed, batches, mode="fp8")
    else:
        fault = restore_lost() if kind == "restore_lost" else (
            contextlib.nullcontext())
        with fault:
            trainer, feed = train.build(cell, seed, devs)
            if kind == "half_batch":
                trainer.step_fn = rows_kept(trainer.step_fn, 2)
            elif kind == "no_exchange":
                trainer.step_fn = rows_kept(trainer.step_fn, cell.chips)
            got = train.setup_steps(trainer, feed, cell, seed)
            train.release(trainer)
        train.change_norms(got, cell, seed)
        ref = train.reference_readings(cell, seed, got["batches"])
    checks = train._compare(got, ref, cell.workload["limits"], cell)
    return {c.name: c.value for c in checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--restore-seeds", default="")
    ap.add_argument("--exchange-seeds", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.place_compile_cache()
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    plan = ([("program", s) for s in seeds(args.seeds)]
            + [("control", s) for s in seeds(args.control_seeds)]
            + [("half_batch", s) for s in seeds(args.fault_seeds)]
            + [("restore_lost", s) for s in seeds(args.restore_seeds)]
            + [("no_exchange", s) for s in seeds(args.exchange_seeds)])
    # the control is the reference alone, which runs on one chip
    devs = harness.require_chips(
        1 if all(kind == "control" for kind, _ in plan) else cell.chips)
    for kind, seed in plan:
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(sys.stderr):
            row = reading(cell, seed, kind, devs)
        row = {"cell": cell.name, "kind": kind, "seed": seed, **row,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

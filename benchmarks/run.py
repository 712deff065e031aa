"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Set REPRO_BENCH_STEPS to shrink the
training benches (CI); roofline rows appear when results/dryrun_*.json exist
(produced by repro.launch.dryrun). ``--json PATH`` additionally emits the
rows plus the structured optimizer-memory and serve tables as one
consolidated JSON for trajectory tracking across PRs — CI runs
``--only memory,serve --json BENCH_ci.json`` and diffs the result against
the committed ``BENCH_baseline.json`` via ``benchmarks/diff_baseline.py``.
"""
from __future__ import annotations

import argparse
import json
import os


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write rows + structured tables as JSON")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benches to run "
                         "(kernels,table1,fig1,fig3,fig4,memory,serve,"
                         "roofline); default: all")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()
    steps = int(os.environ.get("REPRO_BENCH_STEPS", "150"))
    rows = []

    from benchmarks import (bench_data, bench_fig1, bench_fig3, bench_fig4,
                            bench_kernels, bench_memory, bench_serve,
                            bench_table1, roofline_table)

    suite = (
        ("kernels", bench_kernels, {}),
        ("table1", bench_table1, {"steps": steps}),
        ("fig1", bench_fig1, {"steps": max(40, steps // 2)}),
        ("fig3", bench_fig3, {"steps": steps}),
        ("fig4", bench_fig4, {"steps": steps}),
        ("memory", bench_memory, {"steps": max(10, steps // 5)}),
        ("serve", bench_serve, {}),
        ("data", bench_data, {"steps": max(6, steps // 5)}),
        ("roofline", roofline_table, {}),
    )
    only = ({s.strip() for s in args.only.split(",") if s.strip()}
            if args.only else None)
    if only:
        unknown = only - {key for key, _, _ in suite}
        if unknown:
            raise SystemExit(f"--only: unknown bench keys {sorted(unknown)}; "
                             f"known: {[key for key, _, _ in suite]}")

    for key, mod, kwargs in suite:
        if only is not None and key not in only:
            continue
        try:
            rows.extend(mod.run(**kwargs))
        except Exception as e:  # noqa: BLE001
            rows.append((f"{mod.__name__}/FAILED", -1.0,
                         f"{type(e).__name__}:{e}"))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if args.json:
        payload = {
            "rows": [{"name": n, "us_per_call": us, "derived": d}
                     for n, us, d in rows],
            "memory_table": bench_memory.LAST_TABLE,
            "serve_table": bench_serve.LAST_TABLE,
            "data_table": bench_data.LAST_TABLE,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)


if __name__ == "__main__":
    main()

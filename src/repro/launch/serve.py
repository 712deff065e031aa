"""Serving launcher: continuous-batching engine over a (smoke or full) model.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --batch 4 --prompt-len 16 --new-tokens 32 --kv-layout paged

Reports compile time (warmup call) and steady-state tok/s separately — the
pre-warmup number was dominated by XLA compile and meaningless as a
throughput figure. After the timed pass the launcher prints the engine's
consolidated ``stats_snapshot()`` — engine counters, per-request latency
histograms (queue-wait / TTFT / TPOT / e2e), page-pool + scheduler +
prefix-cache + fn-cache state in one nested dict (keys documented in
serve/engine.py). ``--prefix-cache`` turns on the radix prefix cache (and
makes the demo batch share a prompt prefix so hits are observable);
``--preempt`` allows the engine to preempt-and-requeue residents when the
pool is exhausted. ``--trace out.json`` exports a Perfetto-loadable trace
with per-request ttft/e2e lanes; ``--metrics-json`` dumps the obs registry
snapshot.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--kv-layout", choices=["dense", "paged"],
                    default="dense")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size (0 = full dense capacity)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill width (0 = single-shot)")
    ap.add_argument("--prefill-rows", type=int, default=1,
                    help="rows per bucketed prefill batch")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the page pool "
                         "(requires --kv-layout paged)")
    ap.add_argument("--preempt", action="store_true",
                    help="preempt-and-requeue when the page pool is "
                         "exhausted (requires --kv-layout paged)")
    ap.add_argument("--admission", choices=["fcfs", "prefix_aware"],
                    default="fcfs",
                    help="admission order: prefix_aware admits queued "
                         "requests early when their cached prefix pages "
                         "sit at the LRU eviction frontier")
    ap.add_argument("--persist-prefix", action="store_true",
                    help="keep the radix tree in a PrefixStore across "
                         "engine instances (the second launcher pass then "
                         "prefills suffix-only)")
    ap.add_argument("--fn-cache-limit", type=int, default=0,
                    help="bound the compiled-fn LRU (0 = keep default)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="export a Chrome trace-event JSON (ui.perfetto.dev)"
                         " with admission/prefill/decode spans and "
                         "per-request ttft/e2e lanes")
    ap.add_argument("--metrics-json", default="",
                    help="write the obs registry snapshot to this path")
    args = ap.parse_args()

    from repro import obs
    from repro.launch.compile_cache import place_compile_cache

    place_compile_cache()
    obs_on = bool(args.trace or args.metrics_json)
    if obs_on:
        obs.enable(selection=False)

    from repro.configs import get_config, get_smoke_config
    from repro.models import registry
    from repro.serve.config import ServeConfig
    from repro.serve.engine import (ServeEngine, fn_cache_info,
                                    set_fn_cache_limit)
    from repro.serve.prefix_store import PrefixStore

    if args.fn_cache_limit:
        set_fn_cache_limit(args.fn_cache_limit)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = registry.get(cfg)
    params = model.init(jax.random.PRNGKey(args.seed), cfg)
    rng = jax.random.PRNGKey(args.seed + 1)
    batch = {"tokens": np.asarray(
        jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                           cfg.vocab_size), np.int32)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = np.asarray(jax.random.normal(
            rng, (args.batch, cfg.num_frontend_tokens, cfg.d_model)) * 0.02)
    if cfg.family == "encdec":
        batch["src_embeds"] = np.asarray(jax.random.normal(
            rng, (args.batch, args.prompt_len, cfg.d_model)) * 0.02)
    if args.prefix_cache:
        # shared-prefix traffic so radix hits are observable: every row
        # reuses row 0's first half (page-aligned for typical page sizes)
        half = args.prompt_len // 2
        toks = batch["tokens"].copy()
        toks[:, :half] = toks[0, :half]
        batch["tokens"] = toks

    prefix = cfg.num_frontend_tokens if cfg.family == "vlm" else 0
    max_len = args.prompt_len + prefix + args.new_tokens
    store = PrefixStore() if args.persist_prefix else None
    serve_cfg = ServeConfig(
        max_len=max_len, num_slots=args.batch,
        temperature=args.temperature, rng=rng,
        decode_chunk=args.decode_chunk,
        kv_layout=args.kv_layout, page_size=args.page_size,
        num_pages=args.num_pages or None,
        prefill_chunk=args.prefill_chunk,
        prefill_rows=args.prefill_rows,
        prefix_cache=args.prefix_cache, preempt=args.preempt,
        admission=args.admission, prefix_store=store)

    def one_pass(close=False):
        engine = ServeEngine(cfg, params, serve_cfg)
        out = engine.generate(batch, max_new_tokens=args.new_tokens)
        if close:
            # with --persist-prefix this hands the radix tree to the store,
            # so the next pass's engine adopts it warm
            engine.close()
        return out, engine

    # warmup: same shapes/max_len as the timed call, so every compile
    # (prefill, decode chunk, insert) lands here
    t0 = time.perf_counter()
    one_pass(close=True)
    t_compile = time.perf_counter() - t0
    warm = fn_cache_info()

    t0 = time.perf_counter()
    out, engine = one_pass()
    dt = time.perf_counter() - t0
    steady = fn_cache_info()
    tps = args.batch * args.new_tokens / dt
    print(f"compile+first-call: {t_compile:.2f}s")
    print(f"steady state: generated {out.shape} in {dt:.2f}s ({tps:.1f} tok/s)")
    if steady["misses"] > warm["misses"]:
        print(f"  WARNING: steady-state call added "
              f"{steady['misses'] - warm['misses']} fn-cache misses "
              f"(a closure was rebuilt instead of cached)")
    if args.prefix_cache:
        # second wave on the SAME engine: the first wave populated the
        # radix tree, so every re-sent prompt aliases its cached pages and
        # prefills only the copy-on-write tail token. The first warm-tree
        # wave compiles the cached-suffix closure; the timed one is steady.
        engine.generate(batch, max_new_tokens=args.new_tokens)
        t0 = time.perf_counter()
        engine.generate(batch, max_new_tokens=args.new_tokens)
        dt2 = time.perf_counter() - t0
        print(f"  2nd wave (warm radix tree): "
              f"{args.batch * args.new_tokens / dt2:.1f} tok/s "
              f"({dt / max(dt2, 1e-9):.2f}x 1st wave)")
    if store is not None:
        print(f"  prefix store: {store.stats['adoptions']} adoptions")
    # one consolidated dump replaces the old fn-cache / page-pool / prefix
    # printouts — key structure documented in serve/engine.py
    print("engine stats_snapshot:")
    print(json.dumps(engine.stats_snapshot(), indent=2))
    if args.trace:
        obs.export_trace(args.trace)
        print(f"trace written to {args.trace} (open in ui.perfetto.dev)")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(obs.snapshot(), f, indent=2)
        print(f"metrics snapshot written to {args.metrics_json}")
    if obs_on:
        obs.disable()
    print("first row:", out[0][:24])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

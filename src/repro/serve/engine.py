"""Continuous-batching serve engine: slot KV cache, paged pool, bucketed prefill.

``ServeEngine`` compiles prefill/decode ONCE per static configuration — the
jitted closures live in a bounded module-level LRU cache — so fresh engine
instances (and the legacy ``generate`` path) never pay compile time twice.
The engine owns a persistent slot-based KV cache with per-slot position and
on-device finished state: requests with different prompt lengths are
admitted into free slots as others finish (continuous batching), EOS
terminates a slot on-device, and decode runs as a jitted fixed-chunk
``lax.scan`` with a single host sync per chunk instead of per token.

Two KV layouts:

- ``kv_layout="dense"`` (default): every slot owns a ``max_len`` cache row.
- ``kv_layout="paged"``: K/V live in a shared page pool sized by
  ``num_pages`` and each slot maps positions through a page table
  (serve/pages.py + lm.init_paged_cache). Cache memory scales with live
  tokens instead of ``num_slots * max_len``; when the pool runs dry the
  engine admits what fits and leaves the rest queued (admission
  backpressure) instead of failing. Supported for plain GQA/MHA dense and
  moe stacks; a no-op for ssm (no length-indexed KV); other families raise.

Three optional layers ride on the paged pool:

- ``prefix_cache=True``: a host-side radix tree (serve/prefix_cache.py)
  over page-granular token prefixes. Admission looks up the longest cached
  prefix, aliases those pages read-only into the new slot's table
  (refcounted — see serve/pages.py), and prefills ONLY the uncached suffix
  (the bucketed prefill path gains a traced ``start`` offset). When the
  whole prompt is cached the last matched page is copied-on-write before
  the final-token recompute so a shared page is never written through.
  Completed requests insert their prompt pages back into the tree under an
  LRU cap with refcount-aware eviction.
- ``preempt=True``: when the pool is exhausted and the FCFS head cannot
  fit, the engine first evicts prefix-cache pages, then preempts the
  resident with the most remaining budget — its private pages free (shared
  prefix pages just decref), it requeues at the scheduler head carrying its
  already-generated tokens (original arrival preserved), and re-admits via
  the normal — prefix-accelerated, its own prompt+generated pages are
  inserted into the tree first — prefill path. Re-admission is token-exact
  vs the never-preempted run: greedy decoding is deterministic in the
  context, and sampled decoding saves the slot's key at preemption so the
  per-request key stream continues bit-exactly.
- ``on_complete=...``: finished sequences hand off to a background
  detokenize/stream-out worker (serve/streamout.py) so ``step()`` never
  blocks on host-side decode.

Prefill is prompt-length-BUCKETED for dense/moe: prompts are right-padded
to the smallest bucket in {min_bucket, 2*min_bucket, ..., max_len} and
admission groups are padded to ``num_slots`` rows, so the prefill compile
count is bounded by ``len(prefill_buckets)`` — not by the number of
distinct prompt lengths (lm.prefill gathers each row's logits at its true
``lengths - 1``; causal attention makes the pad tokens inert). Long
prefills can additionally be CHUNKED (``prefill_chunk=N``): the bucket is
prefilled N tokens per engine step, interleaved between decode chunks, so
a long prompt never stalls resident decodes for its whole prefill.
Families without a length-indexed KV cache (ssm/hybrid/vlm/encdec, and
EP-MoE whose routing sees pad rows) keep the legacy exact-length
signature-grouped admission path.

Serving policy is carried by one validated ``ServeConfig``
(serve/config.py): ``ServeEngine(cfg, params, ServeConfig(...))`` is the
surface; the historical ``ServeEngine(cfg, params, **kwargs)`` spelling
still works for one release behind a ``DeprecationWarning``. Every
delivery path (``step``/``run``/``generate``/``on_complete``) hands back
``Completion`` records (serve/results.py). Admission order is pluggable
through ``scheduler.AdmissionPolicy`` — ``admission="prefix_aware"``
schedules around the radix tree's LRU eviction frontier — and a
server-level ``PrefixStore`` (serve/prefix_store.py) carries the radix
tree + page pool across engine instances (``close()`` hands them over; the
next engine over the same params adopts them warm).

Observability: the engine records into the process-global obs registry
(``repro.obs``) — per-request queue-wait/TTFT/time-per-output-token/e2e
latency histograms (wall-clock, stamped at submit/admission/completion),
page-pool and fn-cache gauges — and, when ``obs.enable()`` tracing is on,
emits admission/prefill/decode spans plus one retroactive ``e2e``+``ttft``
span lane per request in the exported Perfetto trace.
``stats_snapshot()`` consolidates every stat surface into one nested dict:

- ``engine`` — the per-engine counters (``self.stats``): ``decode_chunks``
  (jitted chunk dispatches), ``decode_steps`` (ACTUAL emitted decode
  positions, including a terminal EOS — not ``chunks * decode_chunk``),
  ``prefills``/``prefill_chunks``/``prefill_tokens``, ``admitted``/
  ``completed``, ``backpressure``/``preempted``, ``prefix_hits``/
  ``prefix_pages_shared``.
- ``latency_us`` — ``queue_wait``/``ttft``/``tpot``/``e2e`` histogram
  summaries (count, mean, min/max, p50/p95/p99), microseconds.
- ``pages`` — ``PageAllocator.stats()`` (num/live/free/peak pages,
  utilization); None for the dense layout.
- ``scheduler`` — ``pending`` queue depth + the admission policy's
  counters (``bypass_admissions``/``bypassed``/``aging_forced`` for
  ``prefix_aware``; None for plain FCFS).
- ``prefix_cache`` — radix-tree ``pages``/``capacity_pages``; None when
  the prefix cache is off.
- ``stream_out`` — background detokenize queue ``pending``; None when no
  stream-out worker runs.
- ``fn_cache`` — the process-wide compiled-fn cache counters
  (``fn_cache_info()``).

Used by the examples, the synthetic-math evaluator (the GSM8K-protocol
proxy: zero-shot greedy decoding, temperature 0), the serve launcher, and
``benchmarks/bench_serve.py``. The pre-engine static-batch loop lives in
``serve/_oracle.py`` (the parity oracle); ``generate`` keeps its original
signature and reproduces the legacy outputs exactly.
"""
from __future__ import annotations

import time
import warnings
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.models import registry
from repro.serve.config import ServeConfig
from repro.serve.pages import PageAllocator, PoolExhausted, pages_for
from repro.serve.prefix_cache import PrefixCache
from repro.serve.results import Completion, RunResult, TokenBatch
from repro.serve.scheduler import (FCFSScheduler, PrefixAwareAdmission,
                                   Request)
from repro.serve.streamout import StreamOut

# ------------------------------------------------------ compiled-fn caching
#
# jax.jit caches on function identity: rebuilding a closure per call (the
# pre-engine behavior) recompiles every time. All jitted serving closures
# are built once per static key and reused process-wide. The cache is a
# bounded LRU: a long-lived server that cycles through many configurations
# (or bucket sizes) evicts the coldest closure instead of growing without
# bound. The default limit comfortably covers one engine's full key set
# (buckets + chunk shapes + decode); size it up for multi-model servers.

_FN_CACHE: OrderedDict = OrderedDict()
_FN_LIMIT = 64
_FN_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def set_fn_cache_limit(limit: int) -> None:
    """Bound the compiled-fn LRU to ``limit`` entries (evicts immediately
    if already over)."""
    global _FN_LIMIT
    if limit < 1:
        raise ValueError(f"fn-cache limit must be >= 1, got {limit}")
    _FN_LIMIT = int(limit)
    while len(_FN_CACHE) > _FN_LIMIT:
        _FN_CACHE.popitem(last=False)
        _FN_STATS["evictions"] += 1


def _cached_fn(key, build):
    fn = _FN_CACHE.get(key)
    if fn is None:
        fn = _FN_CACHE[key] = build()
        _FN_STATS["misses"] += 1
        while len(_FN_CACHE) > _FN_LIMIT:
            _FN_CACHE.popitem(last=False)
            _FN_STATS["evictions"] += 1
    else:
        _FN_CACHE.move_to_end(key)
        _FN_STATS["hits"] += 1
    return fn


def fn_cache_info() -> dict:
    """{hits, misses, evictions, size, limit} of the process-wide
    compiled-fn cache. A stable ``misses`` count across calls means nothing
    was rebuilt (and therefore nothing recompiled)."""
    return dict(_FN_STATS, size=len(_FN_CACHE), limit=_FN_LIMIT)


def clear_fn_cache() -> None:
    _FN_CACHE.clear()
    _FN_STATS.update(hits=0, misses=0, evictions=0)


def make_decode_fn(cfg: ModelConfig, *, mesh=None, batch_axes=("data",)):
    key = ("decode", cfg, mesh, tuple(batch_axes))

    def build():
        model = registry.get(cfg)

        @jax.jit
        def decode_fn(params, tokens, cache):
            return model.decode_step(params, cfg, tokens, cache, mesh=mesh,
                                     batch_axes=batch_axes)

        return decode_fn

    return _cached_fn(key, build)


def make_prefill_fn(cfg: ModelConfig, max_len: int, *, mesh=None,
                    batch_axes=("data",)):
    key = ("prefill", cfg, max_len, mesh, tuple(batch_axes))

    def build():
        model = registry.get(cfg)

        @jax.jit
        def prefill_fn(params, batch):
            return model.prefill(params, cfg, batch, max_len, mesh=mesh,
                                 batch_axes=batch_axes)

        return prefill_fn

    return _cached_fn(key, build)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _admit_pad_size(g: int, moe_impl: str) -> int:
    """Padded row count for a legacy admission group of ``g`` requests:
    next power of two (bounds prefill compile keys to log2(num_slots) per
    signature). EP MoE is exempt — its expert-capacity buckets depend on
    the batch's total token count, so duplicated pad rows would perturb
    the real rows' routing."""
    return g if moe_impl == "ep" else _next_pow2(g)


def _make_buckets(max_len: int, min_bucket: int = 16) -> tuple[int, ...]:
    """Prompt-length buckets: powers of two from ``min_bucket`` up, capped
    at ``max_len`` (the last bucket is exactly max_len)."""
    buckets, b = [], min_bucket
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


def _prompt_prefix(cfg: ModelConfig, batch: dict) -> int:
    """Non-token cache positions a prompt occupies (vlm patch prefix).
    Batch-derived, not cfg-derived: a vlm batch without patch_embeds
    prefills with prefix 0 (see lm.prefill)."""
    if cfg.family == "vlm" and "patch_embeds" in batch:
        return int(batch["patch_embeds"].shape[1])
    return 0


def _sample(logits, temperature: float, keys):
    """Greedy (paper eval protocol) or per-slot temperature sampling — each
    slot consumes its own key stream so the admission order of OTHER slots
    never perturbs a request's tokens."""
    if temperature > 0:
        return jax.vmap(lambda k, lg: jax.random.categorical(
            k, lg.astype(jnp.float32) / temperature))(
                keys, logits).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------- engine


class ServeEngine:
    """Slot-based continuous-batching engine (see module docstring for the
    KV layouts and the bucketed/chunked prefill scheme).

    ``submit`` then ``step`` drive it incrementally; ``run`` drains a whole
    request list. Arrivals are measured in engine steps (one ``step`` = one
    prefill chunk (if a job is active) + one admission pass + one decode
    chunk).

    Caveat: with ``moe_impl="ep"`` on a mesh, expert capacity buckets depend
    on the batch's token count, so (as with any capacity-routed MoE under
    rebatching) a request's tokens can depend on what shares its decode
    batch; admission groups are never padded for ep configs and ep stays on
    the legacy exact-length admission path.
    """

    def __init__(self, cfg: ModelConfig, params,
                 serve_cfg: ServeConfig | None = None, **kwargs):
        if serve_cfg is None:
            # one-release deprecation shim: the historical ~18-kwarg surface
            # funnels into ServeConfig (same validation, one warning); the
            # legacy on_complete contract was (uid, tokens), so wrap it
            warnings.warn(
                "ServeEngine(cfg, params, **kwargs) is deprecated; pass a "
                "ServeConfig: ServeEngine(cfg, params, ServeConfig(...)). "
                "The loose-kwargs surface will be removed next release.",
                DeprecationWarning, stacklevel=2)
            cb = kwargs.pop("on_complete", None)
            if cb is not None:
                kwargs["on_complete"] = lambda c: cb(c.uid, c.tokens)
            serve_cfg = ServeConfig(**kwargs)
        elif kwargs:
            raise TypeError(
                f"ServeEngine got both a ServeConfig and loose kwargs "
                f"{sorted(kwargs)}; fold everything into the ServeConfig")
        scfg = serve_cfg
        self.serve_cfg = scfg
        self.cfg, self.params = cfg, params
        self.model = registry.get(cfg)
        self.max_len, self.num_slots = scfg.max_len, scfg.num_slots
        self.eos_id = scfg.eos_id
        self.pad_id = int(scfg.pad_id)
        self.decode_chunk = int(scfg.decode_chunk)
        self.temperature = float(scfg.temperature)
        self.mesh, self.batch_axes = scfg.mesh, scfg.batch_axes

        # bucketed prefill needs per-row logit gather over a padded batch
        # (lm.prefill lengths=); only length-indexed-KV families support it,
        # and EP-MoE must never see pad rows (routing is batch-coupled)
        self._bucketed = (cfg.family in ("dense", "moe")
                          and cfg.moe_impl != "ep")
        self.prefill_buckets = (_make_buckets(self.max_len, scfg.min_bucket)
                                if self._bucketed else ())
        # bucketed admission prefills fixed [prefill_rows, bucket] batches
        # (larger groups split across calls): one compile key per bucket,
        # and small/stale groups don't pay num_slots rows of pad FLOPs
        self.prefill_rows = min(int(scfg.prefill_rows), self.num_slots)

        self.kv_layout = scfg.kv_layout
        self.page_size = int(scfg.page_size)
        self._alloc: PageAllocator | None = None
        if scfg.kv_layout == "paged":
            if cfg.family == "ssm":
                # no length-indexed KV to page — identical to dense layout
                self.cache = self.model.init_cache(cfg, self.num_slots,
                                                   self.max_len)
            else:
                if cfg.moe_impl == "ep":
                    raise ValueError(
                        "kv_layout='paged' is not supported for "
                        "moe_impl='ep': EP decode dispatch is mesh-coupled "
                        "and stays on the dense cache path. Use "
                        "kv_layout='dense' for ep configs.")
                pps = pages_for(self.max_len, self.page_size)
                self.num_pages = (int(scfg.num_pages)
                                  if scfg.num_pages is not None
                                  else self.num_slots * pps)
                # raises with the supported-family matrix if cfg can't page
                self.cache = self.model.init_paged_cache(
                    cfg, self.num_slots, self.max_len, self.page_size,
                    self.num_pages)
                self._alloc = PageAllocator(self.num_pages, self.num_slots,
                                            pps)
        else:
            self.cache = self.model.init_cache(cfg, self.num_slots,
                                               self.max_len)

        self.preempt = bool(scfg.preempt)
        if self.preempt and self._alloc is None:
            raise ValueError(
                "preempt=True requires kv_layout='paged' with a page pool "
                "(preemption frees and re-acquires pages; this config has "
                "no pool to reclaim — ssm pages are a no-op)")
        self._prefix: PrefixCache | None = None
        self._store = scfg.prefix_store
        self._store_key = None
        if scfg.prefix_cache:
            if self._alloc is None or not self._bucketed or cfg.use_mla:
                raise ValueError(
                    f"prefix_cache=True requires kv_layout='paged' on a "
                    f"bucketed GQA/MHA dense/moe stack (family="
                    f"{cfg.family!r}, use_mla={cfg.use_mla}, moe_impl="
                    f"{cfg.moe_impl!r}): suffix prefill reuses the chunked-"
                    f"prefill machinery and page aliasing needs the pool")
            cap = (int(scfg.prefix_cache_pages)
                   if scfg.prefix_cache_pages is not None
                   else self.num_pages // 2)
            self._prefix = PrefixCache(self.page_size, cap,
                                       self._alloc.incref, self._alloc.decref)
            if self._store is not None:
                # adopt warm state from a previous engine over the same
                # params + pool geometry: the stored k/v pools replace the
                # freshly-initialized ones, the stored allocator (carrying
                # the tree's page references) is re-shaped to this engine's
                # slot geometry, and the stored tree replaces the cold one
                self._store_key = self._store.key_for(
                    cfg, params, page_size=self.page_size,
                    num_pages=self.num_pages)
                state = self._store.take(self._store_key)
                if state is not None:
                    self.cache = {**self.cache, "k": state["k"],
                                  "v": state["v"]}
                    self._alloc = state["alloc"].resize_slots(self.num_slots,
                                                              pps)
                    tree = state["tree"]
                    # the tree's incref/decref are bound to the adopted
                    # allocator — the same object we just resized
                    tree.capacity = cap
                    if len(tree) > cap:
                        tree.evict(len(tree) - cap)
                    self._prefix = tree
                    self._mirror_pages()

        self.admission_policy = None
        if scfg.admission == "prefix_aware":
            fp = (int(scfg.admission_frontier_pages)
                  if scfg.admission_frontier_pages is not None
                  else 2 * pages_for(self.max_len, self.page_size))
            self.admission_policy = PrefixAwareAdmission(
                lambda r: set(self._prefix.match(self._eff_tokens(r),
                                                 touch=False)),
                lambda: self._prefix.lru_pages(fp),
                max_skips=scfg.admission_max_skips)
        self.scheduler = FCFSScheduler(self.admission_policy)

        self._on_complete = scfg.on_complete
        self._stream: StreamOut | None = (
            StreamOut(scfg.on_complete)
            if scfg.on_complete is not None and scfg.stream_out else None)

        self.prefill_chunk = int(scfg.prefill_chunk)
        if self.prefill_chunk:
            if not self._bucketed or cfg.use_mla:
                raise ValueError(
                    f"prefill_chunk is only supported for bucketed GQA/MHA "
                    f"dense/moe serving (family={cfg.family!r}, "
                    f"use_mla={cfg.use_mla}, moe_impl={cfg.moe_impl!r}); "
                    f"use prefill_chunk=0 for this architecture")

        self.finished = jnp.ones((self.num_slots,), bool)  # idle slots are inert
        self.last_tok = jnp.full((self.num_slots,), self.pad_id, jnp.int32)
        base = scfg.rng if scfg.rng is not None else jax.random.PRNGKey(0)
        self._base_rng = base
        self.keys = jax.random.split(base, self.num_slots)

        self._slot_req: list[Request | None] = [None] * self.num_slots
        self._out: dict[int, list[int]] = {}      # uid -> emitted tokens
        self._left: dict[int, int] = {}           # uid -> remaining budget
        self._resume: dict[int, dict] = {}        # uid -> preempted state
        self._meta: dict[int, dict] = {}          # uid -> Completion fields
        self._no_preempt: set[int] = set()        # slots admitted this step
        self._job: dict | None = None             # in-flight chunked prefill
        self._closed = False
        self.clock = 0                            # admission step counter
        # decode_steps counts ACTUAL emitted decode positions (tokens the
        # host consumed, including a terminal EOS) — not chunk * decode_chunk
        # — so goodput math downstream reads real work, not dispatch grain
        self.stats = {"decode_chunks": 0, "decode_steps": 0, "prefills": 0,
                      "prefill_chunks": 0, "admitted": 0, "completed": 0,
                      "backpressure": 0, "preempted": 0, "prefix_hits": 0,
                      "prefix_pages_shared": 0, "prefill_tokens": 0}

        # per-request wall-clock latency (always-on: perf_counter stamps +
        # bounded histograms, no device syncs). Keyed by uid; stamps survive
        # preemption so TTFT/e2e span the request's real lifetime.
        self._req_ns: dict[int, dict] = {}
        # per-ENGINE histograms (stats_snapshot() reports this instance, not
        # every engine the process ever ran), registered last-engine-wins
        # into the global registry — the SwapStats idiom
        self._h_queue_wait = obs.Histogram()
        self._h_ttft = obs.Histogram()
        self._h_tpot = obs.Histogram()
        self._h_e2e = obs.Histogram()
        for nm, h in (("queue_wait_us", self._h_queue_wait),
                      ("ttft_us", self._h_ttft), ("tpot_us", self._h_tpot),
                      ("e2e_us", self._h_e2e)):
            obs.metrics.register(nm, h, subsystem="serve")
        self._g_pages = obs.metrics.gauge("page_pool_live", subsystem="serve")
        self._g_fn_cache = obs.metrics.gauge("fn_cache_size",
                                             subsystem="serve")
        obs.metrics.register("engine", lambda: dict(self.stats),
                             subsystem="serve")

    # ---------------------------------------------------- compiled closures

    def _static_key(self) -> tuple:
        return (self.cfg, self.max_len, self.num_slots, self.eos_id,
                self.pad_id, self.temperature, self.mesh, self.batch_axes,
                self.kv_layout, self.page_size,
                getattr(self, "num_pages", None),
                getattr(self, "prefill_rows", 1))

    def _chunk_fn(self):
        # the build closure must capture only statics (no `self`): the jitted
        # fn lives in the process-wide cache and would otherwise pin the
        # first engine instance's params + KV cache for the process lifetime
        key = ("chunk", self.decode_chunk) + self._static_key()
        model, cfg = self.model, self.cfg
        mesh, axes = self.mesh, self.batch_axes
        eos, pad, steps = self.eos_id, self.pad_id, self.decode_chunk
        temperature = self.temperature

        def build():
            @jax.jit
            def chunk_fn(params, cache, last_tok, finished, keys):
                def body(carry, _):
                    cache, tok, fin, keys = carry
                    logits, cache = model.decode_step(
                        params, cfg, tok[:, None], cache, mesh=mesh,
                        batch_axes=axes)
                    ks = jax.vmap(jax.random.split)(keys)
                    nxt = _sample(logits, temperature, ks[:, 1])
                    keys = ks[:, 0] if temperature > 0 else keys
                    nxt = jnp.where(fin, pad, nxt)
                    if eos is not None:
                        fin = fin | (nxt == eos)
                    return (cache, nxt, fin, keys), nxt

                carry = (cache, last_tok, finished, keys)
                (cache, tok, fin, keys), toks = jax.lax.scan(
                    body, carry, None, length=steps)
                return cache, tok, fin, keys, toks.T  # toks: [B, steps]

            return chunk_fn

        return _cached_fn(key, build)

    @staticmethod
    def _tok0_bookkeeping(eos, temperature):
        """Shared tail of every admission closure: sample the first token
        and scatter per-slot state (pad rows carry the OOB slot index and
        drop)."""
        def finish(cache, slots, logits, last_tok, finished, keys, req_keys):
            ks = jax.vmap(jax.random.split)(req_keys)
            tok0 = _sample(logits, temperature, ks[:, 1])
            fin0 = ((tok0 == eos) if eos is not None
                    else jnp.zeros(tok0.shape, bool))
            last_tok = last_tok.at[slots].set(tok0)
            finished = finished.at[slots].set(fin0)
            keys = keys.at[slots].set(ks[:, 0])
            return cache, last_tok, finished, keys, tok0
        return finish

    def _admit_fn(self, group_size: int, sig: tuple):
        """Legacy exact-length admission (signature-grouped families)."""
        key = ("admit", group_size, sig) + self._static_key()
        model, cfg, max_len = self.model, self.cfg, self.max_len
        mesh, axes, eos = self.mesh, self.batch_axes, self.eos_id
        temperature = self.temperature
        finish = self._tok0_bookkeeping(eos, temperature)

        def build():
            @jax.jit
            def admit_fn(params, cache, batch, slots, last_tok, finished,
                         keys, req_keys):
                logits, new_cache = model.prefill(params, cfg, batch, max_len,
                                                  mesh=mesh, batch_axes=axes)
                cache = model.insert_slots(cache, new_cache, slots)
                return finish(cache, slots, logits, last_tok, finished, keys,
                              req_keys)

            return admit_fn

        return _cached_fn(key, build)

    def _admit_bucket_fn(self, bucket: int):
        """Bucketed single-shot admission: one compile key per bucket (the
        group is split/padded to fixed [prefill_rows, bucket] batches)."""
        key = ("admitb", bucket) + self._static_key()
        model, cfg, max_len = self.model, self.cfg, self.max_len
        mesh, axes, eos = self.mesh, self.batch_axes, self.eos_id
        temperature = self.temperature
        paged = self._alloc is not None
        # paged prefill builds its scratch at bucket length (the pool insert
        # handles any source length); dense must match the cache row length
        prefill_len = bucket if paged else max_len
        finish = self._tok0_bookkeeping(eos, temperature)

        def build():
            @jax.jit
            def admit_fn(params, cache, batch, slots, lengths, last_tok,
                         finished, keys, req_keys):
                logits, new_cache = model.prefill(
                    params, cfg, batch, prefill_len, mesh=mesh,
                    batch_axes=axes, lengths=lengths)
                if paged:
                    cache = model.insert_slots_paged(cache, new_cache, slots,
                                                     lengths)
                else:
                    cache = model.insert_slots(cache, new_cache, slots)
                return finish(cache, slots, logits, last_tok, finished, keys,
                              req_keys)

            return admit_fn

        return _cached_fn(key, build)

    def _admit_prefix_fn(self, scratch_len: int, chunk: int, rows: int):
        """Prefix-cache admission for a same-start group of ``rows``
        requests in ONE call: per row, COW-copy the boundary page
        (``cow_dst == num_pages`` drops the copy), gather the aliased
        prefix [0, start) from the page pools into a dense scratch, prefill
        only the uncached suffix chunk (traced ``start``, shared by the
        whole group — one compile per (scratch_len, chunk, rows) SHAPE, all
        static, not per offset), scatter positions [start, length) back
        through each slot's table (shared pages below ``start`` are never
        written), and sample token 0. Pad rows carry slot=num_slots and
        cow indices=num_pages, so every one of their scatters drops. A
        prefix MISS is the same closure with start=0 over a zero scratch."""
        key = ("padmit", scratch_len, chunk, rows) + self._static_key()
        model, cfg = self.model, self.cfg
        mesh, axes, eos = self.mesh, self.batch_axes, self.eos_id
        temperature = self.temperature
        num_slots, num_pages = self.num_slots, self.num_pages
        ps, nv = self.page_size, self.cfg.padded_vocab_size
        finish = self._tok0_bookkeeping(eos, temperature)

        def build():
            @jax.jit
            def admit_fn(params, cache, tokens, slots, start, lengths,
                         cow_src, cow_dst, last_tok, finished, keys,
                         req_keys):
                # copy-on-write BEFORE the gather and the suffix scatter:
                # the duplicated page carries the shared page's filled
                # positions, then receives the recomputed final token's KV
                src = jnp.minimum(cow_src, num_pages - 1)
                k_pool = cache["k"].at[:, cow_dst].set(cache["k"][:, src])
                v_pool = cache["v"].at[:, cow_dst].set(cache["v"][:, src])
                cache = {**cache, "k": k_pool, "v": v_pool}
                maxp = cache["pages"].shape[1]
                tbl = cache["pages"][jnp.minimum(slots, num_slots - 1)]
                t = jnp.arange(scratch_len)
                page = jnp.clip(tbl[:, jnp.minimum(t // ps, maxp - 1)],
                                0, num_pages - 1)                # [1, SL]
                off = jnp.broadcast_to(t % ps, page.shape)
                m = (t < start)[None, None, :, None, None]
                # the split (page, off) index puts its [1, SL] dims first
                rows = lambda pool: pool[:, page, :, off].transpose(  # noqa: E731
                    2, 0, 1, 3, 4)
                scratch = {"k": jnp.where(m, rows(k_pool), 0),
                           "v": jnp.where(m, rows(v_pool), 0)}
                last0 = jnp.zeros((tokens.shape[0], nv), jnp.float32)
                logits, scratch = model.prefill_chunk(
                    params, cfg, tokens, scratch, start, lengths, last0,
                    mesh=mesh, batch_axes=axes)
                cache = model.insert_slots_paged(cache, scratch, slots,
                                                 lengths, starts=start)
                return finish(cache, slots, logits, last_tok, finished,
                              keys, req_keys)

            return admit_fn

        return _cached_fn(key, build)

    def _prefill_chunk_fn(self, bucket: int, chunk: int):
        key = ("pchunk", bucket, chunk) + self._static_key()
        model, cfg = self.model, self.cfg
        mesh, axes = self.mesh, self.batch_axes

        def build():
            @jax.jit
            def chunk_prefill(params, tokens, scratch, start, lengths, last):
                return model.prefill_chunk(params, cfg, tokens, scratch,
                                           start, lengths, last, mesh=mesh,
                                           batch_axes=axes)

            return chunk_prefill

        return _cached_fn(key, build)

    def _prefill_final_fn(self, bucket: int):
        """Insert a finished chunked-prefill scratch cache into the engine
        cache and sample the first token."""
        key = ("pfinal", bucket) + self._static_key()
        model, cfg, max_len = self.model, self.cfg, self.max_len
        eos, temperature = self.eos_id, self.temperature
        paged = self._alloc is not None
        finish = self._tok0_bookkeeping(eos, temperature)

        def build():
            @jax.jit
            def final_fn(params, cache, scratch, slots, lengths, last_logits,
                         last_tok, finished, keys, req_keys):
                scratch2 = {**scratch, "pos": lengths}
                if paged:
                    cache = model.insert_slots_paged(cache, scratch2, slots,
                                                     lengths)
                else:
                    if bucket < max_len:
                        pad = [(0, 0), (0, 0), (0, max_len - bucket),
                               (0, 0), (0, 0)]
                        scratch2 = {**scratch2,
                                    "k": jnp.pad(scratch2["k"], pad),
                                    "v": jnp.pad(scratch2["v"], pad)}
                    cache = model.insert_slots(cache, scratch2, slots)
                return finish(cache, slots, last_logits, last_tok, finished,
                              keys, req_keys)

            return final_fn

        return _cached_fn(key, build)

    # ----------------------------------------------------------- lifecycle

    def submit(self, req: Request) -> None:
        if self._closed:
            raise RuntimeError("ServeEngine is closed")
        if req.prompt_len == 0:
            raise ValueError(
                f"request {req.uid}: empty prompt — the engine needs at "
                f"least one prompt token to prefill. Prepend a BOS token "
                f"for unconditional generation.")
        prefix = 0
        if self.cfg.family == "vlm" and "patch_embeds" in req.extras:
            prefix = int(np.asarray(req.extras["patch_embeds"]).shape[0])
        need = prefix + req.prompt_len + req.max_new_tokens
        if need > self.max_len:
            raise ValueError(
                f"request {req.uid} needs {need} cache positions "
                f"(prefix {prefix} + prompt {req.prompt_len} + "
                f"{req.max_new_tokens} new) but max_len={self.max_len}")
        if self._alloc is not None:
            np_need = pages_for(need, self.page_size)
            if np_need > self._alloc.num_pages:
                raise PoolExhausted(
                    f"request {req.uid} needs {np_need} pages "
                    f"({need} positions / page_size {self.page_size}) but "
                    f"the pool has {self._alloc.num_pages}; grow num_pages "
                    f"— waiting cannot free enough")
        # first submit stamps the latency clock; a preempted request
        # re-entering through push_front keeps its original stamps
        self._req_ns.setdefault(req.uid, {"submit": time.perf_counter_ns()})
        self.scheduler.submit(req)

    def _free_slots(self) -> list[int]:
        job = set(self._job["slot_ids"]) if self._job else ()
        return [i for i, r in enumerate(self._slot_req)
                if r is None and i not in job]

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds the largest bucket "
                         f"{self.prefill_buckets[-1]} (max_len)")

    # Preempted requests requeue carrying their already-generated tokens:
    # the EFFECTIVE prompt at re-admission is prompt + emitted-so-far, and
    # the remaining budget is what was left at preemption. Every admission
    # site (grouping, page reservation, batching, prefill) goes through
    # these helpers so fresh and resumed requests share one code path.

    def _eff_tokens(self, req: Request) -> np.ndarray:
        res = self._resume.get(req.uid)
        return res["tokens"] if res is not None else req.tokens

    def _eff_len(self, req: Request) -> int:
        return int(self._eff_tokens(req).shape[0])

    def _budget_left(self, req: Request) -> int:
        res = self._resume.get(req.uid)
        return res["left"] if res is not None else req.max_new_tokens

    def _group_key(self, req: Request) -> tuple:
        ex = tuple(sorted((k, np.asarray(v).shape)
                          for k, v in req.extras.items()))
        return (self._bucket_for(self._eff_len(req)), ex)

    def _mirror_pages(self) -> None:
        self.cache = {**self.cache,
                      "pages": jnp.asarray(self._alloc.table)}

    def kv_cache_bytes(self) -> int:
        """Device bytes of the persistent serve cache (all leaves)."""
        return int(sum(x.nbytes for x in jax.tree.leaves(self.cache)))

    def page_pool_stats(self) -> dict | None:
        """Allocator stats for the paged layout (None for dense/no-op)."""
        return self._alloc.stats() if self._alloc is not None else None

    def stats_snapshot(self) -> dict:
        """One nested dict consolidating every serving stat surface (the
        launcher/examples print this instead of separate stat blocks; keys
        documented in the module docstring):

        - ``engine``: the per-engine counter dict (``self.stats``)
        - ``latency_us``: queue-wait / TTFT / time-per-output-token / e2e
          histogram summaries (count, mean, p50/p95/p99)
        - ``pages``: ``PageAllocator.stats()`` (None for dense layout)
        - ``scheduler``: queue depth + admission-policy counters
        - ``prefix_cache``: radix-tree occupancy (None when disabled)
        - ``stream_out``: background detokenize queue depth (None when off)
        - ``fn_cache``: the process-wide compiled-fn cache counters
        """
        return {
            "engine": dict(self.stats),
            "latency_us": {"queue_wait": self._h_queue_wait.summary(),
                           "ttft": self._h_ttft.summary(),
                           "tpot": self._h_tpot.summary(),
                           "e2e": self._h_e2e.summary()},
            "pages": self.page_pool_stats(),
            "scheduler": {
                "pending": int(self.scheduler.pending),
                "admission": (dict(self.admission_policy.stats)
                              if self.admission_policy is not None else None),
            },
            "prefix_cache": ({"pages": len(self._prefix),
                              "capacity_pages": self._prefix.capacity}
                             if self._prefix is not None else None),
            "stream_out": ({"pending": self._stream.pending}
                           if self._stream is not None else None),
            "fn_cache": fn_cache_info(),
        }

    def _insert_prefix_pages(self, slot: int, tokens, covered: int) -> None:
        """Insert ``slot``'s pages for the fully-written full-page prefix of
        ``tokens`` (``covered`` positions hold valid KV) into the radix
        tree. Called BEFORE the slot's free so the pages are still live —
        the tree's incref keeps them across the decref."""
        nfull = min(int(covered), len(tokens)) // self.page_size
        if nfull:
            pages = [int(p) for p in self._alloc.table[slot, :nfull]]
            self._prefix.insert(tokens[:nfull * self.page_size], pages)

    def _complete(self, slot: int, completed: list) -> None:
        req = self._slot_req[slot]
        self._slot_req[slot] = None
        self.stats["completed"] += 1
        toks = np.asarray(self._out.pop(req.uid), np.int32)
        self._left.pop(req.uid, None)
        meta = self._meta.pop(req.uid, {})
        eos_hit = (self.eos_id is not None and toks.size
                   and int(toks[-1]) == self.eos_id)
        comp = Completion(
            uid=req.uid, tokens=toks,
            finish_reason="eos" if eos_hit else "length",
            arrival=float(req.arrival),
            first_token_step=int(meta.get("first_step", self.clock)),
            done_step=int(self.clock),
            prefix_pages=int(meta.get("prefix_pages", 0)))
        completed.append(comp)
        rt = self._req_ns.pop(req.uid, None)
        if rt is not None:
            now_ns = time.perf_counter_ns()
            self._h_e2e.record((now_ns - rt["submit"]) / 1e3)
            first = rt.get("first", now_ns)
            self._h_tpot.record((now_ns - first) / 1e3
                                / max(1, len(toks) - 1))
            tr = obs.tracer()
            if tr is not None:
                # retroactive per-request spans, one timeline lane per uid:
                # e2e (submit -> done) with the ttft head (submit -> first)
                track = f"request {req.uid}"
                tr.complete("e2e", rt["submit"], now_ns, track=track,
                            args={"uid": req.uid, "tokens": len(toks),
                                  "finish": comp.finish_reason})
                tr.complete("ttft", rt["submit"], first, track=track,
                            args={"uid": req.uid})
        if self._alloc is not None:
            if self._prefix is not None:
                self._insert_prefix_pages(slot, req.tokens, req.prompt_len)
            self._alloc.free(slot)
            self._mirror_pages()
        if self._on_complete is not None:
            if self._stream is not None:
                self._stream.put(comp)     # worker detokenizes
            else:
                self._on_complete(comp)    # stream_out=False: inline

    # ----------------------------------------------------------- admission

    def _post_admit(self, group, slot_ids, tok0, completed) -> None:
        tok0 = np.asarray(tok0)[:len(group)]
        self.stats["admitted"] += len(group)
        now_ns = time.perf_counter_ns()
        for req, slot, t in zip(group, slot_ids, tok0):
            self._slot_req[slot] = req
            self._no_preempt.add(slot)  # just admitted: no KV written yet
            # first admission stamps first_token_step; a preempted request
            # keeps its original (its first token really was sampled then)
            self._meta.setdefault(req.uid, {"first_step": self.clock,
                                            "prefix_pages": 0})
            # first admission also samples the first token, so it stamps
            # both queue-wait and TTFT (re-admission keeps the originals)
            rt = self._req_ns.setdefault(req.uid, {"submit": now_ns})
            if "first" not in rt:
                rt["first"] = now_ns
                admit = rt.setdefault("admit", now_ns)
                self._h_queue_wait.record((admit - rt["submit"]) / 1e3)
                self._h_ttft.record((now_ns - rt["submit"]) / 1e3)
            res = self._resume.pop(req.uid, None)
            if res is not None:
                self._out[req.uid] = res["emitted"] + [int(t)]
                self._left[req.uid] = res["left"] - 1
            else:
                self._out[req.uid] = [int(t)]
                self._left[req.uid] = req.max_new_tokens - 1
            if ((self.eos_id is not None and int(t) == self.eos_id)
                    or self._left[req.uid] == 0):
                self._complete(slot, completed)

    def _admit(self, group: list[Request], completed: list) -> None:
        """Legacy exact-length admission (signature-grouped families): pad
        the group to a power of two — duplicate rows, scattered to the
        out-of-range slot index so insert_slots drops them — one prefill
        compile per (pow2 size, prompt signature). EP MoE is exempt: its
        capacity buckets depend on the batch's token count, so pad rows
        would perturb the real rows' routing."""
        free = self._free_slots()
        g = len(group)
        assert g <= len(free)
        slot_ids = free[:g]
        gp = _admit_pad_size(g, self.cfg.moe_impl)
        tokens = np.stack([r.tokens for r in group]).astype(np.int32)
        extras = {k: np.stack([np.asarray(r.extras[k]) for r in group])
                  for k in group[0].extras}
        if gp > g:
            rep = [(0, gp - g)] + [(0, 0)] * (tokens.ndim - 1)
            tokens = np.pad(tokens, rep, mode="edge")
            extras = {k: np.pad(v, [(0, gp - g)] + [(0, 0)] * (v.ndim - 1),
                                mode="edge") for k, v in extras.items()}
        slots = np.asarray(slot_ids + [self.num_slots] * (gp - g), np.int32)
        batch = {"tokens": tokens, **extras}
        req_keys = self._req_keys(group, gp)

        fn = self._admit_fn(gp, group[0].signature())
        self.cache, self.last_tok, self.finished, self.keys, tok0 = fn(
            self.params, self.cache, batch, slots, self.last_tok,
            self.finished, self.keys, req_keys)
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += sum(r.prompt_len for r in group)
        self._post_admit(group, slot_ids, tok0, completed)

    def _req_keys(self, group, gp):
        """Per-request sampling keys. A resumed request continues from the
        key saved at preemption: tok0 bookkeeping and the decode-chunk body
        split identically (sample from split[1], carry split[0]), so the
        sampled stream is bit-exact vs the never-preempted run."""
        if self.temperature > 0:
            ks = []
            for r in group:
                res = self._resume.get(r.uid)
                ks.append(jnp.asarray(res["key"]) if res is not None
                          else jax.random.fold_in(self._base_rng, r.uid))
            return jnp.stack(ks + [self._base_rng] * (gp - len(group)))
        return jnp.zeros((gp,) + self.keys.shape[1:], self.keys.dtype)

    def _bucket_batch(self, group, slot_ids, rows):
        """Pad a bucketed admission group to ``rows`` rows: [rows, bucket]
        tokens, [rows] lengths/slots (pad rows -> OOB slot, dropped)."""
        ns = self.num_slots
        bucket = self._bucket_for(max(self._eff_len(r) for r in group))
        g = len(group)
        tokens = np.full((rows, bucket), self.pad_id, np.int32)
        lengths = np.zeros((rows,), np.int32)
        for i, r in enumerate(group):
            toks = self._eff_tokens(r)
            tokens[i, :len(toks)] = toks
            lengths[i] = len(toks)
        slots = np.asarray(list(slot_ids) + [ns] * (rows - g), np.int32)
        return bucket, tokens, lengths, slots

    # ------------------------------------------------- preempt-and-requeue

    def _preempt_one(self, head_left: int | None = None) -> bool:
        """Preempt the resident with the most remaining budget: free its
        private pages (shared prefix pages just decref), save its resume
        state, and requeue it at the scheduler head with its original
        arrival. Slots admitted this step are exempt — their token-0 KV is
        not written until the next decode chunk, so their pages hold an
        incomplete prefix (and preempting a request to admit another would
        thrash anyway).

        Damping: when ``head_left`` (the remaining budget of the request
        being admitted) is given, only residents with STRICTLY more budget
        left are preemptible. Preemption then only ever moves pages from
        longer-tailed work to shorter work, so a requeued victim can never
        preempt its way straight back in (the ping-pong livelock of an
        unconditional policy) — remaining work strictly decreases along any
        preemption chain."""
        best = None
        for slot, req in enumerate(self._slot_req):
            if req is None or slot in self._no_preempt:
                continue
            left = self._left[req.uid]
            if head_left is not None and left <= head_left:
                continue
            if best is None or left > best[0]:
                best = (left, slot)
        if best is None:
            return False
        _, slot = best
        req = self._slot_req[slot]
        emitted = self._out.pop(req.uid)
        left = self._left.pop(req.uid)
        ctx = np.concatenate([req.tokens,
                              np.asarray(emitted, np.int32)])
        self._resume[req.uid] = {
            "tokens": ctx, "emitted": emitted, "left": left,
            # sampled decoding: the key stream continues from here
            "key": (np.asarray(self.keys[slot])
                    if self.temperature > 0 else None)}
        if self._prefix is not None:
            # positions [0, len(ctx)-1) hold valid KV (the newest emitted
            # token was sampled but not yet fed back/written) — its full
            # pages make the re-admission prefix-accelerated
            self._insert_prefix_pages(slot, ctx, len(ctx) - 1)
        self._slot_req[slot] = None
        self._alloc.free(slot)
        self._mirror_pages()
        # inert on device: no more samples; sentinel table row drops writes
        self.finished = self.finished.at[slot].set(True)
        self.scheduler.push_front([req])
        self.stats["preempted"] += 1
        return True

    def _reclaim(self, need: int, head_left: int | None = None) -> bool:
        """Make room for an admission that needs ``need`` fresh pages:
        first evict LRU prefix-cache pages (cheapest — cached KV is
        recomputable), then preempt one resident with more remaining work
        than the admittee (see ``_preempt_one``). Returns True if anything
        was reclaimed (the caller loops until the request fits or this
        gives up)."""
        freed = False
        if self._prefix is not None:
            short = need - self._alloc.free_pages
            if short > 0 and self._prefix.evict(short):
                freed = True
        if not self._alloc.can_allocate(need) and self.preempt:
            freed = self._preempt_one(head_left) or freed
        return freed

    def _reserve_pages(self, group, free) -> list[Request]:
        """Admission backpressure: allocate pages FCFS, reclaiming (prefix
        eviction, then preemption) when a request doesn't fit; the first
        request that still doesn't fit (and everything behind it) goes back
        to the queue head. Returns the admissible prefix."""
        if self._alloc is None:
            return group
        fit = 0
        for r, slot in zip(group, free):
            need = pages_for(self._eff_len(r) + self._budget_left(r),
                             self.page_size)
            while not self._alloc.can_allocate(need):
                if not self._reclaim(need, self._budget_left(r)):
                    break
            if not self._alloc.can_allocate(need):
                break
            self._alloc.allocate(slot, need)
            fit += 1
        if fit < len(group):
            self.scheduler.push_front(group[fit:])
            self.stats["backpressure"] += len(group) - fit
        if fit:
            self._mirror_pages()
        return group[:fit]

    # ------------------------------------------------ prefix-hit admission

    def _prefix_match_start(self, req: Request, touch: bool = True):
        """The request's radix match and its page-aligned suffix start.
        COW boundary: a match is page-granular, so the start is
        page-aligned UNLESS the entire prompt is cached — then the final
        token's logits must be recomputed (start = len-1, mid-page) and
        the last matched page is duplicated first so the shared copy is
        never written."""
        eff = self._eff_tokens(req)
        length = len(eff)
        matched = self._prefix.match(eff, touch=touch)
        if matched and len(matched) * self.page_size >= length:
            return eff, length, matched, matched[:-1], int(matched[-1]), \
                length - 1
        return eff, length, matched, matched, None, \
            len(matched) * self.page_size

    def _prefix_group_key(self, req: Request) -> tuple:
        """Admission group key for the prefix path: requests sharing a
        suffix ``start`` and a prompt-length bucket prefill as ONE
        [rows, chunk] call. A pure probe — grouping must not touch the LRU
        stamps the prefix-aware policy schedules around."""
        *_, start = self._prefix_match_start(req, touch=False)
        ex = tuple(sorted((k, np.asarray(v).shape)
                          for k, v in req.extras.items()))
        return (start, self._bucket_for(self._eff_len(req)), ex)

    def _admit_prefix_group(self, group, free, completed) -> bool:
        """Admit a same-start group through the radix prefix cache in one
        prefill call: alias each request's cached prefix into its slot's
        table and prefill only the uncached suffixes as a [rows, chunk]
        batch (``rows`` = prefill_rows, pad rows drop on device). The
        matches are re-taken (touched) here; nothing mutates the tree
        between the scheduler's group-key probe and this point, so the
        group's shared ``start`` still holds. Returns False if any member
        hit backpressure (it and everything behind it are back at the
        queue head — the caller stops admitting)."""
        ps = self.page_size
        infos = [self._prefix_match_start(r) for r in group]
        start = infos[0][-1]
        # pin every match before reclaim can evict it out from under us
        # (eviction of a tree-only page would free it for reuse); pinned
        # pages survive prefix eviction with their KV intact, so aliasing
        # them below stays valid even if reclaim drops them from the tree
        pinned = [p for (_, _, matched, *_) in infos for p in matched]
        for p in pinned:
            self._alloc.incref(p)
        admitted, slots = [], []
        try:
            for (eff, length, matched, aliased, cow_src, st), req, slot in \
                    zip(infos, group, free):
                assert st == start, "scheduler grouped mixed starts"
                budget = self._budget_left(req)
                need = pages_for(length + budget, ps)
                n_fresh = need - len(aliased)
                while not self._alloc.can_allocate(n_fresh):
                    if not self._reclaim(n_fresh, budget):
                        break
                if not self._alloc.can_allocate(n_fresh):
                    back = group[len(admitted):]
                    self.scheduler.push_front(back)
                    self.stats["backpressure"] += len(back)
                    break
                self._alloc.alias(slot, aliased, n_fresh)
                admitted.append((req, eff, length, matched, aliased,
                                 cow_src))
                slots.append(slot)
        finally:
            for p in pinned:
                self._alloc.decref(p)
        if not admitted:
            return False
        self._mirror_pages()

        rows = self.prefill_rows
        chunk = _next_pow2(max(length - start
                               for _, _, length, *_ in admitted))
        scratch_len = _next_pow2(max(max(length for _, _, length, *_
                                         in admitted), start + chunk))
        tokens = np.full((rows, chunk), self.pad_id, np.int32)
        lengths = np.zeros((rows,), np.int32)
        slot_arr = np.full((rows,), self.num_slots, np.int32)
        cow_src_arr = np.full((rows,), self.num_pages, np.int32)
        cow_dst_arr = np.full((rows,), self.num_pages, np.int32)
        suffix_total = 0
        for i, ((req, eff, length, matched, aliased, cow_src),
                slot) in enumerate(zip(admitted, slots)):
            suffix = length - start
            tokens[i, :suffix] = eff[start:]
            lengths[i] = length
            slot_arr[i] = slot
            suffix_total += suffix
            if cow_src is not None:
                cow_src_arr[i] = cow_src
                cow_dst_arr[i] = int(self._alloc.table[slot, len(aliased)])
            if matched:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_pages_shared"] += len(aliased)
                meta = self._meta.setdefault(
                    req.uid, {"first_step": self.clock, "prefix_pages": 0})
                meta["prefix_pages"] += len(aliased)

        reqs = [a[0] for a in admitted]
        fn = self._admit_prefix_fn(scratch_len, chunk, rows)
        self.cache, self.last_tok, self.finished, self.keys, tok0 = fn(
            self.params, self.cache, tokens, slot_arr, np.int32(start),
            lengths, cow_src_arr, cow_dst_arr, self.last_tok, self.finished,
            self.keys, self._req_keys(reqs, rows))
        # suffix-only accounting: the aliased prefixes cost zero prefill
        # tokens, and the whole group is ONE prefill call
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += suffix_total
        self._post_admit(reqs, slots, tok0, completed)
        return len(admitted) == len(group)

    def _admit_bucketed(self, group, slot_ids, completed) -> None:
        """Prefill the group in fixed [prefill_rows, bucket] batches: the
        row count is static per bucket, so every group size reuses the one
        compiled closure, and a lone late arrival doesn't pay num_slots
        rows of pad-row FLOPs."""
        rows = self.prefill_rows
        # the whole group shares one bucket (the scheduler groups by it)
        bucket = self._bucket_for(max(r.prompt_len for r in group))
        fn = self._admit_bucket_fn(bucket)
        for i in range(0, len(group), rows):
            sub, sids = group[i:i + rows], slot_ids[i:i + rows]
            _, tokens, lengths, slots = self._bucket_batch(sub, sids, rows)
            req_keys = self._req_keys(sub, rows)
            self.cache, self.last_tok, self.finished, self.keys, tok0 = fn(
                self.params, self.cache, {"tokens": tokens}, slots, lengths,
                self.last_tok, self.finished, self.keys, req_keys)
            self.stats["prefills"] += 1
            self.stats["prefill_tokens"] += int(lengths.sum())
            self._post_admit(sub, sids, tok0, completed)

    def _start_job(self, group, slot_ids) -> None:
        # chunked prefill: admission starts now, the first token lands when
        # the job finalizes steps later — stamp queue-wait's endpoint here
        now_ns = time.perf_counter_ns()
        for r in group:
            self._req_ns.setdefault(r.uid,
                                    {"submit": now_ns}).setdefault("admit",
                                                                   now_ns)
        bucket, tokens, lengths, slots = self._bucket_batch(
            group, slot_ids, self.num_slots)
        scratch = self.model.init_cache(self.cfg, self.num_slots, bucket)
        scratch = {"k": scratch["k"], "v": scratch["v"]}
        self._job = {
            "group": group, "slot_ids": slot_ids, "slots": slots,
            "lengths": lengths, "tokens": tokens, "bucket": bucket,
            "scratch": scratch, "start": 0,
            "last": jnp.zeros((self.num_slots, self.cfg.padded_vocab_size),
                              jnp.float32),
        }
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += int(lengths.sum())

    def _job_step(self, completed) -> None:
        """Advance the in-flight chunked prefill by one chunk; finalize
        (insert + first-token sample) when the bucket is fully prefilled."""
        j = self._job
        c = min(self.prefill_chunk, j["bucket"] - j["start"])
        fn = self._prefill_chunk_fn(j["bucket"], c)
        chunk = j["tokens"][:, j["start"]:j["start"] + c]
        j["last"], j["scratch"] = fn(
            self.params, chunk, j["scratch"], np.int32(j["start"]),
            j["lengths"], j["last"])
        j["start"] += c
        self.stats["prefill_chunks"] += 1
        if j["start"] < j["bucket"]:
            return
        self._job = None
        req_keys = self._req_keys(j["group"], self.num_slots)
        fn = self._prefill_final_fn(j["bucket"])
        self.cache, self.last_tok, self.finished, self.keys, tok0 = fn(
            self.params, self.cache, j["scratch"], j["slots"], j["lengths"],
            j["last"], self.last_tok, self.finished, self.keys, req_keys)
        self._post_admit(j["group"], j["slot_ids"], tok0, completed)

    def _admission(self, completed) -> None:
        """Admit runnable groups into free slots until slots/pages/queue run
        out. At most one chunked-prefill job is in flight; while one is
        active its slots are reserved and admission pauses. With the prefix
        cache enabled, groups are keyed by (suffix start, bucket) so a
        same-start group prefills as ONE [prefill_rows, chunk] call through
        the suffix-prefill path."""
        while self._job is None:
            free = self._free_slots()
            if not free:
                return
            if self._prefix is not None:
                key = self._prefix_group_key
                want = min(len(free), self.prefill_rows)
            else:
                key = self._group_key if self._bucketed else None
                want = len(free)
            group = self.scheduler.next_group(want, now=self.clock, key=key)
            if not group:
                return
            if not self._bucketed:
                self._admit(group, completed)
                continue
            if self._prefix is not None:
                if not self._admit_prefix_group(group, free, completed):
                    return  # pool pressure even after reclaim
                continue
            admitted = self._reserve_pages(group, free)
            if not admitted:
                return  # pool pressure: wait for residents to free pages
            slot_ids = free[:len(admitted)]
            bucket = self._bucket_for(max(self._eff_len(r)
                                          for r in admitted))
            if self.prefill_chunk and bucket > self.prefill_chunk:
                self._start_job(admitted, slot_ids)
            else:
                self._admit_bucketed(admitted, slot_ids, completed)
            if len(admitted) < len(group):
                return  # backpressured tail is back at the queue head

    # ---------------------------------------------------------------- step

    def step(self) -> list[Completion]:
        """One engine step: advance the chunked-prefill job (if any) by one
        chunk, admit every runnable group into free slots, then run one
        jitted decode chunk (a single host sync). Returns a ``Completion``
        per request finished this step."""
        if self._closed:
            raise RuntimeError("ServeEngine is closed")
        completed: list[Completion] = []
        self._no_preempt.clear()  # last step's admits have their KV by now
        if self._job is not None:
            with obs.span("prefill_chunk"):
                self._job_step(completed)
        with obs.span("admission"):
            self._admission(completed)

        if self.num_active:
            with obs.span("decode_chunk"):
                fn = self._chunk_fn()
                self.cache, self.last_tok, self.finished, self.keys, toks = \
                    fn(self.params, self.cache, self.last_tok, self.finished,
                       self.keys)
                self.stats["decode_chunks"] += 1
                toks = np.asarray(toks)  # [num_slots, chunk] — the host sync
            emitted = 0
            for slot in range(self.num_slots):
                req = self._slot_req[slot]
                if req is None:
                    continue
                for t in toks[slot]:
                    self._out[req.uid].append(int(t))
                    self._left[req.uid] -= 1
                    emitted += 1
                    if ((self.eos_id is not None and int(t) == self.eos_id)
                            or self._left[req.uid] == 0):
                        self._complete(slot, completed)
                        break
            # actual emitted positions, not chunk-granular dispatch width:
            # slots that finish mid-chunk (or decode pad into idle slots)
            # don't inflate the count
            self.stats["decode_steps"] += emitted
        if self._alloc is not None:
            self._g_pages.set(self._alloc.stats()["live_pages"])
        self._g_fn_cache.set(len(_FN_CACHE))
        self.clock += 1
        return completed

    def run(self, requests=()) -> RunResult:
        """Submit ``requests`` and drive steps until queue and slots drain.
        Returns a ``RunResult``: a {uid: generated tokens (ends at EOS if
        hit)} mapping whose ``.completions`` carries the full per-request
        ``Completion`` records."""
        for r in requests:
            self.submit(r)
        comps: dict[int, Completion] = {}
        while self.scheduler.pending or self.num_active or self._job:
            for c in self.step():
                comps[c.uid] = c
        if self._stream is not None:
            self._stream.drain()  # surface stream-out callback errors here
        return RunResult(comps)

    def generate(self, batch: dict, *, max_new_tokens: int) -> np.ndarray:
        """Static-batch convenience: decode ``batch`` (all prompts the same
        length, batch size <= num_slots) and return [B, max_new_tokens] with
        ``pad_id`` after EOS — the legacy ``generate`` output contract. The
        returned array's ``.completions`` holds the ``Completion`` records
        (uid == row index)."""
        b = batch["tokens"].shape[0]
        if b > self.num_slots:
            raise ValueError(f"batch {b} > num_slots {self.num_slots}")
        reqs = [Request(uid=i, tokens=np.asarray(batch["tokens"][i]),
                        max_new_tokens=max_new_tokens,
                        extras={k: np.asarray(batch[k][i]) for k in batch
                                if k != "tokens"})
                for i in range(b)]
        res = self.run(reqs)
        out = np.full((b, max_new_tokens), self.pad_id, np.int32)
        for i in range(b):
            toks = res[i][:max_new_tokens]
            out[i, :len(toks)] = toks
        return TokenBatch.wrap(out, res.completions)

    def close(self) -> None:
        """Tear down the engine. Idempotent; the engine must be drained
        (no residents, no queue, no in-flight prefill job). With a
        ``PrefixStore`` configured, the radix tree, its page references,
        and the k/v page pools are handed to the store under the existing
        refcount contract — every slot is free at this point, so the
        tree's one-ref-per-node references are exactly the pool's live
        pages — and the next engine over the same params + geometry adopts
        them warm. ``step``/``submit`` raise afterwards."""
        if self._closed:
            return
        if self.num_active or self.scheduler.pending or self._job:
            raise RuntimeError(
                f"close() on a busy engine: {self.num_active} residents, "
                f"{self.scheduler.pending} queued, job={'yes' if self._job else 'no'} "
                f"— drain with run()/step() first")
        if self._stream is not None:
            self._stream.close()
            self._stream = None
        if self._store is not None and self._prefix is not None:
            self._store.put(self._store_key, self.params, {
                "k": self.cache["k"], "v": self.cache["v"],
                "alloc": self._alloc, "tree": self._prefix})
        self._closed = True


# ------------------------------------------------------------- public API


def generate(params, cfg: ModelConfig, batch: dict, *, max_new_tokens: int,
             max_len: int | None = None, temperature: float = 0.0,
             rng: jax.Array | None = None, mesh=None, batch_axes=("data",),
             eos_id: int | None = None, num_slots: int | None = None,
             decode_chunk: int = 8):
    """Greedy (temperature=0, the paper's eval protocol) or sampled decoding.
    batch["tokens"]: [B, S_prompt]. Returns np.ndarray [B, max_new_tokens].

    Compat wrapper over ``ServeEngine`` — token-for-token identical to the
    pre-engine loop (``serve/_oracle.py``'s ``generate_legacy``). Sampled
    decoding keeps the legacy path so the historical rng stream (one
    batch-wide categorical per step) is preserved exactly."""
    if temperature > 0:
        from repro.serve._oracle import generate_legacy  # lazy: avoids cycle
        return generate_legacy(params, cfg, batch,
                               max_new_tokens=max_new_tokens, max_len=max_len,
                               temperature=temperature, rng=rng, mesh=mesh,
                               batch_axes=batch_axes, eos_id=eos_id)
    b, s = batch["tokens"].shape
    max_len = max_len or (s + _prompt_prefix(cfg, batch) + max_new_tokens)
    engine = ServeEngine(cfg, params, ServeConfig(
        max_len=max_len, num_slots=num_slots or b, eos_id=eos_id,
        decode_chunk=decode_chunk, mesh=mesh, batch_axes=batch_axes))
    return engine.generate(batch, max_new_tokens=max_new_tokens)

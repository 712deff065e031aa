"""Serving tests: engine-vs-legacy token-exact parity across model families,
per-slot EOS termination, staggered admission vs solo runs, slot insertion,
scheduler policy, the ServeConfig surface (validation + deprecation shim),
grouped prefix admission, and compile-once behavior of the evaluator."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.data.synthetic import MathTaskConfig
from repro.models import registry
from repro.serve import engine as engine_mod
from repro.serve._oracle import generate_legacy
from repro.serve.config import ServeConfig
from repro.serve.engine import ServeEngine, generate
from repro.serve.results import Completion
from repro.serve.scheduler import FCFSScheduler, Request

TINY = ModelConfig(name="tiny-serve", family="dense", num_layers=2,
                   d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                   vocab_size=64, dtype="float32", remat="none")

# one arch per cache family: dense GQA, attention-free ssm, moe
PARITY_ARCHS = ["llama3.2-1b", "mamba2-2.7b", "qwen3-moe-30b-a3b"]


def _params(cfg, seed=0):
    return registry.get(cfg).init(jax.random.PRNGKey(seed), cfg)


def _eng(cfg, params, **kw):
    return ServeEngine(cfg, params, ServeConfig(**kw))


def _prompts(cfg, b, s, seed=1):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)}


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_engine_matches_legacy_generate(arch):
    """Greedy engine decode must be token-for-token identical to the
    pre-engine static-batch loop, with and without EOS termination."""
    cfg = get_smoke_config(arch).replace(ssm_chunk=16)
    params = _params(cfg)
    batch = _prompts(cfg, 3, 16)
    kw = dict(max_new_tokens=10)
    raw_leg = generate_legacy(params, cfg, batch, **kw)
    raw_eng = generate(params, cfg, batch, **kw)
    np.testing.assert_array_equal(raw_eng, raw_leg)
    # pick an EOS id the model actually emits so termination is exercised
    eos = int(raw_leg[0, 4])
    leg = generate_legacy(params, cfg, batch, eos_id=eos, **kw)
    eng = generate(params, cfg, batch, eos_id=eos, **kw)
    np.testing.assert_array_equal(eng, leg)


def test_per_slot_eos_stops_decode_early():
    """EOS terminates a slot on-device: the engine must stop decoding well
    before max_new_tokens when every row hits the attractor token early,
    and still reproduce the legacy (post-hoc masked) outputs."""
    cfg = TINY
    params = _params(cfg)
    # identical prompts -> identical rows -> every slot hits EOS at the
    # same (early) step, so early termination is observable deterministically
    one = _prompts(cfg, 1, 8)["tokens"]
    batch = {"tokens": np.repeat(one, 4, axis=0)}
    raw = generate_legacy(params, cfg, batch, max_new_tokens=32)
    vals, counts = np.unique(raw[0], return_counts=True)
    eos = int(vals[np.argmax(counts)])  # greedy attractor: appears early
    hits = np.flatnonzero(raw[0] == eos)
    assert len(hits) and hits[0] < 16, \
        f"attractor not early enough ({hits[:1]})"

    eng = _eng(cfg, params, max_len=8 + 32, num_slots=4, eos_id=eos,
                      decode_chunk=4)
    out = eng.generate(batch, max_new_tokens=32)
    leg = generate_legacy(params, cfg, batch, max_new_tokens=32, eos_id=eos)
    np.testing.assert_array_equal(out, leg)
    assert eng.stats["decode_steps"] < 32, eng.stats


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-2.7b"])
def test_staggered_admission_matches_solo_runs(arch):
    """Requests with different prompt lengths admitted into free slots as
    others finish must produce exactly the tokens of a solo run."""
    cfg = get_smoke_config(arch).replace(ssm_chunk=16)
    params = _params(cfg)
    rng = np.random.default_rng(3)
    lens = [8, 12, 8, 16, 12]
    arrivals = [0, 0, 1, 3, 4]
    reqs = [Request(uid=i,
                    tokens=rng.integers(0, cfg.vocab_size, (lens[i],)),
                    max_new_tokens=9, arrival=arrivals[i])
            for i in range(len(lens))]
    eng = _eng(cfg, params, max_len=32, num_slots=2, decode_chunk=4)
    shared = eng.run([Request(uid=r.uid, tokens=r.tokens, arrival=r.arrival,
                              max_new_tokens=r.max_new_tokens) for r in reqs])
    assert eng.stats["admitted"] == len(reqs)
    for r in reqs:
        solo_eng = _eng(cfg, params, max_len=32, num_slots=1,
                               decode_chunk=4)
        solo = solo_eng.run([Request(uid=0, tokens=r.tokens,
                                     max_new_tokens=r.max_new_tokens)])
        np.testing.assert_array_equal(shared[r.uid], solo[0],
                                      err_msg=f"request {r.uid}")


def test_insert_slots_writes_rows_at_slot_indices():
    from repro.models import lm
    cfg = TINY
    params = _params(cfg)
    batch = _prompts(cfg, 2, 8)
    _, src = lm.prefill(params, cfg, batch, max_len=16)
    cache = lm.init_cache(cfg, 4, 16)
    out = lm.insert_slots(cache, src, np.array([2, 0], np.int32))
    np.testing.assert_array_equal(np.asarray(out["pos"]), [8, 0, 8, 0])
    np.testing.assert_array_equal(np.asarray(out["k"][:, 2]),
                                  np.asarray(src["k"][:, 0]))
    np.testing.assert_array_equal(np.asarray(out["v"][:, 0]),
                                  np.asarray(src["v"][:, 1]))
    # out-of-range slot index is dropped (used to pad admission groups)
    out2 = lm.insert_slots(cache, src, np.array([1, 4], np.int32))
    np.testing.assert_array_equal(np.asarray(out2["pos"]), [0, 8, 0, 0])
    assert not np.asarray(out2["k"][:, 3]).any()


def test_scheduler_fcfs_same_shape_grouping():
    sch = FCFSScheduler()
    tok = lambda n: np.zeros(n, np.int32)  # noqa: E731
    for uid, (ln, arr) in enumerate([(8, 0), (8, 0), (12, 0), (8, 0),
                                     (8, 5)]):
        sch.submit(Request(uid=uid, tokens=tok(ln), max_new_tokens=4,
                           arrival=arr))
    # same-shape grouping never crosses a different-shape head (FCFS)
    g = sch.next_group(free_slots=4, now=0)
    assert [r.uid for r in g] == [0, 1]
    g = sch.next_group(free_slots=4, now=0)
    assert [r.uid for r in g] == [2]
    # arrival gating: uid 4 hasn't arrived at now=0
    g = sch.next_group(free_slots=4, now=0)
    assert [r.uid for r in g] == [3]
    assert sch.next_group(free_slots=4, now=0) == []
    assert sch.next_group(free_slots=4, now=5) != []
    # free-slot cap
    sch2 = FCFSScheduler()
    for uid in range(5):
        sch2.submit(Request(uid=uid, tokens=tok(8), max_new_tokens=4))
    assert len(sch2.next_group(free_slots=3)) == 3
    assert sch2.next_group(free_slots=0) == []


def test_math_accuracy_chunks_and_compiles_once():
    """Two evaluator runs in one process must not rebuild (or recompile)
    any serving closure, and chunked batching must not change the score."""
    from repro.train.evaluate import math_accuracy
    cfg = TINY
    params = _params(cfg)
    task = MathTaskConfig(digits=2, seq_len=40)
    acc1 = math_accuracy(params, cfg, task, num_problems=8, batch_size=4)
    info1 = engine_mod.fn_cache_info()
    acc2 = math_accuracy(params, cfg, task, num_problems=8, batch_size=4)
    info2 = engine_mod.fn_cache_info()
    assert acc2 == acc1
    assert info2["misses"] == info1["misses"], (info1, info2)
    # each cached closure was jit-compiled for exactly one shape set
    for key, fn in engine_mod._FN_CACHE.items():
        if (key[0] in ("admit", "admitb", "chunk", "pchunk", "pfinal")
                and hasattr(fn, "_cache_size")):
            assert fn._cache_size() == 1, key
    # memory scales with batch_size: a different slot count, same answers
    acc3 = math_accuracy(params, cfg, task, num_problems=8, batch_size=8)
    assert acc3 == acc1


def test_generate_temperature_keeps_legacy_rng_stream():
    cfg = TINY
    params = _params(cfg)
    batch = _prompts(cfg, 2, 8)
    a = generate(params, cfg, batch, max_new_tokens=6, temperature=0.7,
                 rng=jax.random.PRNGKey(5))
    b = generate_legacy(params, cfg, batch, max_new_tokens=6, temperature=0.7,
                        rng=jax.random.PRNGKey(5))
    np.testing.assert_array_equal(a, b)


def test_engine_temperature_sampling_is_per_slot():
    """Sampled decoding draws from per-slot key streams: a request's tokens
    must not depend on what else shares the batch."""
    cfg = TINY
    params = _params(cfg)
    rng = np.random.default_rng(9)
    toks = [rng.integers(0, cfg.vocab_size, (8,)) for _ in range(3)]
    eng = _eng(cfg, params, max_len=24, num_slots=3, temperature=0.8,
                      rng=jax.random.PRNGKey(2))
    full = eng.run([Request(uid=i, tokens=toks[i], max_new_tokens=6)
                    for i in range(3)])
    solo_eng = _eng(cfg, params, max_len=24, num_slots=1,
                           temperature=0.8, rng=jax.random.PRNGKey(2))
    solo = solo_eng.run([Request(uid=1, tokens=toks[1], max_new_tokens=6)])
    np.testing.assert_array_equal(full[1], solo[1])


# ----------------------------------------------------- paged KV + bucketing


def _mixed_requests(cfg, seed=7, max_new=8):
    """Mixed-length staggered workload (the paged/bucketed stress shape).

    The ssm prefill scan needs prompt lengths <= ssm_chunk or a multiple of
    it (pre-existing constraint of the exact-length legacy admit path), so
    the ssm arch gets a compatible length mix.
    """
    rng = np.random.default_rng(seed)
    lens = ([8, 16, 8, 12, 32, 5] if cfg.family == "ssm"
            else [8, 21, 8, 16, 30, 5])
    arrivals = [0, 0, 1, 2, 3, 4]
    toks = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]
    return lambda: [Request(uid=i, tokens=toks[i], max_new_tokens=max_new,
                            arrival=arrivals[i]) for i in range(len(lens))]


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_engine_matches_dense_engine(arch):
    """kv_layout='paged' must be token-exact vs the dense engine for a
    mixed-length staggered workload — including under pool pressure, where
    admission backpressure queues requests until pages free."""
    cfg = get_smoke_config(arch).replace(ssm_chunk=16)
    params = _params(cfg)
    mk = _mixed_requests(cfg)
    kw = dict(max_len=40, num_slots=3, decode_chunk=4)
    dense = _eng(cfg, params, **kw).run(mk())
    peng = _eng(cfg, params, kv_layout="paged", page_size=4, **kw)
    paged = peng.run(mk())
    assert set(paged) == set(dense)
    for uid in dense:
        np.testing.assert_array_equal(paged[uid], dense[uid],
                                      err_msg=f"request {uid}")
    if cfg.family == "ssm":
        assert peng.page_pool_stats() is None  # paging is a no-op
        return
    assert peng.page_pool_stats()["peak_live_pages"] > 0
    assert peng.page_pool_stats()["live_pages"] == 0  # all freed on finish
    # undersized pool: same tokens, strictly smaller cache, backpressure
    seng = _eng(cfg, params, kv_layout="paged", page_size=4,
                       num_pages=12, **kw)
    small = seng.run(mk())
    for uid in dense:
        np.testing.assert_array_equal(small[uid], dense[uid])
    assert seng.kv_cache_bytes() < _eng(cfg, params, **kw).kv_cache_bytes()
    assert seng.stats["backpressure"] > 0


def test_paged_kernel_engine_matches_dense_engine():
    """use_pallas='always' sends paged decode through the Pallas kernel
    (interpreted here, compiled on TPU): still token-exact vs the dense
    engine on the mixed staggered workload."""
    cfg = get_smoke_config("llama3.2-1b").replace(use_pallas="always")
    params = _params(cfg)
    mk = _mixed_requests(cfg)
    kw = dict(max_len=40, num_slots=3, decode_chunk=4)
    dense = _eng(cfg, params, **kw).run(mk())
    paged = _eng(cfg, params, kv_layout="paged", page_size=8, **kw).run(mk())
    for uid in dense:
        np.testing.assert_array_equal(paged[uid], dense[uid],
                                      err_msg=f"request {uid}")


@pytest.mark.parametrize("arch", ["llama3.2-1b", "qwen3-moe-30b-a3b"])
@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_chunked_prefill_matches_single_shot(arch, kv_layout):
    """prefill_chunk=N (interleaved chunked prefill) must reproduce the
    single-shot engine token-for-token."""
    cfg = get_smoke_config(arch).replace(ssm_chunk=16)
    params = _params(cfg)
    mk = _mixed_requests(cfg)
    kw = dict(max_len=40, num_slots=3, decode_chunk=4, kv_layout=kv_layout,
              page_size=4)
    single = _eng(cfg, params, **kw).run(mk())
    ceng = _eng(cfg, params, prefill_chunk=8, **kw)
    chunked = ceng.run(mk())
    assert set(chunked) == set(single)
    for uid in single:
        np.testing.assert_array_equal(chunked[uid], single[uid],
                                      err_msg=f"request {uid}")
    # the len-30 prompt buckets to 32 -> 4 chunks of 8
    assert ceng.stats["prefill_chunks"] >= 4, ceng.stats


def test_prefill_compile_count_bounded_by_buckets():
    """Bucketed admission: many distinct prompt lengths, at most one
    prefill closure per bucket (each jit-compiled for exactly one shape)."""
    cfg = TINY
    params = _params(cfg)
    before = set(engine_mod._FN_CACHE)
    eng = _eng(cfg, params, max_len=48, num_slots=4, decode_chunk=4)
    rng = np.random.default_rng(2)
    reqs = [Request(uid=i, tokens=rng.integers(1, cfg.vocab_size, (n,)),
                    max_new_tokens=6)
            for i, n in enumerate([3, 5, 7, 9, 11, 17, 23, 29, 31, 40])]
    eng.run(reqs)
    new_admits = [k for k in engine_mod._FN_CACHE
                  if k not in before and k[0] == "admitb"]
    assert len(new_admits) <= len(eng.prefill_buckets), (
        new_admits, eng.prefill_buckets)
    for k in new_admits:
        assert engine_mod._FN_CACHE[k]._cache_size() == 1, k


def test_submit_rejects_zero_length_prompt():
    eng = _eng(TINY, _params(TINY), max_len=16, num_slots=1)
    req = Request(uid=0, tokens=np.ones(4, np.int32), max_new_tokens=2)
    req.tokens = np.zeros((0,), np.int32)  # bypass Request validation
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(req)


def test_pool_exhausted_vs_backpressure():
    """A request that can NEVER fit raises PoolExhausted at submit;
    transient pressure only queues (and completes)."""
    from repro.serve.pages import PoolExhausted
    cfg = TINY
    params = _params(cfg)
    eng = _eng(cfg, params, max_len=32, num_slots=4,
                      kv_layout="paged", page_size=4, num_pages=5)
    # 8 prompt + 20 new = 28 positions = 7 pages > 5-page pool
    with pytest.raises(PoolExhausted, match="grow num_pages"):
        eng.submit(Request(uid=9, tokens=np.ones(8, np.int32),
                           max_new_tokens=20))
    # three 4-page requests against 5 pages: admitted one at a time
    rng = np.random.default_rng(4)
    toks = [rng.integers(1, cfg.vocab_size, (8,)) for _ in range(3)]
    res = eng.run([Request(uid=i, tokens=toks[i], max_new_tokens=8)
                   for i in range(3)])
    assert eng.stats["backpressure"] > 0
    deng = _eng(cfg, params, max_len=32, num_slots=4)
    dres = deng.run([Request(uid=i, tokens=toks[i], max_new_tokens=8)
                     for i in range(3)])
    for uid in dres:
        np.testing.assert_array_equal(res[uid], dres[uid])


def test_paged_rejects_unsupported_family():
    cfg = TINY.replace(use_mla=True, kv_lora_rank=16, qk_rope_head_dim=8,
                       qk_nope_head_dim=8, v_head_dim=16)
    with pytest.raises(ValueError, match="paged KV cache is not supported"):
        _eng(cfg, None, max_len=16, num_slots=1, kv_layout="paged")


def test_fn_cache_lru_eviction():
    """The compiled-fn cache is a bounded LRU: over-limit inserts evict the
    coldest entry and count it."""
    from repro.serve.engine import make_prefill_fn, set_fn_cache_limit
    old_limit = engine_mod._FN_LIMIT
    try:
        set_fn_cache_limit(2)
        ev0 = engine_mod.fn_cache_info()["evictions"]
        for ml in (101, 102, 103, 104):
            make_prefill_fn(TINY, ml)
        info = engine_mod.fn_cache_info()
        assert info["size"] <= 2
        assert info["evictions"] > ev0
        # most-recent key survives, oldest was evicted
        assert any(k[0] == "prefill" and k[2] == 104
                   for k in engine_mod._FN_CACHE)
        assert not any(k[0] == "prefill" and k[2] == 101
                       for k in engine_mod._FN_CACHE)
    finally:
        set_fn_cache_limit(old_limit)


# ------------------------------------- radix prefix cache + preemption


def _shared_prefix_requests(cfg, page_size=8, seed=11, max_new=6):
    """GSM8K-style workload: one shared few-shot prefix (3 full pages),
    per-request question suffixes, plus an exact page-aligned duplicate of
    the prefix (the full-prompt-match COW case). Arrivals staggered so
    earlier completions populate the radix tree before later admissions."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size, (3 * page_size,)).astype(np.int32)
    toks = [np.concatenate([prefix,
                            rng.integers(1, cfg.vocab_size, (s,))
                            .astype(np.int32)])
            for s in (5, 9, 2)]
    toks.append(prefix.copy())  # full-prompt match -> copy-on-write
    return lambda: [Request(uid=i, tokens=toks[i], max_new_tokens=max_new,
                            arrival=i) for i in range(len(toks))]


def test_prefix_cache_token_exact_and_suffix_only_prefill():
    """Prefix-on must be token-exact vs prefix-off (paged) AND vs the dense
    engine on a shared-prefix workload — including the full-prompt-match
    COW case — while prefilling only the uncached suffixes."""
    cfg = TINY
    params = _params(cfg)
    mk = _shared_prefix_requests(cfg)
    kw = dict(max_len=48, num_slots=1, decode_chunk=4, min_bucket=8)
    pkw = dict(kv_layout="paged", page_size=8, num_pages=32, **kw)
    dense = _eng(cfg, params, **kw).run(mk())
    off_eng = _eng(cfg, params, **pkw)
    off = off_eng.run(mk())
    on_eng = _eng(cfg, params, prefix_cache=True, **pkw)
    on = on_eng.run(mk())
    assert set(on) == set(off) == set(dense)
    for uid in dense:
        np.testing.assert_array_equal(on[uid], dense[uid],
                                      err_msg=f"request {uid} (vs dense)")
        np.testing.assert_array_equal(on[uid], off[uid],
                                      err_msg=f"request {uid} (vs off)")
    # num_slots=1 -> each completion lands in the tree before the next
    # admission: uids 1..3 all hit the 24-token cached prefix
    assert on_eng.stats["prefix_hits"] == 3, on_eng.stats
    assert on_eng.stats["prefix_pages_shared"] > 0
    assert off_eng.stats["prefix_hits"] == 0
    # suffix-only prefill: true-token accounting shows the saving (uid 3 is
    # a full-prompt match -> 1-token COW prefill instead of 24)
    assert on_eng.stats["prefill_tokens"] < off_eng.stats["prefill_tokens"]
    saved = sum(3 * 8 for _ in range(2)) + (3 * 8 - 1)  # uids 1,2 + COW uid 3
    assert (off_eng.stats["prefill_tokens"]
            - on_eng.stats["prefill_tokens"]) == saved
    # every slot released its pages: the only live pages left are the
    # radix tree's cached prefixes
    assert (on_eng.page_pool_stats()["live_pages"]
            == on_eng._prefix.cached_pages)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_preempt_and_requeue_token_exact(temperature):
    """Preempted + re-admitted requests must reproduce the never-preempted
    run token-for-token — greedy and sampled (per-slot key streams carry
    across preemption)."""
    cfg = TINY
    params = _params(cfg)
    rng = np.random.default_rng(13)
    lens = [8, 8, 8, 8, 8]
    news = [4, 12, 6, 12, 8]  # uneven budgets: victim = most remaining
    toks = [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]
    mk = lambda: [Request(uid=i, tokens=toks[i],  # noqa: E731
                          max_new_tokens=news[i]) for i in range(len(lens))]
    kw = dict(max_len=32, num_slots=4, decode_chunk=4, min_bucket=8,
              kv_layout="paged", page_size=4, temperature=temperature,
              rng=jax.random.PRNGKey(6))
    ample = _eng(cfg, params, num_pages=40, **kw).run(mk())
    peng = _eng(cfg, params, num_pages=6, preempt=True, **kw)
    pre = peng.run(mk())
    assert set(pre) == set(ample)
    for uid in ample:
        np.testing.assert_array_equal(pre[uid], ample[uid],
                                      err_msg=f"request {uid}")
    assert peng.stats["preempted"] >= 1, peng.stats
    assert peng.page_pool_stats()["live_pages"] == 0


def test_prefix_cache_with_preemption_token_exact():
    """The combined path — prefix-accelerated re-admission of preempted
    requests over an oversubscribed pool — stays token-exact vs dense."""
    cfg = TINY
    params = _params(cfg)
    mk = _shared_prefix_requests(cfg, max_new=8)
    kw = dict(max_len=48, num_slots=3, decode_chunk=4, min_bucket=8)
    dense = _eng(cfg, params, **kw).run(mk())
    eng = _eng(cfg, params, kv_layout="paged", page_size=8,
                      num_pages=10, prefix_cache=True, preempt=True, **kw)
    out = eng.run(mk())
    for uid in dense:
        np.testing.assert_array_equal(out[uid], dense[uid],
                                      err_msg=f"request {uid}")
    # only the radix tree's cached prefixes remain live, within the pool
    assert (eng.page_pool_stats()["live_pages"]
            == eng._prefix.cached_pages)
    assert eng.page_pool_stats()["high_water_pages"] <= 10


def test_stream_out_matches_run_and_propagates_errors():
    """on_complete fires off the hot loop with a ``Completion`` record for
    every finished request, carrying exactly run()'s tokens; a raising
    callback surfaces from run() (via drain) instead of being swallowed on
    the worker thread."""
    cfg = TINY
    params = _params(cfg)
    mk = _mixed_requests(cfg, max_new=4)
    got = {}
    eng = _eng(cfg, params, max_len=40, num_slots=3, decode_chunk=4,
               on_complete=lambda c: got.__setitem__(c.uid, c))
    res = eng.run(mk())
    assert set(got) == set(res)
    for uid in res:
        assert isinstance(got[uid], Completion)
        np.testing.assert_array_equal(got[uid].tokens, res[uid])
        assert got[uid].finish_reason in ("eos", "length")
        assert got[uid].done_step >= got[uid].first_token_step

    def boom(comp):
        raise RuntimeError("detok failed")

    beng = _eng(cfg, params, max_len=40, num_slots=3, decode_chunk=4,
                on_complete=boom)
    with pytest.raises(RuntimeError, match="detok failed"):
        beng.run(mk())


# ------------------------------- ServeConfig surface + grouped admission


def test_serve_config_validation():
    with pytest.raises(ValueError, match="decode_chunk"):
        ServeConfig(max_len=32, num_slots=2, decode_chunk=0)
    with pytest.raises(ValueError, match="min_bucket"):
        ServeConfig(max_len=32, num_slots=2, min_bucket=12)
    with pytest.raises(ValueError, match="prefix_cache"):
        ServeConfig(max_len=32, num_slots=2, prefix_cache=True)
    with pytest.raises(ValueError, match="admission"):
        ServeConfig(max_len=32, num_slots=2, admission="lifo")
    with pytest.raises(ValueError, match="prefix_aware"):
        ServeConfig(max_len=32, num_slots=2, admission="prefix_aware")
    with pytest.raises(ValueError, match="prefix_store"):
        ServeConfig(max_len=32, num_slots=2,
                    prefix_store=object())


def test_legacy_kwargs_shim_warns_and_matches_new_surface():
    """ServeEngine(cfg, params, **kwargs) still works for one release: it
    warns, builds the same ServeConfig, and wraps a legacy (uid, tokens)
    on_complete callback."""
    cfg = TINY
    params = _params(cfg)
    mk = _mixed_requests(cfg, max_new=4)
    got = {}
    with pytest.warns(DeprecationWarning, match="ServeConfig"):
        leg_eng = ServeEngine(cfg, params, max_len=40, num_slots=3,
                              decode_chunk=4,
                              on_complete=lambda uid, t:
                              got.__setitem__(uid, t))
    assert leg_eng.serve_cfg.max_len == 40
    leg = leg_eng.run(mk())
    new = _eng(cfg, params, max_len=40, num_slots=3, decode_chunk=4).run(mk())
    assert set(leg) == set(new) == set(got)
    for uid in new:
        np.testing.assert_array_equal(leg[uid], new[uid])
        np.testing.assert_array_equal(got[uid], new[uid])
    with pytest.raises(TypeError, match="both a ServeConfig"):
        ServeEngine(cfg, params, ServeConfig(max_len=40, num_slots=1),
                    decode_chunk=4)


def test_run_result_carries_completions():
    cfg = TINY
    params = _params(cfg)
    mk = _mixed_requests(cfg, max_new=4)
    res = _eng(cfg, params, max_len=40, num_slots=3, decode_chunk=4).run(mk())
    assert set(res.completions) == set(res)
    for uid, comp in res.completions.items():
        assert comp.uid == uid
        np.testing.assert_array_equal(comp.tokens, res[uid])
        assert comp.finish_reason == "length"  # no eos_id configured


def test_engine_close_is_terminal():
    cfg = TINY
    params = _params(cfg)
    eng = _eng(cfg, params, max_len=16, num_slots=1)
    eng.close()
    eng.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(Request(uid=0, tokens=np.ones(4, np.int32),
                           max_new_tokens=2))
    with pytest.raises(RuntimeError, match="closed"):
        eng.step()


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_grouped_prefix_admission_token_exact(temperature):
    """Same-start grouped admission (prefill_rows > 1: one [rows, bucket]
    suffix prefill per wave) must reproduce one-request-per-call admission
    token-for-token — greedy AND sampled (per-slot key streams make the
    grouping invisible) — with identical suffix-only prefill_tokens but
    fewer prefill dispatches."""
    cfg = TINY
    params = _params(cfg)
    rng = np.random.default_rng(17)
    prefix = rng.integers(1, cfg.vocab_size, (16,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, cfg.vocab_size, (5,))
                               .astype(np.int32)])
               for _ in range(8)]
    kw = dict(max_len=32, num_slots=4, decode_chunk=4, min_bucket=8,
              kv_layout="paged", page_size=8, num_pages=32,
              prefix_cache=True, temperature=temperature,
              rng=jax.random.PRNGKey(3))
    mk = lambda: [Request(uid=i, tokens=prompts[i],  # noqa: E731
                          max_new_tokens=6) for i in range(len(prompts))]
    one_eng = _eng(cfg, params, prefill_rows=1, **kw)
    one = one_eng.run(mk())
    grp_eng = _eng(cfg, params, prefill_rows=4, **kw)
    grp = grp_eng.run(mk())
    assert set(grp) == set(one)
    for uid in one:
        np.testing.assert_array_equal(grp[uid], one[uid],
                                      err_msg=f"request {uid}")
    # same suffix-only token accounting, fewer dispatches
    assert grp_eng.stats["prefill_tokens"] == one_eng.stats["prefill_tokens"]
    assert grp_eng.stats["prefills"] < one_eng.stats["prefills"]
    assert grp_eng.stats["prefix_hits"] == one_eng.stats["prefix_hits"] > 0


def test_prefix_aware_admission_token_exact_vs_fcfs():
    """admission='prefix_aware' may only reorder admissions, never change
    tokens: every request must complete with exactly its strict-FCFS
    output (per-slot key streams make order invisible to sampling)."""
    cfg = TINY
    params = _params(cfg)
    rng = np.random.default_rng(23)
    prefix = rng.integers(1, cfg.vocab_size, (16,)).astype(np.int32)
    toks = []
    for i in range(10):
        tail = rng.integers(1, cfg.vocab_size, (3 + (i % 4),))
        toks.append(np.concatenate([prefix, tail]).astype(np.int32)
                    if i % 2 == 0 else
                    rng.integers(1, cfg.vocab_size,
                                 (16 + (i % 5),)).astype(np.int32))
    mk = lambda: [Request(uid=i, tokens=toks[i],  # noqa: E731
                          max_new_tokens=5, arrival=i // 3)
                  for i in range(len(toks))]
    kw = dict(max_len=32, num_slots=2, decode_chunk=4, min_bucket=8,
              kv_layout="paged", page_size=8, num_pages=24,
              prefix_cache=True, prefix_cache_pages=6)
    fcfs = _eng(cfg, params, admission="fcfs", **kw).run(mk())
    pa_eng = _eng(cfg, params, admission="prefix_aware",
                  admission_max_skips=3, **kw)
    pa = pa_eng.run(mk())
    assert set(pa) == set(fcfs)  # nobody starves
    for uid in fcfs:
        np.testing.assert_array_equal(pa[uid], fcfs[uid],
                                      err_msg=f"request {uid}")


def test_insert_slots_paged_routes_through_table():
    """Rows land on their table's pages; pad slots and positions past the
    row's length are dropped; pool pages of other slots are untouched."""
    from repro.models import lm
    cfg = TINY
    params = _params(cfg)
    batch = _prompts(cfg, 2, 8)
    _, src = lm.prefill(params, cfg, batch, max_len=8)
    ps, num_pages = 4, 6
    cache = lm.init_paged_cache(cfg, 3, 16, ps, num_pages)
    table = np.full((3, 4), num_pages, np.int32)
    table[2, :2] = [5, 1]   # slot 2: pages 5 then 1
    table[0, :2] = [0, 3]
    cache = {**cache, "pages": jax.numpy.asarray(table)}
    out = lm.insert_slots_paged(cache, src, np.array([2, 3], np.int32),
                                np.array([6, 8], np.int32))
    np.testing.assert_array_equal(np.asarray(out["pos"]), [0, 0, 6])
    # slot 2 row 0: positions 0..3 -> page 5, positions 4..5 -> page 1
    # (pool pages are [KVH, page_size, Dh]; the prefill cache is [S, KVH, Dh])
    heads_first = lambda x: np.asarray(x).transpose(0, 2, 1, 3)  # noqa: E731
    np.testing.assert_array_equal(np.asarray(out["k"][:, 5]),
                                  heads_first(src["k"][:, 0, :ps]))
    np.testing.assert_array_equal(np.asarray(out["k"][:, 1, :, :2]),
                                  heads_first(src["k"][:, 0, ps:ps + 2]))
    # positions >= length (6,7) dropped; row 1 (pad slot 3) dropped entirely
    assert not np.asarray(out["k"][:, 1, :, 2:]).any()
    for pg in (0, 2, 3, 4):
        assert not np.asarray(out["k"][:, pg]).any(), pg

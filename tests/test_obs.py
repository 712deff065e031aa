"""Observability layer: histogram quantile accuracy + bounded memory,
counter/histogram thread-safety, trace-event well-formedness, the
obs-on/obs-off bit-identical-trajectory contract, selection telemetry
agreeing with optimizer counts, the exploration->exploitation report, the
banked/serve trace structure (the banked boundary's nested spans and its
byte counters), the compile counter, and the serve engine's consolidated
``stats_snapshot()`` (including the decode_steps accounting)."""
import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro import obs
from repro.configs.base import (ModelConfig, OptimizerConfig, SelectConfig,
                                TrainConfig)
from repro.obs import report
from repro.obs.registry import Counter, Histogram
from repro.obs.selection import SelectionTrace
from repro.obs.trace import Tracer, validate_trace, validate_trace_file
from repro.train.trainer import Trainer

# vocab >= 32 so the synthetic-math token space fits (finite losses)
TINY = ModelConfig(name="obs-tiny", family="dense", num_layers=4,
                   d_model=16, num_heads=2, num_kv_heads=2, head_dim=8,
                   d_ff=32, vocab_size=32, dtype="float32", remat="none",
                   tie_embeddings=False)


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with tracing off (obs.metrics is
    process-global by design; instruments are additive and harmless)."""
    obs.disable()
    yield
    obs.disable()


def _tcfg(method="adagradselect", residency="banked", steps=6,
          async_swap=True, steps_per_epoch=3):
    return TrainConfig(
        model=TINY, method=method,
        select=SelectConfig(k_percent=40, steps_per_epoch=steps_per_epoch,
                            epsilon_decay=0.1),
        optimizer=OptimizerConfig(
            lr=1e-3, schedule="constant", warmup_steps=0,
            moment_residency=residency,
            offload="host" if residency == "banked" else "none",
            async_swap=async_swap, total_steps=steps),
        seq_len=48, global_batch=4, steps=steps, seed=0, log_every=0)


# --------------------------------------------------------------- histogram
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31),
       n=st.integers(min_value=1, max_value=400),
       scale=st.sampled_from([1e-3, 1.0, 1e3, 1e6]))
def test_histogram_quantiles_match_numpy(seed, n, scale):
    """p50/p95/p99 land within the documented ~4.4% bucket error of the
    nearest-rank (numpy 'lower') order statistic."""
    rng = np.random.default_rng(seed)
    # stay inside the bucketed range [2**-16, 2**48] (values beyond it
    # clamp to the edge buckets; the instrument's unit is microseconds)
    xs = np.clip(rng.lognormal(mean=0.0, sigma=2.0, size=n) * scale,
                 2.0**-12, 2.0**44)
    h = Histogram()
    for x in xs:
        h.record(x)
    srt = np.sort(xs)
    for q in (0.0, 0.5, 0.95, 0.99, 1.0):
        want = srt[int(np.floor(q * (n - 1)))]
        got = h.quantile(q)
        assert got == pytest.approx(want, rel=0.05), (q, got, want)
    assert h.count == n
    assert h.mean == pytest.approx(float(np.mean(xs)), rel=1e-9)
    assert h.min == pytest.approx(float(srt[0]))
    assert h.max == pytest.approx(float(srt[-1]))


def test_histogram_bounded_memory_and_extremes():
    h = Histogram()
    for v in (0.0, -5.0, 1e-30, 1e30, 7.0):
        h.record(v)
    # bucket storage is a fixed-size array regardless of value range
    assert len(h._counts) == Histogram.num_buckets
    assert h.count == 5
    assert h.quantile(0.0) == 0.0  # negatives/zero collapse to zero bucket
    s = h.summary()
    assert set(s) >= {"count", "mean", "p50", "p95", "p99", "min", "max"}
    assert Histogram().summary() == {"count": 0}
    assert Histogram().quantile(0.5) == 0.0


def test_counter_and_histogram_thread_safety():
    c = Counter()
    h = Histogram()
    n, per = 8, 2000

    def work():
        for i in range(per):
            c.inc()
            h.record(float(i + 1))

    threads = [threading.Thread(target=work) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n * per
    assert h.count == n * per
    assert h.total == pytest.approx(n * per * (per + 1) / 2)


def test_registry_snapshot_shapes_and_register_semantics():
    reg = obs.MetricsRegistry()
    reg.counter("a", subsystem="s1").inc(3)
    reg.gauge("g", subsystem="s1").set(2.5)
    reg.histogram("h", subsystem="s2").record(1.0)
    reg.register("cb", lambda: {"x": 1}, subsystem="s2")
    snap = reg.snapshot()
    assert snap["s1"]["a"] == 3 and snap["s1"]["g"] == 2.5
    assert snap["s2"]["h"]["count"] == 1
    assert snap["s2"]["cb"] == {"x": 1}
    json.dumps(snap)  # JSON-able end to end
    # last-writer-wins + failing callables render as an error value
    reg.register("cb", lambda: 1 / 0, subsystem="s2")
    assert "error" in reg.snapshot()["s2"]["cb"]
    # same key returns the same instrument
    assert reg.counter("a", subsystem="s1") is reg.counter("a",
                                                           subsystem="s1")


# ------------------------------------------------------------------ tracer
def test_trace_events_well_formed(tmp_path):
    tr = obs.enable()
    with obs.span("outer", {"k": 1}):
        with obs.span("inner"):
            pass
        obs.instant("tick", {"n": 2})
    t0 = tr._t0_ns
    tr.complete("retro", t0 + 1000, t0 + 5000, track="lane A")
    path = tmp_path / "t.json"
    obs.export_trace(str(path))
    events = validate_trace_file(str(path))
    by_ph = {}
    for e in events:
        by_ph.setdefault(e["ph"], []).append(e)
    assert [e["name"] for e in by_ph["B"]] == ["outer", "inner"]
    assert [e["name"] for e in by_ph["E"]] == ["inner", "outer"]
    assert by_ph["i"][0]["name"] == "tick"
    (x,) = by_ph["X"]
    assert x["name"] == "retro" and x["dur"] == pytest.approx(4.0)
    # the synthetic track got a thread_name metadata event
    assert any(e["ph"] == "M" and e["args"]["name"] == "lane A"
               for e in events)


def test_validate_trace_rejects_malformed():
    ok = [{"ph": "B", "name": "a", "pid": 0, "tid": 1, "ts": 1.0},
          {"ph": "E", "name": "a", "pid": 0, "tid": 1, "ts": 2.0}]
    validate_trace(ok)
    with pytest.raises(AssertionError):  # unterminated span
        validate_trace(ok[:1])
    with pytest.raises(AssertionError):  # mismatched E name
        validate_trace([ok[0], {**ok[1], "name": "b"}])
    with pytest.raises(AssertionError):  # time going backwards on one tid
        validate_trace([{**ok[0], "ts": 5.0}, ok[1]])
    with pytest.raises(AssertionError):  # unknown phase
        validate_trace([{**ok[0], "ph": "Q"}])


def test_tracer_bounded_buffer_drops_not_grows():
    tr = Tracer(max_events=10)
    for i in range(50):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 10
    assert tr.dropped == 41  # 50 instants + 1 thread_name metadata - 10 kept


def test_noop_span_when_disabled():
    assert not obs.enabled()
    assert obs.span("anything") is obs.NOOP_SPAN
    obs.instant("ignored")  # must not raise
    with pytest.raises(RuntimeError):
        obs.export_trace("/tmp/never.json")


def test_timed_records_histogram_always_and_span_only_when_on():
    h = Histogram()
    with obs.timed(h, "work"):
        pass
    assert h.count == 1  # histogram fed even with tracing off
    tr = obs.enable()
    with obs.timed(h, "work"):
        pass
    assert h.count == 2
    names = [e["name"] for e in tr.events() if e["ph"] == "B"]
    assert names == ["work"]


# ------------------------------------------------- trainer contract + trace
@pytest.mark.parametrize("residency", ["device", "banked"])
def test_obs_on_off_trajectories_bit_identical(residency):
    log_off = Trainer(_tcfg(residency=residency)).train()
    obs.enable()
    log_on = Trainer(_tcfg(residency=residency)).train()
    assert log_on.losses == log_off.losses


def test_selection_trace_reproduces_opt_counts_every_boundary():
    """The telemetry counts must equal state["opt"]["counts"] after EVERY
    step, not just at the end — train one step at a time and compare."""
    obs.enable()
    tr = Trainer(_tcfg(residency="banked", steps=6))
    sel = obs.selection_trace()
    for i in range(6):
        tr.train(steps=1, start_step=i)
        np.testing.assert_array_equal(
            sel.counts, np.asarray(tr.state["opt"]["counts"], np.float64))
    assert len(sel) == 6
    assert sel.masks().shape == (6, sel.num_blocks)


def test_banked_train_trace_has_phases_and_swap_thread(tmp_path):
    obs.enable()
    Trainer(_tcfg(residency="banked", steps=6, async_swap=True)).train()
    path = tmp_path / "train.json"
    obs.export_trace(str(path))
    events = validate_trace_file(str(path))
    b_names = {e["name"] for e in events if e["ph"] == "B"}
    assert {"train_step", "phase_a", "swap", "phase_b"} <= b_names
    # the background boundary dispatch runs on its own named track
    threads = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert any(t.startswith("swap-planner") for t in threads), threads
    planner_tids = {e["tid"] for e in events
                    if e["ph"] == "M"
                    and e["args"]["name"].startswith("swap-planner")}
    assert any(e["ph"] == "B" and e["name"] == "swap_dispatch_job"
               and e["tid"] in planner_tids for e in events)


def _parents(events) -> dict:
    """{span name: names of the spans directly around it on its thread,
    None where it opens at the top}."""
    stacks, out = {}, {}
    for e in events:
        if e["ph"] == "B":
            stack = stacks.setdefault(e["tid"], [])
            out.setdefault(e["name"], set()).add(stack[-1] if stack else None)
            stack.append(e["name"])
        elif e["ph"] == "E":
            stacks[e["tid"]].pop()
    return out


def test_banked_trace_nests_the_boundary_spans(tmp_path):
    obs.enable()
    Trainer(_tcfg(residency="banked", steps=6, async_swap=True)).train()
    path = tmp_path / "train.json"
    obs.export_trace(str(path))
    events = validate_trace_file(str(path))
    parents = _parents(events)
    assert parents["index_sync"] == {"phase_a"}
    assert parents["swap_join"] == {"swap"}
    assert parents["swap_commit"] == {"swap"}  # the prediction hits
    assert parents["swap_sync"] == {"swap"}    # the first step has no job
    assert parents["data_wait"] == {None}      # before train_step opens
    planner = {e["tid"] for e in events if e["ph"] == "M"
               and e["args"]["name"].startswith("swap-planner")}
    for name in ("swap_admit", "swap_evict"):
        assert parents[name] <= {"swap_dispatch_job", "swap_sync"}
        assert any(e["ph"] == "B" and e["name"] == name
                   and e["tid"] in planner for e in events), name
        args = [e["args"] for e in events
                if e["ph"] == "B" and e["name"] == name]
        assert all(set(a) == {"group", "blocks", "bytes"} for a in args)


@pytest.mark.parametrize("async_swap", [True, False])
def test_swap_bytes_are_the_rows_of_the_plans_that_ran(monkeypatch,
                                                       async_swap):
    from repro.core import masked_adamw as ma
    ran = []
    prefetch = ma.prefetch_admissions

    def recorded(plans, store, staging=None):
        ran.extend(plans)
        return prefetch(plans, store, staging)

    monkeypatch.setattr(ma, "prefetch_admissions", recorded)
    tr = Trainer(_tcfg(residency="banked", steps=6, async_swap=async_swap))
    tr.train()

    def row_bytes(plan):
        # a block's float32 first and second moments, from the params
        leaves = jax.tree.leaves(tr.state["params"][plan.key])
        return 2 * 4 * sum(int(np.prod(x.shape[1:] if plan.stacked
                                       else x.shape)) for x in leaves)

    stats = tr.step_fn.swap_stats
    admit = sum(len(p.ad_blocks) * row_bytes(p) for p in ran)
    evict = sum(len(p.ev_blocks) * row_bytes(p) for p in ran)
    assert admit > 0 and evict > 0
    assert stats.admit_bytes.value == admit
    assert stats.evict_bytes.value == evict
    assert stats.admit.count == stats.evict.count > 0


def test_evictions_are_gathered_first_and_copied_once_ready(monkeypatch):
    """The planner dispatches the evicted rows' gathers before the
    admissions, and ``swap_evict`` (``writeback_evictions``) starts with
    them ready, so its time holds the copy to the host and not the wait."""
    from repro.core import masked_adamw as ma
    calls = []
    gather, prefetch, store = (ma.gather_evictions, ma.prefetch_admissions,
                               ma.writeback_evictions)

    def gathered(plans, banks):
        calls.append("gather")
        return gather(plans, banks)

    def prefetched(plans, st, staging=None):
        calls.append("admit")
        return prefetch(plans, st, staging)

    def stored(plans, rows, st):
        calls.append("evict")
        assert all(r.is_ready() for r in rows)
        return store(plans, rows, st)

    monkeypatch.setattr(ma, "gather_evictions", gathered)
    monkeypatch.setattr(ma, "prefetch_admissions", prefetched)
    monkeypatch.setattr(ma, "writeback_evictions", stored)
    Trainer(_tcfg(residency="banked", steps=6, async_swap=True)).train()
    assert calls and calls == ["gather", "admit", "evict"] * (len(calls) // 3)


def test_last_tracer_keeps_the_finished_spans():
    tr = obs.enable()
    with obs.span("outer", {"bytes": [3]}):
        with obs.span("inner"):
            pass
    with obs.span("open"):
        obs.disable()
    assert obs.tracer() is None and obs.last_tracer() is tr
    (outer,), (inner,) = tr.spans("outer"), tr.spans("inner")
    assert outer["args"] == {"bytes": [3]} and inner["args"] == {}
    assert outer["ts"] <= inner["ts"] and outer["dur"] >= inner["dur"] >= 0
    assert tr.spans("absent") == []
    obs.disable()  # a second disable keeps the last tracer
    assert obs.last_tracer() is tr


def test_compiles_counts_each_program_once_and_marks_the_timeline():
    obs.count_compiles()
    obs.count_compiles()  # a second call adds no second listener
    compiles = obs.metrics.counter("compiles", subsystem="jax")
    x, y = jnp.arange(7.0), jnp.arange(5.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    n0 = compiles.value
    f(x).block_until_ready()
    assert compiles.value == n0 + 1
    f(x).block_until_ready()  # the same shape: no new program
    assert compiles.value == n0 + 1
    tr = obs.enable()
    f(y).block_until_ready()
    assert compiles.value == n0 + 2
    marks = [e for e in tr.events() if e["ph"] == "i"]
    assert [e["name"] for e in marks] == ["compile"]
    assert marks[0]["args"]["s"] > 0


_CACHED = """
import jax, jax.numpy as jnp
from repro import obs
obs.count_compiles()
asked = []
jax.monitoring.register_event_listener(lambda name, **kw: asked.append(
    name) if name == "/jax/compilation_cache/compile_requests_use_cache"
    else None)
x = jnp.ones((4,))
for f in (lambda v: v + 1, lambda v: v * 2):
    jax.jit(f)(x).block_until_ready()
print(obs.metrics.counter("compiles", subsystem="jax").value, len(asked))
"""


def test_compiles_agree_with_the_persistent_cache_requests(tmp_path):
    """With JAX's persistent cache on, a miss (first process) and a hit
    (second) each count once, as the cache's own request event does."""
    import repro
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "PYTHONPATH": str(Path(repro.__file__).parents[1])}
    counts = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", _CACHED], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        counts.append(tuple(map(int, p.stdout.split())))
    assert counts[0] == counts[1]
    assert counts[0][0] == counts[0][1] > 0
    assert any((tmp_path / "cache").iterdir())


# -------------------------------------------------------- selection report
@pytest.mark.parametrize("method", ["adagradselect", "lisa", "grass"])
def test_selection_report_renders_per_method(method):
    obs.enable()
    Trainer(_tcfg(method=method, residency="device", steps=8)).train()
    sel = obs.selection_trace()
    assert len(sel) == 8
    out = report.render_selection_trace(sel, bins=4)
    assert "selection heatmap" in out
    assert "entropy" in out
    for b in range(sel.num_blocks):
        assert f"block {b:3d}" in out


def test_report_summarize_and_edge_cases():
    masks = np.zeros((10, 4), bool)
    masks[:5, 0] = True   # block 0 early only
    masks[5:, 1] = True   # block 1 late only
    s = report.summarize(masks, bins=2)
    assert s["rates"].shape == (4, 2)
    assert s["rates"][0].tolist() == [1.0, 0.0]
    assert s["rates"][1].tolist() == [0.0, 1.0]
    with pytest.raises(ValueError):
        report.summarize(np.zeros(3), bins=2)
    empty = report.render_selection_trace(SelectionTrace())
    assert "no steps recorded" in empty


def test_selection_snapshot_roundtrip():
    sel = SelectionTrace()
    rng = np.random.default_rng(0)
    for step in range(5):
        sel.record(step, rng.integers(0, 2, 7).astype(bool),
                   rng.random(7))
    doc = json.loads(json.dumps(sel.snapshot()))
    back = SelectionTrace.from_snapshot(doc)
    np.testing.assert_array_equal(back.counts, sel.counts)
    np.testing.assert_array_equal(back.masks(), sel.masks())
    np.testing.assert_allclose(back.norms(), sel.norms())


# ------------------------------------------------------------------- serve
SERVE_TINY = ModelConfig(name="tiny-serve-obs", family="dense", num_layers=2,
                         d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
                         vocab_size=64, dtype="float32", remat="none")


def _serve(new_tokens=10, decode_chunk=4, num_requests=3, **kw):
    from repro.models import registry
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine
    from repro.serve.scheduler import Request

    params = registry.get(SERVE_TINY).init(jax.random.PRNGKey(0), SERVE_TINY)
    rng = np.random.default_rng(1)
    eng = ServeEngine(SERVE_TINY, params,
                      ServeConfig(max_len=64, num_slots=4,
                                  decode_chunk=decode_chunk, **kw))
    reqs = [Request(uid=i,
                    tokens=rng.integers(1, 64, (8 + i,)).astype(np.int32),
                    max_new_tokens=new_tokens, arrival=i)
            for i in range(num_requests)]
    res = eng.run(reqs)
    return eng, res


def test_serve_stats_snapshot_structure():
    eng, res = _serve()
    snap = eng.stats_snapshot()
    assert set(snap) == {"engine", "latency_us", "pages", "scheduler",
                        "prefix_cache", "stream_out", "fn_cache"}
    lat = snap["latency_us"]
    assert set(lat) == {"queue_wait", "ttft", "tpot", "e2e"}
    for h in lat.values():
        assert h["count"] == 3  # one sample per completed request
        assert h["p50"] > 0
    assert snap["engine"]["completed"] == 3
    assert snap["pages"] is None  # dense layout
    assert snap["scheduler"]["pending"] == 0
    assert snap["fn_cache"]["size"] > 0
    json.dumps(snap)


def test_decode_steps_counts_emitted_positions():
    """Prefill emits token 1; decode emits the remaining max_new - 1 — per
    request — regardless of decode_chunk granularity (the old accounting
    added decode_chunk per dispatched chunk)."""
    for chunk in (3, 4):
        eng, res = _serve(new_tokens=10, decode_chunk=chunk,
                          num_requests=3)
        assert all(len(t) == 10 for t in res.values())
        assert eng.stats["decode_steps"] == 3 * 9, (
            chunk, eng.stats["decode_steps"])


def test_serve_trace_per_request_lanes(tmp_path):
    obs.enable(selection=False)
    eng, res = _serve()
    path = tmp_path / "serve.json"
    obs.export_trace(str(path))
    events = validate_trace_file(str(path))
    xs = [e for e in events if e["ph"] == "X"]
    lanes = {e["tid"]: [] for e in xs}
    for e in xs:
        lanes[e["tid"]].append(e["name"])
    # one synthetic lane per request, each carrying ttft + e2e
    assert len(lanes) == 3
    for names in lanes.values():
        assert sorted(names) == ["e2e", "ttft"]
    track_names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"request 0", "request 1", "request 2"} <= track_names
    b_names = {e["name"] for e in events if e["ph"] == "B"}
    assert {"admission", "decode_chunk"} <= b_names


def test_swap_stats_as_dict_views_histograms():
    """SwapStats timing fields are views over the obs histograms (satellite
    1: one timing source of truth, bench JSON schema unchanged)."""
    tr = Trainer(_tcfg(residency="banked", steps=6))
    tr.train()
    stats = tr.step_fn.swap_stats
    d = stats.as_dict()
    assert set(d) >= {"steps", "boundaries", "predicted_hits", "sync_swaps",
                      "dispatches", "phase_a_us", "swap_us", "phase_b_us",
                      "predicted_hit_rate"}
    assert d["steps"] == 6
    assert d["phase_a_us"] == pytest.approx(stats.phase_a.total)
    assert stats.phase_a.count == 6  # one sample per step
    # the active trainer's swap stats are visible in the global snapshot
    snap = obs.metrics.snapshot()
    assert snap["swap"]["banked"]["steps"] == 6
    assert snap["swap"]["phase_a_us"]["count"] == 6

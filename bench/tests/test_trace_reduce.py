"""trace_reduce on hand-built traces with known overlaps and gaps."""
import pytest

import trace_reduce as tr
from trace_reduce import Span


def test_union_and_idle_on_two_chips():
    # chip 0: ops [0,10] and [5,20] overlap, [30,40]; window [0,50]
    # busy 30, gaps (20,30) and (40,50)
    chip0 = [Span("%fusion.1 = bf16[8] fusion(%x.2)", 0, 10),
             Span("fusion.2", 5, 20), Span("while.4", 0, 20),
             Span("%masked_adamw.3 = (f32[1]) custom-call(%fusion.1)", 30, 40)]
    # chip 1: one op [10,35] inside the window, one clipped at the edge
    chip1 = [Span("fusion.9", 10, 35), Span("copy.1", 45, 70)]
    host = [Span("phase_a", 18, 32), Span("swap", 25, 28),
            Span("phase_b", 39, 60), Span("pjit", 0, 60)]
    s = tr.reduce([chip0, chip1], host, (0, 50),
                  labels=("phase_a", "swap", "phase_b"))
    assert s.window_s == pytest.approx(50e-9)
    assert s.busy_s == pytest.approx((30 + 30) / 2 * 1e-9)
    assert s.idle_share == pytest.approx(1 - 30 / 50)
    # per chip, summed by stable name (instance numbers dropped)
    assert s.op_s["fusion"] == pytest.approx((10 + 15 + 25) / 2 * 1e-9)
    assert s.op_s["copy"] == pytest.approx(5 / 2 * 1e-9)
    assert s.kernel_s("masked_adamw") == pytest.approx(10 / 2 * 1e-9)
    # gaps: chip0 (20,30) mid 25 -> swap (innermost), (40,50) -> phase_b;
    # chip1 (0,10) mid 5 -> none, (35,45) mid 40 -> phase_b
    by = dict((k, v) for k, v in s.idle_by_label())
    assert by["swap"] == pytest.approx(10e-9)
    assert by["phase_b"] == pytest.approx(20e-9)
    assert by["none"] == pytest.approx(10e-9)
    assert [k for k, _ in s.top_ops()][0] == "fusion"
    assert "while" not in s.op_s


def test_merge_nested_and_touching():
    spans = [Span("a", 0, 10), Span("b", 2, 3), Span("c", 10, 12),
             Span("d", 20, 21)]
    assert tr.merge(spans) == [(0, 12), (20, 21)]


def test_empty_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([[]], [], (5, 5))


def test_load_finds_the_window_in_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    device, host, (lo, hi) = tr.load(str(path))
    assert device == []            # no TPU planes on the CPU
    assert hi > lo
    assert any(sp.name == "bench_window" for sp in host)

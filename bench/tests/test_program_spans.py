"""The per-layer metrics that read the program's own trace of the window,
on the tiny cell; and the idle gaps that the boundary's spans would label
inside ``swap``."""
import json
import math

import jax
import pytest

import run
import tiny
import trace_reduce as tr
from trace_reduce import Span

BOUNDARY = ("swap_join_ms_per_step", "swap_commit_ms_per_step",
            "swap_transfer_gbps")
BOTH = ("data_wait_ms_per_step", "window_compiles")


def _traced(root, capsys, seconds):
    assert run.main(["--workload", tiny.CELL, "--seed", str(2 ** 33 + 7),
                     "--seconds", str(seconds), "--trace", "1"],
                    root=root, devices=jax.devices()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_banked_cell_reads_the_boundary_spans(tmp_path, capsys):
    line = _traced(tiny.make_root(tmp_path), capsys, seconds=3.0)
    assert line["correct"] is True, line["checks"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    for name in BOUNDARY + BOTH:
        assert name in m and math.isfinite(m[name]), (name, m)
    assert m["swap_transfer_gbps"] > 0
    # both are nested inside the boundary's span, whose histogram gives
    # swap_ms_per_step over a window that holds the traced one
    assert (m["swap_join_ms_per_step"] + m["swap_commit_ms_per_step"]
            <= m["swap_ms_per_step"])
    assert m["window_compiles"] == 0


def test_the_full_cell_reads_data_wait_and_compiles(tmp_path, capsys):
    line = _traced(tiny.make_root(tmp_path, method="full",
                                  residency="device"), capsys, seconds=0.3)
    assert line["correct"] is True, line["checks"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    for name in BOTH:
        assert name in m and math.isfinite(m[name]), (name, m)
    assert not set(BOUNDARY) & set(m)
    assert m["window_compiles"] == 0


def test_a_gap_inside_swap_join_is_labelled_by_it():
    # the chip idles over (10, 30); the training thread is inside
    # swap_join, nested in swap, at the gap's midpoint 20; the planner's
    # swap_evict, on its own thread and not a label, spans it too
    chip = [Span("fusion.1", 0, 10), Span("fusion.2", 30, 40)]
    host = [Span("train_step", 0, 40), Span("swap", 8, 32),
            Span("swap_join", 12, 28), Span("swap_evict", 14, 26)]
    labels = ("phase_a", "swap", "phase_b", "train_step", "swap_join")
    s = tr.reduce([chip], host, (0, 40), labels=labels)
    assert s.idle_by_label() == [["swap_join", pytest.approx(20e-9)]]
    s = tr.reduce([chip], host, (0, 40), labels=labels[:-1])
    assert s.idle_by_label() == [["swap", pytest.approx(20e-9)]]

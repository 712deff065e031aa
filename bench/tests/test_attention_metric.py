"""``attention_ms_per_step`` on hand-built trace summaries."""
import pytest

import harness
import trace_reduce as tr
from trace_reduce import Span

READ = harness.metric_reader("attention_ms_per_step")


def _summary(names):
    chip = [Span(n, 10 * i, 10 * i + 4) for i, n in enumerate(names)]
    return tr.reduce([chip], [], (0, 1000))


def test_nothing_to_read_without_a_trace_or_the_kernel():
    assert READ({"kind": "train", "steps": 2}) is None
    assert READ({"kind": "train", "steps": 2, "trace": None}) is None
    # the parent's XLA path: fusions only, no flash_attention events
    trace = _summary(["%fusion.3 = bf16[8] fusion(%a)", "convolution.2"])
    assert READ({"kind": "train", "steps": 2, "trace": trace}) is None


def test_device_ms_of_the_flash_kernels_per_step():
    # forward twice (the remat), dq and dkv: 4 x 4 ns over 2 steps; the
    # fusion between them does not count
    trace = _summary(["%flash_attention_fwd.1 = (bf16[4]) custom-call(%q)",
                      "flash_attention_fwd.7", "fusion.2",
                      "flash_attention_dq.3", "flash_attention_dkv"])
    got = READ({"kind": "train", "steps": 2, "trace": trace})
    assert got == pytest.approx(1000.0 * 16e-9 / 2)

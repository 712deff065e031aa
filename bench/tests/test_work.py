"""Work counts against hand counts at small shapes, and the qwen2.5-0.5b
cells' counts as hard numbers."""
import json
from pathlib import Path

import numpy as np
import pytest

import harness
import work

BENCH = Path(__file__).resolve().parents[1]
DENSE = harness.load_module(BENCH / "families" / "dense_decoder.py")

SMALL = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 3, "intermediate_size": 16, "vocab_size": 10}


def test_sizes_and_matmul_params():
    s = DENSE.sizes(SMALL)
    assert s["head_dim"] == 2 and s["bias"] is False
    # per layer: q 8*4*2=64, k,v 2*8*2*2=64, o 64, mlp 3*8*16=384
    assert DENSE.matmul_params(SMALL) == 3 * (64 + 64 + 64 + 384) + 8 * 10


def test_causal_pairs_within_segments():
    # segments of 1, 2 and 3 tokens: 1 + 3 + 6 query-key pairs
    assert work.causal_pairs([1, 2, 3]) == 10
    # 4 FLOPs per pair, head dim and head, forward and backward (3x)
    assert DENSE.train_flops(SMALL, 6, 10) == \
        6 * DENSE.matmul_params(SMALL) * 6 + 3 * 4 * 3 * 4 * 2 * 10


def test_bytes():
    # read p, g, m, v; write p, m, v: bf16 params and grads, f32 moments
    assert work.adamw_bytes(10, 2, 2, 4) == 10 * (2 + 2 + 2 + 4 * 4)
    assert work.grad_read_bytes(10, 2) == 20


def test_block_params_count_published_heads():
    b = DENSE.block_params({**SMALL, "attention_bias": True})
    assert b == [80] + [576 + 2 * 8 + 4 * 2 + 2 * 2 * 2] * 3 + [8]
    assert DENSE.block_params(SMALL)[1] == 576 + 2 * 8


def test_qwen_matmul_params():
    cfg = json.load(open(BENCH / "configs" / "qwen2.5-0.5b.json"))
    assert DENSE.matmul_params(cfg) == 24 * 14_909_440 + 896 * 151_936
    assert DENSE.matmul_params(cfg) == pytest.approx(4.94e8, rel=1e-3)


@pytest.mark.parametrize("cell", ["qwen2.5-0.5b.train.ags_banked",
                                  "qwen2.5-0.5b.train.full"])
def test_qwen_cells_count_what_they_counted_before(cell):
    """The counts the cells read before the family interface, exactly."""
    import jax.numpy as jnp
    c = harness.load_cell(cell)
    per_block = np.array(c.family.block_params(c.config), np.int64)
    assert per_block.tolist() == [136_134_656] + [14_912_384] * 24 + [896]
    assert c.family.train_flops(c.config, 8192, 123_456_789) == \
        56_136_959_176_704
    p_bytes = jnp.dtype(c.config["torch_dtype"]).itemsize
    m_bytes = jnp.dtype(c.traffic["job"]["optimizer"]["moment_dtype"]).itemsize
    total = int(per_block.sum())
    assert work.adamw_bytes(total, p_bytes, p_bytes, m_bytes) == 10_868_720_896
    assert work.grad_read_bytes(total, p_bytes) == 988_065_536


def test_peaks_by_device_kind():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")

"""Work counts against hand counts at small shapes."""
import json
from pathlib import Path

import pytest

import work

SMALL = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 3, "intermediate_size": 16, "vocab_size": 10}


def test_sizes_and_matmul_params():
    s = work.sizes(SMALL)
    assert s["head_dim"] == 2
    # per layer: q 8*4*2=64, k,v 2*8*2*2=64, o 64, mlp 3*8*16=384
    assert work.layer_matmul_params(s) == 64 + 64 + 64 + 384
    assert work.matmul_params(s) == 3 * 576 + 8 * 10


def test_causal_pairs_within_segments():
    # segments of 1, 2 and 3 tokens: 1 + 3 + 6 query-key pairs
    assert work.causal_pairs([1, 2, 3]) == 10
    s = work.sizes(SMALL)
    # 4 FLOPs per pair, head dim and head
    assert work.attention_fwd_flops(s, 10) == 4 * 3 * 4 * 2 * 10
    assert work.train_flops(s, 6, 10) == 6 * work.matmul_params(s) * 6 \
        + 3 * 4 * 3 * 4 * 2 * 10


def test_bytes():
    # read p, g, m, v; write p, m, v: bf16 params and grads, f32 moments
    assert work.adamw_bytes(10, 2, 2, 4) == 10 * (2 + 2 + 2 + 4 * 4)
    assert work.grad_read_bytes(10, 2) == 20


def test_block_params_count_published_heads():
    s = work.sizes(SMALL)
    b = work.block_params(s, with_biases=True)
    assert b["layer"] == 576 + 2 * 8 + 4 * 2 + 2 * 2 * 2
    assert b["embed"] == 80 and b["final_norm"] == 8


def test_qwen_matmul_params():
    root = Path(__file__).resolve().parents[1]
    cfg = json.load(open(root / "configs" / "qwen2.5-0.5b.json"))
    s = work.sizes(cfg)
    assert work.matmul_params(s) == 24 * 14_909_440 + 896 * 151_936
    assert work.matmul_params(s) == pytest.approx(4.94e8, rel=1e-3)


def test_peaks_by_device_kind():
    import harness
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")

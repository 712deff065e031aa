"""Operations and least bytes of the work a cell does, from its published
sizes (the configuration file), never from the program's padded layout.

A dense decoder of ``L`` layers, hidden size ``D``, ``H`` query heads and
``KVH`` key/value heads of size ``Dh``, feed-forward width ``F`` and
vocabulary ``V`` with tied embeddings:

* matmul parameters per token: per layer ``D*H*Dh + 2*D*KVH*Dh + H*Dh*D +
  3*D*F``, plus ``D*V`` for the output head. The embedding lookup is not a
  matmul. Published heads only: heads the program pads for sharding do no
  work that anyone needs.
* attention: a query at position ``i`` of a segment attends to ``i + 1``
  keys (causal within its packed segment), which costs ``2*Dh`` FLOPs for
  the score and ``2*Dh`` for the value mix per head.
* training FLOPs are 3x the forward (forward, and backward for inputs and
  weights); recomputation does not count.
"""
from __future__ import annotations

import numpy as np


def sizes(config: dict) -> dict:
    """The sizes this module needs, from a configuration file."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {"layers": int(config["num_hidden_layers"]), "d": d, "heads": h,
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim") or d // h),
            "ff": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"])}


def layer_matmul_params(s: dict) -> int:
    d, h, kvh, dh = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    return d * h * dh + 2 * d * kvh * dh + h * dh * d + 3 * d * s["ff"]


def matmul_params(s: dict) -> int:
    """Parameters that take part in a matmul per token (the ``N`` of 6N)."""
    return s["layers"] * layer_matmul_params(s) + s["d"] * s["vocab"]


def causal_pairs(segment_lengths) -> int:
    """Query-key pairs of causal attention within segments."""
    n = np.asarray(segment_lengths, np.int64)
    return int(np.sum(n * (n + 1) // 2))


def attention_fwd_flops(s: dict, pairs: int) -> int:
    """Forward attention FLOPs (scores and value mix) over ``pairs``."""
    return 4 * s["layers"] * s["heads"] * s["head_dim"] * int(pairs)


def train_flops(s: dict, tokens: int, pairs: int) -> int:
    """Forward plus backward FLOPs of ``tokens`` trained whose causal
    attention spans ``pairs`` query-key pairs."""
    return 6 * matmul_params(s) * int(tokens) + 3 * attention_fwd_flops(s,
                                                                        pairs)


def block_params(s: dict, with_biases: bool, norms: bool = True) -> dict:
    """Published parameter count of each block the method selects among:
    the embedding, one transformer layer, the final norm."""
    d, h, kvh, dh = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    layer = layer_matmul_params(s) + (2 * d if norms else 0)
    if with_biases:
        layer += h * dh + 2 * kvh * dh
    return {"embed": s["vocab"] * d, "layer": layer, "final_norm": d}


def adamw_bytes(params: int, p_bytes: int, g_bytes: int,
                moment_bytes: int) -> int:
    """Least bytes of one AdamW update of ``params`` parameters: read p, g,
    m, v and write p, m, v."""
    return int(params) * (2 * p_bytes + g_bytes + 4 * moment_bytes)


def grad_read_bytes(params: int, g_bytes: int) -> int:
    """One read of every gradient element (the block norms)."""
    return int(params) * g_bytes


"""Work counts that every family shares: causal query-key pairs within
packed segments, and the least bytes of the optimizer's update and of the
block gradient norms. A family's FLOPs and its blocks' parameter counts
come from its own module (``bench/families/``), from its published sizes,
never from the program's padded layout.
"""
from __future__ import annotations

import numpy as np


def causal_pairs(segment_lengths) -> int:
    """Query-key pairs of causal attention within segments."""
    n = np.asarray(segment_lengths, np.int64)
    return int(np.sum(n * (n + 1) // 2))


def adamw_bytes(params: int, p_bytes: int, g_bytes: int,
                moment_bytes: int) -> int:
    """Least bytes of one AdamW update of ``params`` parameters: read p, g,
    m, v and write p, m, v."""
    return int(params) * (2 * p_bytes + g_bytes + 4 * moment_bytes)


def grad_read_bytes(params: int, g_bytes: int) -> int:
    """One read of every gradient element (the block norms)."""
    return int(params) * g_bytes

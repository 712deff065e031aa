"""Fused per-block gradient sum-of-squares (paper Alg. 1 lines 1-6).

The selection hot-spot: without fusion, computing per-block norms costs one
extra HBM pass over every gradient leaf. The kernel streams a stacked
gradient once through VMEM, viewed as [L, R/128, 128] so every tile is
lane-dense, and accumulates per-lane partial sums in the layer's output
block, which stays resident across that layer's row blocks.

Grid: (L, cdiv(R/128, rows)) — the row-block axis is innermost (sequential
on TPU), so the accumulator legally carries across the blocks of one layer.
The last row block may overhang the array; its out-of-range rows are
masked to zero before they are squared.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 512  # 512 x 128 elements per tile; a multiple of 16 (bf16)


def _kernel(g_ref, o_ref, *, rows: int, br: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    g = g_ref[0].astype(jnp.float32)                  # [br, 128]
    if rows % br:
        row = j * br + jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
        g = jnp.where(row < rows, g, 0.0)
    o_ref[0] += jnp.sum(g * g, axis=0, keepdims=True)


def block_grad_sq_norms(g3d: jax.Array, *, interpret: bool = True) -> jax.Array:
    """g3d: [L, R/128, 128] (lane-padded by ops.py) -> [L] f32 sum of
    squares."""
    l, rows, lanes = g3d.shape
    assert lanes == LANES, g3d.shape
    br = min(rows, BLOCK_ROWS)
    partials = pl.pallas_call(
        partial(_kernel, rows=rows, br=br),
        grid=(l, pl.cdiv(rows, br)),
        in_specs=[pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((1, 1, LANES), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((l, 1, LANES), jnp.float32),
        interpret=interpret,
        name="block_grad_sq_norms",
    )(g3d)
    return jnp.sum(partials, axis=(1, 2))

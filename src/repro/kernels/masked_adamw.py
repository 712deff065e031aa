"""Fused block-masked AdamW update (paper Alg. 1 lines 9-13 + moments).

The optimizer step is purely memory-bound (reads p, g, m, v; writes p, m, v
= ~36 bytes/param at bf16 params + f32 moments). The unfused XLA form
materializes m-hat/v-hat intermediates; this kernel does the whole masked
update in one VMEM pass, in place (p, m and v alias their outputs).

Layout: every stacked leaf is viewed as [L, R/128, 128] and walked in
lane-dense (rows, 128) tiles. Per-layer values live in SMEM: the selection
bit and the bias corrections ``1 - b1**c`` / ``1 - b2**c``, which the
wrapper computes in XLA from the per-block counts exactly as the reference
does. The traced learning rate rides in SMEM too; b1, b2, eps and the
weight decay are compile-time constants. An unselected layer's tiles are
copied through unchanged, so its parameters and moments stay bit-identical.

Grid: (L, cdiv(R/128, rows)); the last row block may overhang the array,
and its out-of-range rows are computed on padding and never written.

``banked_masked_adamw`` is the banked-residency variant (paper §3.3): the
moments are compact [cap]-row banks and the parameter/gradient rows are
addressed *through the [cap] slots vector* with scalar prefetch
(``PrefetchScalarGridSpec``) — the grid walks bank rows and the p/g index
maps dereference ``slots[i]`` to pick the full-leaf row, so the former
``gather_rows -> masked_adamw -> scatter_rows`` chain collapses into one
kernel and the two materialized [cap, R] copies of p and g disappear.
Sentinel slots (``slots[i] >= L``, unfilled bank rows) are clamped to a
real row for the fetch and neutralized by ``sel == 0`` (the masked update
is the identity there); the compact p output for those rows is dropped by
the caller's ``scatter_rows(..., mode="drop")``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 512  # 512 x 128 elements per tile; a multiple of 16 (bf16)

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _update(lr_ref, sel_ref, bc1_ref, bc2_ref, p_ref, g_ref, m_ref, v_ref,
            po_ref, mo_ref, vo_ref, *, b1, b2, eps, wd):
    i = pl.program_id(0)

    @pl.when(sel_ref[i] > 0)
    def _step():
        g = g_ref[...].astype(jnp.float32)
        p = p_ref[...].astype(jnp.float32)
        m2 = b1 * m_ref[...].astype(jnp.float32) + (1 - b1) * g
        v2 = b2 * v_ref[...].astype(jnp.float32) + (1 - b2) * g * g
        mhat = m2 / bc1_ref[i]
        vhat = v2 / bc2_ref[i]
        step = lr_ref[0] * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
        po_ref[...] = (p - step).astype(po_ref.dtype)
        mo_ref[...] = m2.astype(mo_ref.dtype)
        vo_ref[...] = v2.astype(vo_ref.dtype)

    @pl.when(sel_ref[i] <= 0)
    def _keep():
        po_ref[...] = p_ref[...]
        mo_ref[...] = m_ref[...]
        vo_ref[...] = v_ref[...]


def _row_scalars(sel, counts, lr, b1, b2):
    """SMEM operands: lr [1], sel [n], and the bias corrections [n]."""
    c = jnp.maximum(counts.astype(jnp.float32), 1.0)
    return (jnp.asarray(lr, jnp.float32).reshape(1),
            sel.astype(jnp.float32), 1 - b1 ** c, 1 - b2 ** c)


def masked_adamw(p, g, m, v, sel, counts, lr, b1, b2, eps, wd, *,
                 interpret: bool = True):
    """p,g: [L, R/128, 128] (param dtype); m,v: same shape (moment dtype,
    f32 arithmetic); sel, counts: [L]; lr: scalar (traced); b1, b2, eps,
    wd: Python floats. Returns (p', m', v'), written in place of p, m, v."""
    l, rows, lanes = p.shape
    assert lanes == LANES, p.shape
    br = min(rows, BLOCK_ROWS)
    data = pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0))
    return pl.pallas_call(
        partial(_update, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=(l, pl.cdiv(rows, br)),
        in_specs=[_SMEM] * 4 + [data] * 4,
        out_specs=(data, data, data),
        out_shape=(jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(m.shape, m.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        input_output_aliases={4: 0, 6: 1, 7: 2},
        interpret=interpret,
        name="masked_adamw",
    )(*_row_scalars(sel, counts, lr, b1, b2), p, g, m, v)


def _banked_update(slots_ref, *refs, **consts):
    # identical arithmetic to the dense kernel; the slots vector only steers
    # the p/g BlockSpec index maps (scalar prefetch)
    del slots_ref
    _update(*refs, **consts)


def banked_masked_adamw(p, g, m, v, slots, sel, counts,
                        lr, b1, b2, eps, wd, *, interpret: bool = True):
    """Fused gather -> masked AdamW -> (compact) update over bank rows.

    p, g: [L, R/128, 128] full stacked leaves; m, v: [cap, R/128, 128]
    moment banks (updated in place); slots: [cap] i32 bank->leaf row map
    (``>= L`` marks an unfilled slot); sel, counts: [cap] per-slot (sel
    must already be 0 for sentinel slots). Returns (p_rows', m', v'), each
    [cap, R/128, 128] — the caller scatters p_rows' back with drop-mode OOB
    semantics. Grid walks (cap, row blocks); p/g blocks are addressed via
    ``slots[i]`` through scalar prefetch, sentinels clamped to row L-1
    (fetch-only: sel == 0 makes the update an identity and the scatter
    drops the row)."""
    l, rows, lanes = p.shape
    cap = m.shape[0]
    assert lanes == LANES, p.shape
    br = min(rows, BLOCK_ROWS)
    leaf_row = pl.BlockSpec((1, br, LANES),
                            lambda i, j, s: (jnp.minimum(s[i], l - 1), j, 0))
    bank_row = pl.BlockSpec((1, br, LANES), lambda i, j, s: (i, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(cap, pl.cdiv(rows, br)),
        in_specs=[_SMEM] * 4 + [leaf_row] * 2 + [bank_row] * 2,
        out_specs=(bank_row, bank_row, bank_row))
    bank_shape = (cap, rows, LANES)
    return pl.pallas_call(
        partial(_banked_update, b1=b1, b2=b2, eps=eps, wd=wd),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(bank_shape, p.dtype),
                   jax.ShapeDtypeStruct(bank_shape, m.dtype),
                   jax.ShapeDtypeStruct(bank_shape, v.dtype)),
        # operand indices count the scalar-prefetch slots vector first
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
        name="banked_masked_adamw",
    )(jnp.asarray(slots, jnp.int32),
      *_row_scalars(sel, counts, lr, b1, b2), p, g, m, v)

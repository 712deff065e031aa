"""Causal flash attention (fwd + bwd) for packed training batches on TPU.

``attention.apply`` calls it for training attention whenever the kernels
are on (``ops.use_kernels``) and the attention is plain causal (no prefix,
no softcap) over a sequence the kernel can take (``fits``); see
``attention_core.py`` for the paths that stay on XLA.

Layout: q, k, v are [BH, S, D] with the head folded into the batch
(b-major, ``BH = B * heads``; GQA is gather-expanded to the q heads
outside the kernel). Packed segment ids are int32, one row per batch row
[B, S], and each kernel picks row ``bh // heads`` in its index map.

Forward   grid (BH, S/BQ): the head's K and V^T sit in VMEM whole; an
          online softmax walks the K blocks this Q block can attend to, on
          [BK, BQ] score tiles, so its reductions over keys run down the
          sublanes; it writes O^T and the log-sum-exp (LSE) of each query.
Backward  two kernels, each accumulating over one loop:
            dq:   grid (BH, S/BQ), loop over the same K blocks
            dkv:  grid (BH, S/BK), loop over the Q blocks that attend to
                  the K block, on [BK, BQ] tiles
          probabilities are recomputed from q, k and the saved LSE. No
          tile is transposed inside a loop.

Block skipping: the loops run over one range of blocks per (row, block),
read from SMEM (scalar prefetch). ``_ranges`` computes them in XLA: the
blocks at or below the diagonal whose segment ids can meet the block's
(the id ranges of the two blocks overlap). No block outside the range
holds a pair that may attend, so for packed rows the loops skip the other
segments' blocks as well as those above the diagonal. Every visited tile
is masked.

Precision: the MXU takes the operands' own dtype (bf16 in training) and
accumulates in f32; the softmax statistics stay f32; the probabilities
and dS are cast to the input dtype before their matmuls. q is scaled by
``D ** -0.5`` before it is rounded to its dtype, once, identically in all
three kernels. The forward's output stays f32 for the backward's
``delta = rowsum(dO * O)``: from a bf16-rounded O, the rows of dS no
longer sum to zero, and the gradients of the key bias drift.

Per-position vectors (LSE, ``delta``, segment ids) live in HBM as rows:
[N, 1, S] where a grid block covers the positions, [N, S/b, b] where a
loop walks them (one sublane row per block); the forward reads the key
ids as a column [B, S, 1], and the dq kernel turns its block's rows into
columns once per grid step.

Blocks: ``block_sizes(S, D)``, chosen on one TPU v5e at S 1024, D 64.
``fits`` says whether the kernel takes a shape at all.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# what one kernel holds in VMEM whole, double-buffered: the head's two
# [S, D] operands and the key ids' lane-padded column
VMEM_BUDGET = 8 * 2 ** 20


def block_sizes(s: int, d: int) -> tuple[int, int]:
    """(BQ, BK): 256 x 256 tiles where they divide the sequence (the
    fastest of 128, 256 and 512 on one TPU v5e at S 1024, D 64), 128 x 128
    for wider heads or where 256 does not divide S."""
    b = 256 if d <= 128 and s % 256 == 0 else LANES
    return b, b


def fits(s: int, d: int, itemsize: int) -> bool:
    """Whether the kernels take an [S, D] head: the blocks tile S, the
    head's transposed V tiles whole sublanes, and what a kernel holds
    whole stays within ``VMEM_BUDGET``."""
    bq, bk = block_sizes(s, d)
    whole = 2 * (2 * s * d * itemsize + s * LANES * 4)
    return (s % bq == 0 and s % bk == 0 and d % 16 == 0
            and whole <= VMEM_BUDGET)


def _col(row):
    """[1, n] -> [n, 1]: a block's per-position row as a column."""
    n = row.shape[-1]
    return jnp.transpose(jnp.broadcast_to(row, (LANES, n)))[:, :1]


def _mask(shape, row0, col0, causal, qseg, kseg, transposed=False):
    """Where a [rows, cols] score tile may attend, or None for all of it.
    Rows are queries and columns keys, or the reverse when ``transposed``;
    ``qseg``/``kseg`` broadcast against the tile (a column and a row)."""
    mask = None
    if causal:
        r = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        c = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = (c >= r) if transposed else (r >= c)
    if qseg is not None:
        same = qseg == kseg
        mask = same if mask is None else mask & same
    return mask


def _nt(a, b):
    """a @ b.T on the MXU, accumulated in f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _scaled(q, scale):
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _ranges(seg, s, bq, bk, causal):
    """Loop ranges ``(first, end)``, int32 and flattened row-major: over K
    blocks per (row, Q block) and over Q blocks per (row, K block).
    ``seg``: [B, S] or None (one row, every id equal)."""
    nq, nk = s // bq, s // bk
    meet = jnp.ones((1, nq, nk), bool)
    if seg is not None:
        qs, ks = seg.reshape(-1, nq, bq), seg.reshape(-1, nk, bk)
        meet = ((ks.max(-1)[:, None, :] >= qs.min(-1)[:, :, None])
                & (ks.min(-1)[:, None, :] <= qs.max(-1)[:, :, None]))
    if causal:
        meet = meet & (jnp.arange(nk)[None, :] * bk
                       <= (jnp.arange(nq)[:, None] + 1) * bq - 1)

    def first_end(m, axis):
        # every block meets at least the one holding its own positions
        n = m.shape[axis]
        first = jnp.argmax(m, axis=axis)
        end = n - jnp.argmax(jnp.flip(m, axis), axis=axis)
        return (first.reshape(-1).astype(jnp.int32),
                end.reshape(-1).astype(jnp.int32))

    return first_end(meet, 2), first_end(meet, 1)


# ----------------------------------------------------------------- forward


def _row_index(heads, segmented):
    """The batch row of this grid step's head (0: every row alike)."""
    return pl.program_id(0) // heads if segmented else 0


def _loop_range(first_ref, end_ref, row):
    """This grid step's loop range, from the scalar-prefetched ranges."""
    at = row * pl.num_programs(1) + pl.program_id(1)
    return first_ref[at], end_ref[at]


def _fwd_kernel(first_ref, end_ref, q_ref, k_ref, vt_ref, *rest, bq, bk, scale, causal, segmented, heads):
    # transposed orientation: [BK, BQ] score tiles, so the softmax's
    # reductions over keys run down the sublanes
    if segmented:
        qseg_ref, kseg_ref, ot_ref, lse_ref = rest
        qseg = qseg_ref[0]                                    # [1, BQ]
    else:
        ot_ref, lse_ref = rest
        qseg = None
    qi = pl.program_id(1)
    q = _scaled(q_ref[0], scale)                              # [BQ, D]
    row = _row_index(heads, segmented)

    def body(j, carry):
        m_prev, l_prev, acc = carry               # [1, BQ] x 2, [D, BQ]
        start = pl.multiple_of(j * bk, bk)
        k = k_ref[0, pl.ds(start, bk), :]                     # [BK, D]
        vt = vt_ref[0, j]                                     # [D, BK]
        st = _nt(k, q)                                        # [BK, BQ]
        if causal or segmented:
            kseg = (kseg_ref[0, pl.ds(start, bk), :] if segmented
                    else None)                                # [BK, 1]
            st = jnp.where(_mask(st.shape, start, qi * bq, causal, kseg,
                                 qseg, transposed=True), st, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(pt, axis=0, keepdims=True)
        acc = alpha * acc + _nn(vt, pt.astype(vt.dtype))
        return m_new, l_new, acc

    first, end = _loop_range(first_ref, end_ref, row)
    m, lsum, acc = jax.lax.fori_loop(
        first, end, body,
        (jnp.full((1, bq), NEG_INF / 2, jnp.float32),
         jnp.zeros((1, bq), jnp.float32),
         jnp.zeros((q.shape[-1], bq), jnp.float32)))
    lsum = jnp.maximum(lsum, 1e-30)
    ot_ref[0] = acc / lsum
    lse_ref[0] = m + jnp.log(lsum)


def _seg_views(seg, b):
    """[B, S] int32 ids -> ([B, 1, S], [B, S/b, b]) rows."""
    n, s = seg.shape
    return seg.reshape(n, 1, s), seg.reshape(n, s // b, b)


def flash_attention_fwd(q, k, v, seg=None, *, heads=1, bq, bk, causal=True,
                        interpret=True):
    """q, k, v: [BH, S, D]; seg: optional [B, S] int32 packed segment ids
    (``BH = B * heads``) -> (o [BH, S, D] f32, lse [BH, 1, S] f32)."""
    bh, s, d = q.shape
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    segmented = seg is not None
    nk = s // bk
    kern = partial(_fwd_kernel, bq=bq, bk=bk, scale=d ** -0.5, causal=causal,
                   segmented=segmented, heads=heads)
    vt = v.reshape(bh, nk, bk, d).transpose(0, 1, 3, 2)       # [BH, nk, D, BK]
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, i, *_: (b, i, 0)),
        pl.BlockSpec((1, s, d), lambda b, i, *_: (b, 0, 0)),
        pl.BlockSpec((1, nk, d, bk), lambda b, i, *_: (b, 0, 0, 0)),
    ]
    args = [q, k, vt]
    if segmented:
        rows, _ = _seg_views(seg, bk)
        in_specs += [pl.BlockSpec((1, 1, bq),
                                  lambda b, i, *_: (b // heads, 0, i)),
                     pl.BlockSpec((1, s, 1),
                                  lambda b, i, *_: (b // heads, 0, 0))]
        args += [rows, seg.reshape(seg.shape[0], s, 1)]
    (first, end), _ = _ranges(seg, s, bq, bk, causal)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(bh, s // bq), in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, d, bq), lambda b, i, *_: (b, 0, i)),
                   pl.BlockSpec((1, 1, bq), lambda b, i, *_: (b, 0, i))))
    ot, lse = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((bh, d, s), jnp.float32),
                   jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)),
        interpret=interpret,
        name="flash_attention_fwd",
    )(first, end, *args)
    return ot.transpose(0, 2, 1), lse


# ----------------------------------------------------------------- backward


def _bwd_dq_kernel(first_ref, end_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, *rest, bq, bk, scale, causal, segmented, heads):
    if segmented:
        qseg_ref, kseg_ref, dq_ref = rest
        qseg = _col(qseg_ref[0])
    else:
        (dq_ref,) = rest
        qseg = None
    qi = pl.program_id(1)
    q = _scaled(q_ref[0], scale)                              # [BQ, D]
    do = do_ref[0]
    lse = _col(lse_ref[0])                                    # [BQ, 1]
    delta = _col(delta_ref[0])
    row = _row_index(heads, segmented)

    def body(j, dq):
        start = pl.multiple_of(j * bk, bk)
        k = k_ref[0, pl.ds(start, bk), :]
        v = v_ref[0, pl.ds(start, bk), :]
        s = _nt(q, k)
        if causal or segmented:
            kseg = kseg_ref[0, pl.ds(j, 1), :] if segmented else None
            s = jnp.where(_mask(s.shape, qi * bq, start, causal, qseg, kseg),
                          s, NEG_INF)
        p = jnp.exp(s - lse)                                  # [BQ, BK]
        ds = p * (_nt(do, v) - delta)
        return dq + _nn(ds.astype(k.dtype), k)

    first, end = _loop_range(first_ref, end_ref, row)
    dq = jax.lax.fori_loop(first, end, body,
                           jnp.zeros((bq, q.shape[-1]), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(first_ref, end_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, *rest, bq, bk, scale, causal, segmented, heads):
    if segmented:
        qseg_ref, kseg_ref, dk_ref, dv_ref = rest
        kseg = _col(kseg_ref[0])                              # [BK, 1]
    else:
        dk_ref, dv_ref = rest
        kseg = None
    ki = pl.program_id(1)
    k = k_ref[0]                                              # [BK, D]
    v = v_ref[0]
    row = _row_index(heads, segmented)

    def body(i, carry):
        dk, dv = carry
        start = pl.multiple_of(i * bq, bq)
        q = _scaled(q_ref[0, pl.ds(start, bq), :], scale)     # [BQ, D]
        do = do_ref[0, pl.ds(start, bq), :]
        lse = lse_ref[0, pl.ds(i, 1), :]                      # [1, BQ]
        delta = delta_ref[0, pl.ds(i, 1), :]
        st = _nt(k, q)                                        # [BK, BQ]
        if causal or segmented:
            qseg = qseg_ref[0, pl.ds(i, 1), :] if segmented else None
            st = jnp.where(_mask(st.shape, ki * bk, start, causal, kseg,
                                 qseg, transposed=True), st, NEG_INF)
        pt = jnp.exp(st - lse)
        dv = dv + _nn(pt.astype(do.dtype), do)
        dst = pt * (_nt(v, do) - delta)
        return dk + _nn(dst.astype(q.dtype), q), dv

    first, end = _loop_range(first_ref, end_ref, row)
    zeros = jnp.zeros((bk, k.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(first, end, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, seg=None, *, heads=1, bq, bk,
                        causal=True, interpret=True):
    """Gradients (dq, dk, dv) of ``flash_attention_fwd`` for the output
    cotangent ``do``; ``lse`` as the forward returned it."""
    bh, s, d = q.shape
    segmented = seg is not None
    kw = dict(bq=bq, bk=bk, scale=d ** -0.5, causal=causal,
              segmented=segmented, heads=heads)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.reshape(bh, 1, s)
    nq = s // bq
    head = pl.BlockSpec((1, s, d), lambda b, i, *_: (b, 0, 0))
    q_rows = pl.BlockSpec((1, 1, bq), lambda b, i, *_: (b, 0, i))
    q_blk = pl.BlockSpec((1, bq, d), lambda b, i, *_: (b, i, 0))
    dq_specs = [q_blk, head, head, q_blk, q_rows, q_rows]
    dq_args = [q, k, v, do, lse, delta]
    k_blk = pl.BlockSpec((1, bk, d), lambda b, j, *_: (b, j, 0))
    q_loop = pl.BlockSpec((1, nq, bq), lambda b, j, *_: (b, 0, 0))
    dkv_specs = [head, k_blk, k_blk, head, q_loop, q_loop]
    dkv_args = [q, k, v, do, lse.reshape(bh, nq, bq),
                delta.reshape(bh, nq, bq)]
    if segmented:
        rows, k_blocks = _seg_views(seg, bk)
        _, q_blocks = _seg_views(seg, bq)
        dq_specs += [pl.BlockSpec((1, 1, bq),
                                  lambda b, i, *_: (b // heads, 0, i)),
                     pl.BlockSpec((1, s // bk, bk),
                                  lambda b, i, *_: (b // heads, 0, 0))]
        dq_args += [rows, k_blocks]
        dkv_specs += [pl.BlockSpec((1, nq, bq),
                                   lambda b, j, *_: (b // heads, 0, 0)),
                      pl.BlockSpec((1, 1, bk),
                                   lambda b, j, *_: (b // heads, 0, j))]
        dkv_args += [q_blocks, rows]
    q_range, k_range = _ranges(seg, s, bq, bk, causal)
    dq = pl.pallas_call(
        partial(_bwd_dq_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bh, nq), in_specs=dq_specs,
            out_specs=q_blk),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
        name="flash_attention_dq",
    )(*q_range, *dq_args)
    dk, dv = pl.pallas_call(
        partial(_bwd_dkv_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bh, s // bk), in_specs=dkv_specs,
            out_specs=(k_blk, k_blk)),
        out_shape=(jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)),
        interpret=interpret,
        name="flash_attention_dkv",
    )(*k_range, *dkv_args)
    return dq, dk, dv


# ------------------------------------------------------------- public op


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention(q, k, v, seg, heads, bq, bk, causal, interpret):
    """Causal (or full) attention of q over k, v: [BH, S, D] -> [BH, S, D].
    ``seg``: None, or [B, S] int32 packed segment ids (``BH = B * heads``):
    attention is then restricted to equal-segment pairs. The ids ride
    through the custom_vjp as an operand with no gradient."""
    o, _ = flash_attention_fwd(q, k, v, seg, heads=heads, bq=bq, bk=bk,
                               causal=causal, interpret=interpret)
    return o.astype(q.dtype)


def _vjp_fwd(q, k, v, seg, heads, bq, bk, causal, interpret):
    o, lse = flash_attention_fwd(q, k, v, seg, heads=heads, bq=bq, bk=bk,
                                 causal=causal, interpret=interpret)
    return o.astype(q.dtype), (q, k, v, seg, o, lse)


def _vjp_bwd(heads, bq, bk, causal, interpret, res, do):
    q, k, v, seg, o, lse = res
    dq, dk, dv = flash_attention_bwd(q, k, v, o, lse, do, seg, heads=heads,
                                     bq=bq, bk=bk, causal=causal,
                                     interpret=interpret)
    return dq, dk, dv, None


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)

"""Flash-decoding: one query token against a long KV cache.

Layout: q [BH, D], k/v [BH, S, D] (GQA expanded outside, like
flash_attention.py). Grid (BH, S/BK) with the KV-block axis innermost
(sequential), carrying online-softmax stats (m, l, acc) in VMEM scratch —
a single pass over the cache at HBM bandwidth, which is the roofline for
decode. ``valid_len`` masks unwritten cache slots; it may be a per-row
vector so continuous-batching slots at mixed progress each attend over
their own cache length.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 512
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bk, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[0, 0] = NEG_INF
        l_ref[0, 0] = 0.0
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # [D]
    k = k_ref[0].astype(jnp.float32)                  # [BK, D]
    v = v_ref[0].astype(jnp.float32)
    s = k @ q                                         # [BK]
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk,), 0)
    s = jnp.where(kpos < len_ref[0], s, NEG_INF)
    m_prev, l_prev = m_ref[0, 0], l_ref[0, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    p = jnp.exp(s - m_new)                            # [BK]
    alpha = jnp.exp(m_prev - m_new)
    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_prev * alpha + jnp.sum(p)
    acc_ref[...] = acc_ref[...] * alpha + (p @ v)[None, :]

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_ref[0] / jnp.maximum(l_ref[0, 0], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention(q, k, v, valid_len, *, bk=DEFAULT_BK, interpret=True):
    """q: [BH, D]; k, v: [BH, S, D]; valid_len: scalar i32 or [BH] i32
    vector (per-row valid cache length) -> o [BH, D]."""
    bh, s, d = k.shape
    bk = min(bk, s)
    assert s % bk == 0, (s, bk)
    scale = d ** -0.5
    vlen = jnp.asarray(valid_len, jnp.int32)
    if vlen.ndim == 0:
        vlen = jnp.full((bh,), vlen, jnp.int32)
    assert vlen.shape == (bh,), (vlen.shape, bh)
    return pl.pallas_call(
        partial(_kernel, bk=bk, scale=scale),
        grid=(bh, s // bk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, j: (b,)),
            pl.BlockSpec((1, d), lambda b, j: (b, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda b, j: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32),
                        pltpu.VMEM((1, d), jnp.float32)],
        interpret=interpret,
    )(vlen, q, k, v)


# ------------------------------------------------------------ paged variant
#
# Same online-softmax pass, but the KV cache lives in a shared page pool
# ([num_pages, KVH, page_size, D] per layer) and each batch row reads its
# pages through a scalar-prefetched page table: the KV block for grid step
# (b, h, j) is pool page ``table[b, j]`` at kv head ``hmap[h]`` — the page
# gather happens in the BlockSpec index map, so the dense [B, S, ...] view
# is never materialized. GQA needs no head expansion of the pool either
# (the dense kernel requires pre-expanded [BH, S, D] k/v); one pool page
# serves every query head of its kv group. The kv-head axis sits outside
# the page so that each block's two minor dims are a whole (page_size, D)
# tile, which is what the TPU's block tiling requires.


def _paged_kernel(tbl_ref, hm_ref, vlen_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, ps, scale):
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # [1, D]
    k = k_ref[0, 0].astype(jnp.float32)               # [ps, D]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [1, ps]
    # virtual position of page-slot i within this row's cache; positions at
    # or past valid_len are masked, which also neutralizes sentinel table
    # entries (allocated pages always cover [0, valid_len))
    kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < vlen_ref[b], s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]           # [1, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, page_tables, valid_len, hmap,
                           *, interpret=True):
    """q: [B, H, D]; k_pool/v_pool: [num_pages, KVH, page_size, D];
    page_tables: [B, max_pages] i32 pool page ids (entries >= num_pages mark
    unallocated slots — clamped for the fetch, masked by ``valid_len``);
    valid_len: [B] i32 per-row cache length; hmap: [H] i32 q-head -> kv-head
    map -> o [B, H, D].

    Grid (B, H, max_pages) with the page axis innermost (sequential online
    softmax, like the dense kernel); page/table indirection happens in the
    BlockSpec index maps via scalar prefetch (table, head map and per-row
    lengths all live in SMEM)."""
    b, h, d = q.shape
    num_pages, kvh, ps, dk = k_pool.shape
    assert dk == d, (dk, d)
    maxp = page_tables.shape[1]
    assert page_tables.shape == (b, maxp), (page_tables.shape, b)
    scale = d ** -0.5
    vlen = jnp.asarray(valid_len, jnp.int32)
    if vlen.ndim == 0:
        vlen = jnp.full((b,), vlen, jnp.int32)
    assert vlen.shape == (b,), (vlen.shape, b)
    tbl = jnp.asarray(page_tables, jnp.int32)
    hm = jnp.asarray(hmap, jnp.int32)
    assert hm.shape == (h,), (hm.shape, h)

    def page_of(bi, hi, j, tbl_ref, hm_ref, vlen_ref):
        return (jnp.minimum(tbl_ref[bi, j], num_pages - 1), hm_ref[hi], 0, 0)

    def row_of(bi, hi, j, tbl_ref, hm_ref, vlen_ref):
        return (bi, hi, 0, 0)

    # q/o carry a unit axis so their blocks' minor dims are the whole (1, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, h, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), row_of),
            pl.BlockSpec((1, 1, ps, d), page_of),
            pl.BlockSpec((1, 1, ps, d), page_of),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), row_of),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32),
                        pltpu.VMEM((1, d), jnp.float32)])
    o = pl.pallas_call(
        partial(_paged_kernel, ps=ps, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(tbl, hm, vlen, q.reshape(b, h, 1, d), k_pool, v_pool)
    return o.reshape(b, h, d)

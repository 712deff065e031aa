"""Attention math shared by GQA and MLA layers.

GQA uses *gather expansion*: each q head gathers its kv group via a static
index map (``head2group``) instead of reshaping H into [KVH, rep]. The
reshape-free form keeps the q-head axis cleanly shardable over the mesh
``model`` axis for ANY head count (GSPMD pads uneven dims), while kv stays
replicated (KVH < shards — the normal GQA case) or KVH-sharded (divisible).
FLOP count is identical to grouped GQA.

Execution paths:
  * ``chunked_attention``  -- q-chunked exact attention via lax.scan; the XLA
    path for every attention the flash kernel does not take (bounds the
    score-matrix working set to [B, H, chunk_q, S_k]): serving prefill,
    prefix-LM (vlm) and softcapped masks, MLA, the encoder-decoder's
    encoder and decoder, sequences the kernel's blocks do not tile, and
    everything with the kernels off.
  * ``decode_attention``   -- single-query attention against a length-masked
    KV cache.
  * Training attention in ``layers/attention.apply`` runs the Pallas flash
    kernel (kernels/flash_attention.py) when the kernels are on and the
    mask is plain causal, packed segments included. The other attention
    kernel on a main path is the paged decode kernel, called by
    layers/attention.apply_decode_paged; the dense Pallas decode kernel and
    the RMSNorm kernel are reached only from tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def head2group(num_heads: int, num_kv_heads: int) -> np.ndarray:
    """Static q-head -> kv-group index map (kv-major grouping)."""
    rep = num_heads // num_kv_heads
    return np.arange(num_heads) // rep


def expand_kv(k: jax.Array, hmap: np.ndarray) -> jax.Array:
    """k: [B, S, KVH, D] -> [B, S, H, D] via static gather (identity when
    KVH == H)."""
    if k.shape[2] == hmap.shape[0] and (hmap == np.arange(len(hmap))).all():
        return k
    return k[:, :, hmap, :]


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return jnp.tanh(scores / cap) * cap
    return scores


def full_attention(q, k, v, *, hmap=None, causal=True, q_offset=0,
                   prefix_len=0, softcap=0.0, kv_len_mask=None,
                   q_seg=None, k_seg=None):
    """Exact attention. q: [B, Sq, H, Dh]; k: [B, Sk, KVH, Dh];
    v: [B, Sk, KVH, Dv]; hmap: head2group map (None -> MHA identity).
    kv_len_mask: [B, Sk] bool of valid cache slots.
    q_seg/k_seg: [B, Sq]/[B, Sk] packed segment ids — scores are masked to
    equal-segment pairs (block-diagonal attention; combined with the causal
    row-position mask this is exactly per-example causal attention). A
    query always keeps its own position (q_seg[i] == k_seg[i] at the same
    index), so no softmax row is ever fully masked."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    if hmap is None:
        hmap = head2group(h, k.shape[2])
    ke = expand_kv(k, hmap).astype(jnp.float32)
    ve = expand_kv(v, hmap).astype(jnp.float32)
    qf = q.astype(jnp.float32) * (dh ** -0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, ke)
    scores = _softcap(scores, softcap)
    if causal:
        q_pos = q_offset + jnp.arange(sq)
        k_pos = jnp.arange(sk)
        mask = q_pos[:, None] >= k_pos[None, :]
        if prefix_len:
            mask = mask | (k_pos[None, :] < prefix_len)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    if kv_len_mask is not None:
        scores = jnp.where(kv_len_mask[:, None, None, :], scores, NEG_INF)
    if q_seg is not None:
        seg_ok = q_seg[:, None, :, None] == k_seg[:, None, None, :]
        scores = jnp.where(seg_ok, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, ve)
    return out.astype(q.dtype)


def chunked_attention(q, k, v, *, hmap=None, chunk_q=512, causal=True,
                      prefix_len=0, softcap=0.0, remat_chunks=True,
                      segment_ids=None):
    """Exact causal attention, scanned over query chunks to bound memory.
    S must be divisible by chunk_q (or <= chunk_q).

    ``segment_ids``: [B, S] packed segment ids (0 = pad) — block-diagonal
    masking as in full_attention; the query-side ids are chunked along with
    q, the key side stays whole.

    ``remat_chunks``: rematerialize each chunk's probs in the backward
    instead of stashing [nq, B, H, chunk, S] f32 residuals (that tensor is
    what blows the training peak otherwise — flash attention's backward
    makes the same trade on real hardware)."""
    b, s, h, dh = q.shape
    if s <= chunk_q:
        return full_attention(q, k, v, hmap=hmap, causal=causal,
                              prefix_len=prefix_len, softcap=softcap,
                              q_seg=segment_ids, k_seg=segment_ids)
    assert s % chunk_q == 0, (s, chunk_q)
    nq = s // chunk_q
    qs = q.reshape(b, nq, chunk_q, h, dh).transpose(1, 0, 2, 3, 4)
    segs = (None if segment_ids is None
            else segment_ids.reshape(b, nq, chunk_q).transpose(1, 0, 2))

    def body(_, args):
        i, qc, qsc = args if segs is not None else (*args, None)
        out = full_attention(qc, k, v, hmap=hmap, causal=causal,
                             q_offset=i * chunk_q, prefix_len=prefix_len,
                             softcap=softcap, q_seg=qsc,
                             k_seg=None if qsc is None else segment_ids)
        return None, out

    if remat_chunks:
        body = jax.checkpoint(body)
    xs = ((jnp.arange(nq), qs) if segs is None
          else (jnp.arange(nq), qs, segs))
    _, outs = jax.lax.scan(body, None, xs)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, v.shape[-1])


def decode_attention(q, k_cache, v_cache, cache_len, *, hmap=None, softcap=0.0):
    """q: [B, 1, H, Dh]; caches [B, Smax, KVH, D*]; cache_len: scalar int or
    per-row [B] vector — number of valid cache slots per row (the new
    token's k/v already written)."""
    sk = k_cache.shape[1]
    cl = jnp.asarray(cache_len)
    cl = cl[:, None] if cl.ndim else cl
    valid = jnp.arange(sk)[None, :] < cl
    valid = jnp.broadcast_to(valid, (q.shape[0], sk))
    return full_attention(q, k_cache, v_cache, hmap=hmap, causal=False,
                          kv_len_mask=valid, softcap=softcap)

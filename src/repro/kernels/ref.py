"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def block_grad_sq_norms(g: jax.Array) -> jax.Array:
    """g: [L, ...] -> [L] sum of squares over non-leading axes (f32)."""
    gf = g.astype(jnp.float32)
    return jnp.sum(gf * gf, axis=tuple(range(1, gf.ndim)))


def masked_adamw(p, g, m, v, sel, counts, lr, b1, b2, eps, wd):
    """p,g,m,v: [L, R]; sel, counts: [L] (counts = post-increment per-block
    step). Returns (p', m', v') with the masked-AdamW semantics of
    core/masked_adamw.py."""
    gf = g.astype(jnp.float32)
    selb = (sel > 0)[:, None]
    m2 = jnp.where(selb, b1 * m + (1 - b1) * gf, m)
    v2 = jnp.where(selb, b2 * v + (1 - b2) * gf * gf, v)
    c = jnp.maximum(counts, 1.0)[:, None]
    mhat = m2 / (1 - b1 ** c)
    vhat = v2 / (1 - b2 ** c)
    pf = p.astype(jnp.float32)
    step = lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
    p2 = jnp.where(selb, pf - step, pf)
    return p2.astype(p.dtype), m2, v2


def flash_attention(q, k, v, *, causal=True, segment_ids=None):
    """q,k,v: [B, H, S, D] (MHA layout) -> [B, H, S, D]. f32 softmax.
    ``segment_ids``: optional [B, S] packed segment ids — block-diagonal
    masking (attend only within equal segments)."""
    s = q.shape[2]
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    if segment_ids is not None:
        seg_ok = (segment_ids[:, None, :, None]
                  == segment_ids[:, None, None, :])
        scores = jnp.where(seg_ok, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention(q, k, v, valid_len):
    """q: [B, H, D]; k,v: [B, H, S, D]; valid_len: scalar or per-row [B]
    vector — masked single-query attention."""
    s = k.shape[2]
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    vl = jnp.asarray(valid_len)
    if vl.ndim:
        mask = jnp.arange(s)[None, None, :] < vl[:, None, None]
    else:
        mask = (jnp.arange(s) < vl)[None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhk,bhkd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention(q, k_pool, v_pool, page_tables, valid_len, hmap):
    """q: [B, H, D]; k_pool/v_pool: [num_pages, KVH, page_size, D];
    page_tables: [B, max_pages] i32 (entries >= num_pages are unallocated
    sentinels: clamped for the gather, masked by valid_len); hmap: [H] i32
    q-head -> kv-head map. Gathers the pool into the dense per-row view and
    defers to the dense oracle."""
    b = q.shape[0]
    num_pages, kvh, ps, d = k_pool.shape
    tbl = jnp.minimum(jnp.asarray(page_tables, jnp.int32), num_pages - 1)
    maxp = tbl.shape[1]
    hm = jnp.asarray(hmap)

    def dense(pool):  # [B, maxp, KVH, ps, D] -> [B, H, maxp * ps, D]
        rows = pool[tbl].transpose(0, 2, 1, 3, 4).reshape(b, kvh,
                                                          maxp * ps, d)
        return rows[:, hm]

    return decode_attention(q, dense(k_pool), dense(v_pool),
                            jnp.asarray(valid_len))


def rmsnorm(x, scale, eps=1e-5):
    """x: [N, D]; scale: [D]."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)

"""GQA/MHA attention layer with RoPE, optional QKV bias, KV caching."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.models.layers import attention_core as core
from repro.models.layers.rope import apply_rope


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    """Q-heads are padded to cfg.padded_heads for TP alignment (NamedSharding
    needs exact divisibility). Padded heads are zero-MASKED at the attention
    output, so their weights receive zero gradient and the function is
    exactly the unpadded model (see _head_mask)."""
    d, kvh, dh = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    hp = cfg.padded_heads
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    sc = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, hp, dh)) * sc).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, kvh, dh)) * sc).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, kvh, dh)) * sc).astype(dt),
        "wo": (jax.random.normal(ks[3], (hp, dh, d)) * sc).astype(dt),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((hp, dh), dt)
        p["bk"] = jnp.zeros((kvh, dh), dt)
        p["bv"] = jnp.zeros((kvh, dh), dt)
    return p


def _head_mask(cfg: ModelConfig, dtype):
    hp = cfg.padded_heads
    if hp == cfg.num_heads:
        return None
    return (jnp.arange(hp) < cfg.num_heads).astype(dtype)[None, None, :, None]


def _hmap(cfg: ModelConfig):
    import numpy as np
    rep = max(1, cfg.num_heads // cfg.num_kv_heads)
    return np.minimum(np.arange(cfg.padded_heads) // rep,
                      cfg.num_kv_heads - 1)


def _project_qkv(params, cfg: ModelConfig, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.attn_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.seq_shard_kv and x.shape[1] > 1:
        # replicated-kv fallback: force k/v sequence-sharded so GSPMD lowers
        # the projection to a local matmul + (cheap bf16) all-gather in the
        # attention einsum, instead of split-contraction + f32 all-reduce
        from jax.sharding import PartitionSpec as P
        try:
            k = jax.lax.with_sharding_constraint(k, P(None, "model", None, None))
            v = jax.lax.with_sharding_constraint(v, P(None, "model", None, None))
        except (ValueError, RuntimeError):
            pass  # no mesh context (single-device tests)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary_factor)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary_factor)
    return q, k, v


def _kernel_path(cfg: ModelConfig, q, prefix_len: int, flash: bool) -> bool:
    """Whether training attention runs the flash kernel: the caller allows
    it, the kernels are on, the mask is plain causal (no prefix, no
    softcap) and the kernel's blocks tile the sequence within its VMEM
    budget."""
    _, s, _, d = q.shape
    return (flash and ops.use_kernels(cfg) and not prefix_len
            and not cfg.attn_logit_softcap
            and fa.fits(s, d, q.dtype.itemsize))


def apply(params: dict, cfg: ModelConfig, x: jax.Array, *, positions=None,
          prefix_len: int = 0, chunk_q: int = 512, segment_ids=None,
          mesh=None, batch_axes=("data",), flash: bool = True) -> jax.Array:
    """Training/prefill forward (causal). x: [B, S, D] -> [B, S, D].

    ``positions``: [S] or [B, S] RoPE positions (packed batches pass
    per-segment-reset positions). ``segment_ids``: [B, S] packed segment
    ids (0 = pad) — attention is block-diagonal over equal segments.
    Plain causal attention runs the flash kernel where ``_kernel_path``
    allows it (per shard of ``mesh``), everything else
    ``chunked_attention``; ``flash=False`` keeps a caller on the latter
    (the encoder-decoder's decoder). The counters
    ``attention/kernel_layers`` and ``attention/xla_layers`` count the
    layers traced on each path."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(params, cfg, x, positions)
    kernel = _kernel_path(cfg, q, prefix_len, flash)
    obs.metrics.counter("kernel_layers" if kernel else "xla_layers",
                        subsystem="attention").inc()
    if kernel:
        hmap = _hmap(cfg)
        out = ops.flash_attention(q, core.expand_kv(k, hmap),
                                  core.expand_kv(v, hmap), causal=True,
                                  segment_ids=segment_ids, mesh=mesh,
                                  batch_axes=batch_axes)
    else:
        out = core.chunked_attention(q, k, v, hmap=_hmap(cfg),
                                     chunk_q=chunk_q, causal=True,
                                     prefix_len=prefix_len,
                                     softcap=cfg.attn_logit_softcap,
                                     segment_ids=segment_ids)
    out = out.astype(x.dtype)
    hm = _head_mask(cfg, out.dtype)
    if hm is not None:
        out = out * hm
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def apply_prefill(params, cfg: ModelConfig, x, *, prefix_len: int = 0,
                  chunk_q: int = 512, cache_len: int = 0):
    """Like apply() but also returns (k, v) padded to cache_len for the cache."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = core.chunked_attention(q, k, v, hmap=_hmap(cfg), chunk_q=chunk_q,
                                 causal=True, prefix_len=prefix_len,
                                 softcap=cfg.attn_logit_softcap)
    out = out.astype(x.dtype)
    hm = _head_mask(cfg, out.dtype)
    if hm is not None:
        out = out * hm
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    if cache_len and cache_len > s:
        pad = [(0, 0), (0, cache_len - s), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return out, (k, v)


def apply_prefill_chunk(params, cfg: ModelConfig, x, k_cache, v_cache, start):
    """One chunk of an incremental prefill. x: [B, C, D] chunk tokens at
    positions [start, start+C); caches [B, Smax, KVH, Dh] carry every
    earlier chunk's K/V. Writes this chunk's K/V at ``start`` (a traced
    scalar — one compile per chunk shape, not per offset) and attends the
    chunk queries over the whole cache with the causal mask anchored at
    ``q_offset=start``; cache positions past start+C are zero AND causally
    masked, so the result equals the single-shot prefill chunk-for-chunk.
    Returns (out [B,C,D], new_k, new_v)."""
    b, c, _ = x.shape
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(c)
    q, k, v = _project_qkv(params, cfg, x, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), start, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), start, axis=1)
    out = core.full_attention(q, k_cache, v_cache, hmap=_hmap(cfg),
                              causal=True, q_offset=start,
                              softcap=cfg.attn_logit_softcap)
    out = out.astype(x.dtype)
    hm = _head_mask(cfg, out.dtype)
    if hm is not None:
        out = out * hm
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, k_cache, v_cache


def apply_decode(params, cfg: ModelConfig, x, k_cache, v_cache, pos):
    """One-token decode. x: [B, 1, D]; caches [B, Smax, KVH, Dh]; pos: scalar
    or per-row [B] vector index of the new token (per-slot positions for
    continuous batching). Returns (out [B,1,D], new_k, new_v)."""
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]
    q, k, v = _project_qkv(params, cfg, x, positions)
    # batched scatter: row i writes at its own pos[i]; out-of-bounds writes
    # (finished slots stepped past max_len) are dropped
    k_cache = k_cache.at[jnp.arange(b), pos].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[jnp.arange(b), pos].set(v[:, 0].astype(v_cache.dtype))
    out = core.decode_attention(q, k_cache, v_cache, pos + 1,
                                hmap=_hmap(cfg),
                                softcap=cfg.attn_logit_softcap)
    out = out.astype(x.dtype)
    hm = _head_mask(cfg, out.dtype)
    if hm is not None:
        out = out * hm
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, k_cache, v_cache


def apply_decode_paged(params, cfg: ModelConfig, x, k_pool, v_pool, pages,
                       pos):
    """One-token decode against a shared page pool. x: [B, 1, D]; pools
    [num_pages, KVH, page_size, Dh] (one layer's slice); pages: [B,
    max_pages] i32 per-slot page tables (entries >= num_pages unallocated);
    pos: per-row [B] write position. The new K/V scatters into pool page
    ``pages[b, pos // page_size]``; writes through sentinel entries (freed
    or overrun slots) land out of bounds and drop, so a finished slot that
    keeps riding the decode chunk can never touch a reassigned page.
    Returns (out [B,1,D], new_k_pool, new_v_pool)."""
    b = x.shape[0]
    num_pages, ps = k_pool.shape[0], k_pool.shape[2]
    maxp = pages.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]
    q, k, v = _project_qkv(params, cfg, x, positions)
    pidx = pos // ps
    page = jnp.where(pidx < maxp,
                     pages[jnp.arange(b), jnp.minimum(pidx, maxp - 1)],
                     num_pages)
    off = pos % ps
    k_pool = k_pool.at[page, :, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[page, :, off].set(v[:, 0].astype(v_pool.dtype))
    hmap = _hmap(cfg)
    if ops.use_kernels(cfg):
        out = ops.paged_decode_attention(q, k_pool, v_pool, pages, pos + 1,
                                         hmap)
    else:
        # reference path: gather the row-major dense view through the table
        # (clamped — garbage rows sit past valid_len and mask to exact
        # zeros) and reuse the dense decode attention, so paged and dense
        # engines are bit-identical on this path
        tbl = jnp.minimum(pages, num_pages - 1)
        kvh, dh = k_pool.shape[1], k_pool.shape[3]
        dense = lambda pool: pool[tbl].transpose(0, 1, 3, 2, 4).reshape(  # noqa: E731
            b, maxp * ps, kvh, dh)
        kd, vd = dense(k_pool), dense(v_pool)
        out = core.decode_attention(q, kd, vd, pos + 1, hmap=hmap,
                                    softcap=cfg.attn_logit_softcap)
    out = out.astype(x.dtype)
    hm = _head_mask(cfg, out.dtype)
    if hm is not None:
        out = out * hm
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, k_pool, v_pool

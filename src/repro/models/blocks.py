"""Transformer/SSM block definitions with a uniform interface.

Every block apply returns ``(x, aux)`` where aux is a scalar auxiliary loss
(0 where not applicable) so heterogeneous stacks scan uniformly.
Residual connections live inside the block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import attention, mla, mlp, moe, norms, ssm

ZERO = jnp.zeros((), jnp.float32)


# ------------------------------------------------------------- attention block


def attn_block_init(key: jax.Array, cfg: ModelConfig, d_ff: int = 0) -> dict:
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.dtype)
    return {
        "ln1": norms.init(cfg.d_model, dt),
        "attn": (mla.init(k1, cfg) if cfg.use_mla else attention.init(k1, cfg)),
        "ln2": norms.init(cfg.d_model, dt),
        "mlp": mlp.init(k2, cfg.d_model, d_ff or cfg.d_ff, cfg),
    }


def attn_block_apply(params, cfg: ModelConfig, x, *, prefix_len=0, chunk_q=512,
                     positions=None, segment_ids=None, mesh=None,
                     batch_axes=("data",)):
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        h = mla.apply(params["attn"], cfg, h, chunk_q=chunk_q)
    else:
        h = attention.apply(params["attn"], cfg, h, prefix_len=prefix_len,
                            chunk_q=chunk_q, positions=positions,
                            segment_ids=segment_ids, mesh=mesh,
                            batch_axes=batch_axes)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    x = x + mlp.apply(params["mlp"], cfg, h)
    return x, ZERO


def attn_block_prefill(params, cfg: ModelConfig, x, *, cache_len, prefix_len=0,
                       chunk_q=512):
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        h, kv = mla.apply_prefill(params["attn"], cfg, h, cache_len=cache_len,
                                  chunk_q=chunk_q)
    else:
        h, kv = attention.apply_prefill(params["attn"], cfg, h,
                                        cache_len=cache_len,
                                        prefix_len=prefix_len, chunk_q=chunk_q)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    x = x + mlp.apply(params["mlp"], cfg, h)
    return x, kv


def attn_block_decode(params, cfg: ModelConfig, x, cache0, cache1, pos):
    """cache0/cache1: (k, v) for GQA or (ckv, kpe) for MLA."""
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        h, cache0, cache1 = mla.apply_decode(params["attn"], cfg, h, cache0,
                                             cache1, pos)
    else:
        h, cache0, cache1 = attention.apply_decode(params["attn"], cfg, h,
                                                   cache0, cache1, pos)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    x = x + mlp.apply(params["mlp"], cfg, h)
    return x, cache0, cache1


def attn_block_prefill_chunk(params, cfg: ModelConfig, x, k_cache, v_cache,
                             start):
    """One chunk of an incremental prefill (GQA only — chunked prefill
    rejects MLA upstream)."""
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    h, k_cache, v_cache = attention.apply_prefill_chunk(
        params["attn"], cfg, h, k_cache, v_cache, start)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    x = x + mlp.apply(params["mlp"], cfg, h)
    return x, k_cache, v_cache


def attn_block_decode_paged(params, cfg: ModelConfig, x, k_pool, v_pool,
                            pages, pos):
    """Paged-KV decode (GQA only — the paged layout rejects MLA upstream)."""
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    h, k_pool, v_pool = attention.apply_decode_paged(
        params["attn"], cfg, h, k_pool, v_pool, pages, pos)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    x = x + mlp.apply(params["mlp"], cfg, h)
    return x, k_pool, v_pool


# ------------------------------------------------------------- MoE block


def moe_block_init(key: jax.Array, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    dt = jnp.dtype(cfg.dtype)
    return {
        "ln1": norms.init(cfg.d_model, dt),
        "attn": (mla.init(k1, cfg) if cfg.use_mla else attention.init(k1, cfg)),
        "ln2": norms.init(cfg.d_model, dt),
        "moe": moe.init(k2, cfg),
    }


def moe_block_apply(params, cfg: ModelConfig, x, *, mesh=None,
                    batch_axes=("data",), chunk_q=512, positions=None,
                    segment_ids=None):
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        h = mla.apply(params["attn"], cfg, h, chunk_q=chunk_q)
    else:
        h = attention.apply(params["attn"], cfg, h, chunk_q=chunk_q,
                            positions=positions, segment_ids=segment_ids,
                            mesh=mesh, batch_axes=batch_axes)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    y, aux = moe.apply(params["moe"], cfg, h, mesh=mesh, batch_axes=batch_axes)
    return x + y, aux * cfg.router_aux_loss


def moe_block_prefill(params, cfg: ModelConfig, x, *, cache_len, mesh=None,
                      batch_axes=("data",), chunk_q=512):
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        h, kv = mla.apply_prefill(params["attn"], cfg, h, cache_len=cache_len,
                                  chunk_q=chunk_q)
    else:
        h, kv = attention.apply_prefill(params["attn"], cfg, h,
                                        cache_len=cache_len, chunk_q=chunk_q)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    y, _ = moe.apply(params["moe"], cfg, h, mesh=mesh, batch_axes=batch_axes)
    return x + y, kv


def moe_block_decode(params, cfg: ModelConfig, x, cache0, cache1, pos, *,
                     mesh=None, batch_axes=("data",)):
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    if cfg.use_mla:
        h, cache0, cache1 = mla.apply_decode(params["attn"], cfg, h, cache0,
                                             cache1, pos)
    else:
        h, cache0, cache1 = attention.apply_decode(params["attn"], cfg, h,
                                                   cache0, cache1, pos)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    if cfg.moe_impl == "ep" and mesh is not None:
        # masked-source EP dispatch: minimal expert FLOPs even though decode
        # activations are model-replicated (see moe.apply_ep_decode)
        y, _ = moe.apply_ep_decode(params["moe"], cfg, h, mesh, batch_axes)
    else:
        y, _ = moe.apply_dense(params["moe"], cfg, h)
    return x + y, cache0, cache1


def moe_block_prefill_chunk(params, cfg: ModelConfig, x, k_cache, v_cache,
                            start, *, mesh=None, batch_axes=("data",)):
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    h, k_cache, v_cache = attention.apply_prefill_chunk(
        params["attn"], cfg, h, k_cache, v_cache, start)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    y, _ = moe.apply(params["moe"], cfg, h, mesh=mesh, batch_axes=batch_axes)
    return x + y, k_cache, v_cache


def moe_block_decode_paged(params, cfg: ModelConfig, x, k_pool, v_pool,
                           pages, pos):
    """Paged-KV MoE decode (dense expert dispatch only — EP-MoE decode is
    mesh-coupled and stays on the dense cache path)."""
    h = norms.apply(params["ln1"], x, cfg.norm_eps)
    h, k_pool, v_pool = attention.apply_decode_paged(
        params["attn"], cfg, h, k_pool, v_pool, pages, pos)
    x = x + h
    h = norms.apply(params["ln2"], x, cfg.norm_eps)
    y, _ = moe.apply_dense(params["moe"], cfg, h)
    return x + y, k_pool, v_pool


# ------------------------------------------------------------- SSM block


def ssm_block_init(key: jax.Array, cfg: ModelConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    return {"ln": norms.init(cfg.d_model, dt), "mamba": ssm.init(key, cfg)}


def ssm_block_apply(params, cfg: ModelConfig, x):
    h = norms.apply(params["ln"], x, cfg.norm_eps)
    return x + ssm.apply(params["mamba"], cfg, h), ZERO


def ssm_block_prefill(params, cfg: ModelConfig, x):
    h = norms.apply(params["ln"], x, cfg.norm_eps)
    out, state = ssm.apply(params["mamba"], cfg, h, return_state=True)
    return x + out, state


def ssm_block_decode(params, cfg: ModelConfig, x, conv_state, ssm_state):
    h = norms.apply(params["ln"], x, cfg.norm_eps)
    out, conv_state, ssm_state = ssm.apply_decode(params["mamba"], cfg, h,
                                                  conv_state, ssm_state)
    return x + out, conv_state, ssm_state

"""Decoder-only language model assembly (dense / moe / ssm / hybrid / vlm).

Parameters for the L transformer blocks are STACKED (leading axis L) and the
body is a ``lax.scan`` over layers. This is what makes AdaGradSelect's
per-step dynamic block selection recompile-free: block masks become runtime
vectors indexed by scan position (see core/partition.py).

Uniform API (registry.py exposes the same for encdec):
    init(key, cfg)                                    -> params
    apply_train(params, cfg, batch, ...)              -> (logits, aux, extra)
    init_cache(cfg, batch_size, max_len)              -> cache
    prefill(params, cfg, batch, max_len, ...)         -> (last_logits, cache)
    decode_step(params, cfg, tokens, cache, ...)      -> (logits, cache)

``batch``: {"tokens": [B,S] i32, optional "patch_embeds": [B,Np,D]}.
Returned logits are aligned with batch["tokens"] positions for every family.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks
from repro.models.layers import norms

# --------------------------------------------------------------- utilities


def stack_init(init_fn, key, n):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _remat(f, cfg: ModelConfig):
    if cfg.remat == "none":
        return f
    if cfg.remat == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(f)


def _gated(apply_fn, p_l, x, mask_bit):
    from repro.core.gated import gated_block_apply
    return gated_block_apply(apply_fn, p_l, x, mask_bit)


def scan_stack(cfg: ModelConfig, apply_fn, x, stacked, masks=None):
    """Scan ``apply_fn(params_l, x) -> (x, aux)`` over a stacked param group.
    If cfg.gate_weight_grads and masks ([L] f32/bool) given, frozen layers
    skip their weight-gradient computation via lax.cond (DESIGN 3.3)."""
    gate = cfg.gate_weight_grads and masks is not None

    def body(carry, xs):
        x, aux = carry
        if gate:
            p_l, m_l = xs
            y, a = _gated(apply_fn, p_l, x, m_l)
        else:
            y, a = apply_fn(xs, x)
        return (y, aux + a), None

    xs = (stacked, masks) if gate else stacked
    (x, aux), _ = jax.lax.scan(_remat(body, cfg), (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux


def _embed_tokens(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if cfg.family == "vlm":  # gemma-style embedding scale
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _logits(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        out = jnp.einsum("bsd,vd->bsv", x, params["embed"]["tok"])
    else:
        out = jnp.einsum("bsd,dv->bsv", x, params["lm_head"]["w"])
    if cfg.logits_softcap:
        out = jnp.tanh(out / cfg.logits_softcap) * cfg.logits_softcap
    vp = cfg.padded_vocab_size
    if vp != cfg.vocab_size:
        # TP-alignment vocab padding: pad logits masked to -inf (exact CE,
        # never decoded)
        bias = jnp.where(jnp.arange(vp) < cfg.vocab_size, 0.0, -1e30)
        out = out + bias.astype(out.dtype)
    return out


def _hybrid_split(cfg: ModelConfig):
    p = cfg.shared_attn_period
    nsite = cfg.num_layers // p
    rem = cfg.num_layers - nsite * p
    return p, nsite, rem


# --------------------------------------------------------------- init


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: dict = {
        "embed": {"tok": (jax.random.normal(keys[0], (cfg.padded_vocab_size,
                                                      cfg.d_model))
                          * cfg.d_model**-0.5).astype(dt)},
        "final_norm": norms.init(cfg.d_model, dt),
    }
    if cfg.family in ("dense", "vlm"):
        params["layers"] = stack_init(
            lambda k: blocks.attn_block_init(k, cfg), keys[1], cfg.num_layers)
    elif cfg.family == "moe":
        if cfg.first_k_dense:
            params["dense_layers"] = stack_init(
                lambda k: blocks.attn_block_init(k, cfg), keys[1], cfg.first_k_dense)
        params["moe_layers"] = stack_init(
            lambda k: blocks.moe_block_init(k, cfg), keys[2],
            cfg.num_layers - cfg.first_k_dense)
    elif cfg.family == "ssm":
        params["layers"] = stack_init(
            lambda k: blocks.ssm_block_init(k, cfg), keys[1], cfg.num_layers)
    elif cfg.family == "hybrid":
        params["layers"] = stack_init(
            lambda k: blocks.ssm_block_init(k, cfg), keys[1], cfg.num_layers)
        params["shared_attn"] = blocks.attn_block_init(keys[2], cfg)
    else:
        raise ValueError(cfg.family)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": (jax.random.normal(
            keys[3], (cfg.d_model, cfg.padded_vocab_size))
            * cfg.d_model**-0.5).astype(dt)}
    if cfg.mtp_depth:
        params["mtp"] = {
            "norm_h": norms.init(cfg.d_model, dt),
            "norm_e": norms.init(cfg.d_model, dt),
            "proj": (jax.random.normal(keys[4], (2 * cfg.d_model, cfg.d_model))
                     * (2 * cfg.d_model)**-0.5).astype(dt),
            "block": (blocks.moe_block_init(keys[5], cfg) if cfg.family == "moe"
                      else blocks.attn_block_init(keys[5], cfg)),
        }
    return params


# --------------------------------------------------------------- train fwd


def _check_packed_support(cfg: ModelConfig):
    """Packed batches need block-diagonal attention; families whose token
    mixing is not per-position-maskable (SSM state scans, the shared-attn
    hybrid) and the MLA/vlm paths don't implement it — reject loudly rather
    than silently training across example boundaries."""
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"segment-packed batches are not supported for family="
            f"{cfg.family!r}: the SSM state scan carries context across "
            f"segment boundaries. Use the unpacked pipeline (pack=False) "
            f"for this architecture.")
    if cfg.family == "vlm":
        raise ValueError(
            "segment-packed batches are not supported for family='vlm' "
            "(the patch prefix is shared by every row); use pack=False")
    if cfg.use_mla:
        raise ValueError(
            "segment-packed batches are not implemented for MLA attention; "
            "use pack=False or a GQA/MHA architecture")
    if cfg.mtp_depth:
        raise ValueError(
            "segment-packed batches are not implemented for mtp_depth > 0: "
            "the MTP head's attention is not segment-masked and its "
            "shift-2 loss would cross example boundaries; use pack=False")


def apply_train(params: dict, cfg: ModelConfig, batch: dict, *, mesh=None,
                batch_axes=("data",), masks: dict | None = None):
    """-> (logits aligned to batch['tokens'], aux_loss, extra).

    Packed SFT batches (data/pipeline) additionally carry
    ``segment_ids`` [B, S] (0 = pad) and ``positions`` [B, S]
    (per-segment reset): attention becomes block-diagonal over segments and
    RoPE sees each example at its unpacked positions, so the packed forward
    equals running every segment as its own row."""
    tokens = batch["tokens"]
    masks = masks or {}
    segment_ids = batch.get("segment_ids")
    positions = batch.get("positions")
    if segment_ids is not None:
        _check_packed_support(cfg)
    x = _embed_tokens(params, cfg, tokens)
    prefix_len = 0
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        prefix_len = patches.shape[1]

    aux = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "vlm"):
        fn = partial(_apply_attn_block, cfg, mesh, batch_axes, prefix_len,
                     positions, segment_ids)
        x, a = scan_stack(cfg, fn, x, params["layers"], masks.get("layers"))
        aux += a
    elif cfg.family == "moe":
        if cfg.first_k_dense:
            fn = partial(_apply_attn_block, cfg, mesh, batch_axes, 0,
                         positions, segment_ids)
            x, a = scan_stack(cfg, fn, x, params["dense_layers"],
                              masks.get("dense_layers"))
            aux += a
        fn = partial(_apply_moe_block, cfg, mesh, batch_axes, positions,
                     segment_ids)
        x, a = scan_stack(cfg, fn, x, params["moe_layers"], masks.get("moe_layers"))
        aux += a
    elif cfg.family == "ssm":
        fn = partial(_apply_ssm_block, cfg)
        x, a = scan_stack(cfg, fn, x, params["layers"], masks.get("layers"))
        aux += a
    elif cfg.family == "hybrid":
        x, a = _hybrid_train(params, cfg, x, masks, mesh, batch_axes)
        aux += a

    h_pre = x
    x = norms.apply(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x)
    if cfg.family == "vlm":
        logits = logits[:, prefix_len:]

    extra = {}
    if cfg.mtp_depth:
        extra["mtp_logits"] = _mtp_logits(params, cfg, h_pre, tokens, mesh,
                                          batch_axes)
    return logits, aux, extra


def _apply_attn_block(cfg, mesh, batch_axes, prefix_len, positions,
                      segment_ids, p_l, x):
    return blocks.attn_block_apply(p_l, cfg, x, prefix_len=prefix_len,
                                   positions=positions,
                                   segment_ids=segment_ids, mesh=mesh,
                                   batch_axes=batch_axes)


def _apply_moe_block(cfg, mesh, batch_axes, positions, segment_ids, p_l, x):
    return blocks.moe_block_apply(p_l, cfg, x, mesh=mesh,
                                  batch_axes=batch_axes, positions=positions,
                                  segment_ids=segment_ids)


def _apply_ssm_block(cfg, p_l, x):
    return blocks.ssm_block_apply(p_l, cfg, x)


def _hybrid_train(params, cfg: ModelConfig, x, masks, mesh, batch_axes):
    """ssm layers with the shared attn block applied every period layers.
    Shared-block weight sharing = same params closed over at every site."""
    p, nsite, rem = _hybrid_split(cfg)
    stacked = params["layers"]
    lmask = masks.get("layers")
    grouped = jax.tree.map(
        lambda t: t[: nsite * p].reshape(nsite, p, *t.shape[1:]), stacked)
    gmask = (None if lmask is None
             else lmask[: nsite * p].reshape(nsite, p))
    shared = params["shared_attn"]
    smask = masks.get("shared_attn")

    def outer(carry, xs):
        x, aux = carry
        grp, gm = xs if gmask is not None else (xs, None)
        x, a = scan_stack(cfg, partial(_apply_ssm_block, cfg), x, grp, gm)
        aux += a
        shared_fn = lambda p_l, xx: blocks.attn_block_apply(  # noqa: E731
            p_l, cfg, xx, mesh=mesh, batch_axes=batch_axes)
        if cfg.gate_weight_grads and smask is not None:
            x, a2 = _gated(shared_fn, shared, x, smask)
        else:
            x, a2 = shared_fn(shared, x)
        return (x, aux + a2), None

    xs = (grouped, gmask) if gmask is not None else grouped
    (x, aux), _ = jax.lax.scan(_remat(outer, cfg),
                               (x, jnp.zeros((), jnp.float32)), xs)
    if rem:
        tail = jax.tree.map(lambda t: t[nsite * p:], stacked)
        tmask = None if lmask is None else lmask[nsite * p:]
        x, a = scan_stack(cfg, partial(_apply_ssm_block, cfg), x, tail, tmask)
        aux += a
    return x, aux


def _mtp_logits(params, cfg: ModelConfig, h_pre, tokens, mesh, batch_axes):
    """Deepseek-style depth-1 multi-token prediction head: predict t+2."""
    m = params["mtp"]
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    e = _embed_tokens(params, cfg, nxt)
    h = jnp.concatenate([norms.apply(m["norm_h"], h_pre, cfg.norm_eps),
                         norms.apply(m["norm_e"], e, cfg.norm_eps)], axis=-1)
    h = h @ m["proj"]
    if cfg.family == "moe":
        h, _ = blocks.moe_block_apply(m["block"], cfg, h, mesh=mesh,
                                      batch_axes=batch_axes)
    else:
        h, _ = blocks.attn_block_apply(m["block"], cfg, h, mesh=mesh,
                                       batch_axes=batch_axes)
    h = norms.apply(params["final_norm"], h, cfg.norm_eps)
    return _logits(params, cfg, h)


# --------------------------------------------------------------- caches


def _attn_layer_count(cfg: ModelConfig) -> int:
    if cfg.family in ("dense", "vlm"):
        return cfg.num_layers
    if cfg.family == "moe":
        return cfg.num_layers
    return 0


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    """Slot-based cache: every leaf has a batch/slot axis (axis 1 for the
    stacked per-layer leaves, axis 0 for ``pos``). ``pos`` is a PER-SLOT
    [B] i32 vector — the number of tokens written per slot — so slots at
    mixed decode progress can coexist (continuous batching)."""
    dt = jnp.dtype(cfg.dtype)
    cache: dict = {"pos": jnp.zeros((batch_size,), jnp.int32)}
    b = batch_size
    if cfg.family in ("dense", "vlm", "moe"):
        n = _attn_layer_count(cfg)
        if cfg.use_mla:
            cache["ckv"] = jnp.zeros((n, b, max_len, cfg.kv_lora_rank), dt)
            cache["kpe"] = jnp.zeros((n, b, max_len, cfg.qk_rope_head_dim), dt)
        else:
            kvh, dh = cfg.num_kv_heads, cfg.head_dim
            cache["k"] = jnp.zeros((n, b, max_len, kvh, dh), dt)
            cache["v"] = jnp.zeros((n, b, max_len, kvh, dh), dt)
    if cfg.family in ("ssm", "hybrid"):
        from repro.models.layers import ssm as ssm_mod
        d_inner, nheads, gn = ssm_mod.dims(cfg)
        lc = cfg.num_layers
        km1 = cfg.ssm_conv - 1
        cache["conv"] = {"x": jnp.zeros((lc, b, km1, d_inner), dt),
                         "b": jnp.zeros((lc, b, km1, gn), dt),
                         "c": jnp.zeros((lc, b, km1, gn), dt)}
        cache["state"] = jnp.zeros((lc, b, nheads, cfg.ssm_head_dim,
                                    cfg.ssm_state), jnp.float32)
    if cfg.family == "hybrid":
        p, nsite, rem = _hybrid_split(cfg)
        kvh, dh = cfg.num_kv_heads, cfg.head_dim
        cache["ak"] = jnp.zeros((nsite, b, max_len, kvh, dh), dt)
        cache["av"] = jnp.zeros((nsite, b, max_len, kvh, dh), dt)
    return cache


# --------------------------------------------------------------- prefill


def prefill(params: dict, cfg: ModelConfig, batch: dict, max_len: int, *,
            mesh=None, batch_axes=("data",), lengths=None):
    """``lengths`` (optional [B] i32): true prompt lengths when ``tokens`` is
    right-padded to a shared bucket (bucketed prefill). Per-row logits are
    gathered at ``lengths - 1`` and ``cache["pos"] = lengths``; K/V
    projections are pointwise in sequence and attention is causal, so rows
    are exact regardless of pad tokens to their right. Only length-indexed
    KV families support this (dense/moe, incl. MLA) — SSM state scans would
    absorb the pad tokens, so ssm/hybrid/vlm reject ``lengths``."""
    if lengths is not None and cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"bucket-padded prefill (lengths=) is not supported for family="
            f"{cfg.family!r}: its recurrent/prefix state would absorb the "
            f"pad tokens. Serve this family with exact-length prefill "
            f"(ServeEngine falls back automatically).")
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = _embed_tokens(params, cfg, tokens)
    prefix_len = 0
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        prefix_len = patches.shape[1]
    cache = init_cache(cfg, b, max_len)
    seq = x.shape[1]

    if cfg.family in ("dense", "vlm"):
        def body(x, p_l):
            x, kv = blocks.attn_block_prefill(p_l, cfg, x, cache_len=max_len,
                                              prefix_len=prefix_len)
            return x, kv
        x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
        cache["k"], cache["v"] = ks, vs
    elif cfg.family == "moe":
        kss, vss = [], []
        if cfg.first_k_dense:
            def body_d(x, p_l):
                return blocks.attn_block_prefill(p_l, cfg, x, cache_len=max_len)
            x, kv_d = jax.lax.scan(body_d, x, params["dense_layers"])
            kss.append(kv_d[0]); vss.append(kv_d[1])

        def body_m(x, p_l):
            return blocks.moe_block_prefill(p_l, cfg, x, cache_len=max_len,
                                            mesh=mesh, batch_axes=batch_axes)
        x, kv_m = jax.lax.scan(body_m, x, params["moe_layers"])
        kss.append(kv_m[0]); vss.append(kv_m[1])
        if cfg.use_mla:
            cache["ckv"] = jnp.concatenate(kss, axis=0)
            cache["kpe"] = jnp.concatenate(vss, axis=0)
        else:
            cache["k"] = jnp.concatenate(kss, axis=0)
            cache["v"] = jnp.concatenate(vss, axis=0)
    elif cfg.family == "ssm":
        def body_s(x, p_l):
            x, st = blocks.ssm_block_prefill(p_l, cfg, x)
            return x, st
        x, (convs, states) = jax.lax.scan(body_s, x, params["layers"])
        cache["conv"], cache["state"] = convs, states
    elif cfg.family == "hybrid":
        x, cache = _hybrid_prefill(params, cfg, x, cache, max_len)

    x = norms.apply(params["final_norm"], x, cfg.norm_eps)
    if lengths is None:
        logits = _logits(params, cfg, x[:, -1:, :])[:, 0]
        cache["pos"] = jnp.full((b,), seq, jnp.int32)
    else:
        lv = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
        xl = x[jnp.arange(b), jnp.clip(lv - 1, 0, seq - 1)][:, None]
        logits = _logits(params, cfg, xl)[:, 0]
        cache["pos"] = lv
    return logits, cache


def prefill_chunk(params: dict, cfg: ModelConfig, tokens, cache: dict, start,
                  lengths, last_logits, *, mesh=None, batch_axes=("data",)):
    """One chunk of an incremental prefill over a scratch dense cache.

    ``tokens``: [B, C] chunk at positions [start, start+C) (``start`` is a
    traced i32 scalar — one compile per chunk SHAPE, not per offset);
    ``cache``: {"k", "v"} scratch [L, B, S_bucket, KVH, Dh] carrying earlier
    chunks' K/V; ``lengths``: [B] true prompt lengths; ``last_logits``:
    [B, V] carried last-position logits, updated for rows whose final prompt
    token falls inside this chunk. Returns (last_logits', cache'). Chunked
    prefill needs per-chunk KV append + offset attention, which the MLA and
    recurrent families don't implement — dense/moe GQA only."""
    if cfg.family not in ("dense", "moe") or cfg.use_mla:
        raise ValueError(
            f"chunked prefill is not supported for family={cfg.family!r}"
            f"{' with MLA' if cfg.use_mla else ''}: it needs per-chunk KV "
            f"append with offset attention. Use single-shot prefill "
            f"(prefill_chunk=0) for this architecture.")
    b, c = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    x = _embed_tokens(params, cfg, tokens)

    if cfg.family == "dense":
        def body(x, xs):
            p_l, k_l, v_l = xs
            x, k_l, v_l = blocks.attn_block_prefill_chunk(p_l, cfg, x, k_l,
                                                          v_l, start)
            return x, (k_l, v_l)
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                             cache["v"]))
        cache = {**cache, "k": ks, "v": vs}
    else:
        kss, vss = [], []
        off = 0
        if cfg.first_k_dense:
            def body_d(x, xs):
                p_l, k_l, v_l = xs
                x, k_l, v_l = blocks.attn_block_prefill_chunk(p_l, cfg, x,
                                                              k_l, v_l, start)
                return x, (k_l, v_l)
            nd = cfg.first_k_dense
            x, (k_d, v_d) = jax.lax.scan(
                body_d, x, (params["dense_layers"], cache["k"][:nd],
                            cache["v"][:nd]))
            kss.append(k_d); vss.append(v_d); off = nd

        def body_m(x, xs):
            p_l, k_l, v_l = xs
            x, k_l, v_l = blocks.moe_block_prefill_chunk(
                p_l, cfg, x, k_l, v_l, start, mesh=mesh, batch_axes=batch_axes)
            return x, (k_l, v_l)
        x, (k_m, v_m) = jax.lax.scan(
            body_m, x, (params["moe_layers"], cache["k"][off:],
                        cache["v"][off:]))
        kss.append(k_m); vss.append(v_m)
        cache = {**cache, "k": jnp.concatenate(kss, axis=0),
                 "v": jnp.concatenate(vss, axis=0)}

    x = norms.apply(params["final_norm"], x, cfg.norm_eps)
    # rows whose last prompt token lives in this chunk pick up their logits
    idx = jnp.clip(lengths - 1 - start, 0, c - 1)
    sel = _logits(params, cfg, x[jnp.arange(b), idx][:, None])[:, 0]
    hit = (lengths - 1 >= start) & (lengths - 1 < start + c)
    last_logits = jnp.where(hit[:, None], sel.astype(last_logits.dtype),
                            last_logits)
    return last_logits, cache


def init_paged_cache(cfg: ModelConfig, batch_size: int, max_len: int,
                     page_size: int, num_pages: int) -> dict:
    """Paged serve cache: K/V live in shared pools [L, num_pages, KVH,
    page_size, Dh] (kv head outside the page, so the paged decode kernel's
    per-head page block is a whole (page_size, Dh) tile) and each slot maps
    virtual positions through ``pages``
    [B, max_pages] (i32; the ``num_pages`` sentinel marks unallocated
    entries — see serve/pages.py). ``pos`` semantics are identical to the
    dense cache. SSM has no length-indexed KV, so paging is a no-op and the
    regular cache is returned; families whose decode state the paged layout
    cannot express raise with the supported alternatives."""
    if cfg.family == "ssm":
        return init_cache(cfg, batch_size, max_len)
    if cfg.family not in ("dense", "moe") or cfg.use_mla:
        raise ValueError(
            f"paged KV cache is not supported for family={cfg.family!r}"
            f"{' with MLA' if cfg.use_mla else ''}: only plain GQA/MHA "
            f"dense and moe stacks (and ssm, where it is a no-op) have a "
            f"paged decode path. Use kv_layout='dense' for this "
            f"architecture.")
    dt = jnp.dtype(cfg.dtype)
    maxp = -(-max_len // page_size)
    n = _attn_layer_count(cfg)
    kvh, dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "pos": jnp.zeros((batch_size,), jnp.int32),
        "pages": jnp.full((batch_size, maxp), num_pages, jnp.int32),
        "k": jnp.zeros((n, num_pages, kvh, page_size, dh), dt),
        "v": jnp.zeros((n, num_pages, kvh, page_size, dh), dt),
    }


def insert_slots_paged(cache: dict, src: dict, slots, lengths,
                       starts=None) -> dict:
    """Scatter a dense prefill cache (``src``: k/v [L, n, S, KVH, Dh]) into
    the page pools through the device-mirrored table ``cache["pages"]``.
    ``slots``: [n] i32 slot per row (entries == num_slots are admission
    padding — their writes drop); ``lengths``: [n] true prompt lengths —
    positions >= length route to the OOB sentinel and drop, so bucket-pad
    garbage never reaches the pool. ``starts`` (optional, [n] or scalar
    i32): first position to write per row — positions below it also drop,
    which is the prefix-cache aliased-page write rule: table entries below
    ``start`` map to pages shared read-only with other slots (or the radix
    tree) and must never be written through; the suffix scatter begins at
    the slot's first private (or copied-on-write) page."""
    k_pool, v_pool = cache["k"], cache["v"]
    num_pages, ps = k_pool.shape[1], k_pool.shape[3]
    num_slots, maxp = cache["pages"].shape
    slots = jnp.asarray(slots, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    s_max = src["k"].shape[2]
    valid_slot = slots < num_slots
    tbl = jnp.where(valid_slot[:, None],
                    cache["pages"][jnp.minimum(slots, num_slots - 1)],
                    num_pages)                                   # [n, maxp]
    t = jnp.arange(s_max)
    page = tbl[:, jnp.minimum(t // ps, maxp - 1)]                # [n, s_max]
    ok = (t[None, :] < lengths[:, None]) & (t[None, :] // ps < maxp)
    if starts is not None:
        starts = jnp.broadcast_to(jnp.asarray(starts, jnp.int32),
                                  lengths.shape)
        ok = ok & (t[None, :] >= starts[:, None])
    page = jnp.where(ok, page, num_pages)
    off = jnp.broadcast_to(t % ps, page.shape)
    # the split (page, off) index puts its [n, s_max] dims first
    to_pool = lambda x: x.transpose(1, 2, 0, 3, 4)  # noqa: E731
    k_pool = k_pool.at[:, page, :, off].set(to_pool(src["k"]).astype(
        k_pool.dtype))
    v_pool = v_pool.at[:, page, :, off].set(to_pool(src["v"]).astype(
        v_pool.dtype))
    pos = cache["pos"].at[slots].set(lengths)
    return {**cache, "k": k_pool, "v": v_pool, "pos": pos}


def insert_slots(cache: dict, src: dict, slots) -> dict:
    """Write the rows of ``src`` (a cache of batch size n, e.g. from a fresh
    prefill) into ``cache`` at slot indices ``slots`` ([n] i32). Every cache
    leaf carries the slot axis at position 1 (stacked [L, B, ...]) except
    the per-slot scalars ``pos``/``src_len`` ([B]). Out-of-range slot
    indices are dropped (JAX scatter semantics), which admission code uses
    to pad groups to a fixed batch."""
    slots = jnp.asarray(slots, jnp.int32)
    out = {}
    for key, val in cache.items():
        if key in ("pos", "src_len"):
            out[key] = val.at[slots].set(src[key].astype(val.dtype))
        else:
            out[key] = jax.tree.map(
                lambda c, s: c.at[:, slots].set(s.astype(c.dtype)),
                val, src[key])
    return out


def _hybrid_prefill(params, cfg, x, cache, max_len):
    p, nsite, rem = _hybrid_split(cfg)
    stacked = params["layers"]
    grouped = jax.tree.map(
        lambda t: t[: nsite * p].reshape(nsite, p, *t.shape[1:]), stacked)
    shared = params["shared_attn"]

    def outer(x, grp):
        def inner(x, p_l):
            x, st = blocks.ssm_block_prefill(p_l, cfg, x)
            return x, st
        x, states = jax.lax.scan(inner, x, grp)
        x, akv = blocks.attn_block_prefill(shared, cfg, x, cache_len=max_len)
        return x, (states, akv)

    x, (sts, akvs) = jax.lax.scan(outer, x, grouped)
    convs, states = sts
    # [nsite, p, B, ...] -> [nsite*p, B, ...] (convs is a {x,b,c} dict)
    flat2 = lambda t: t.reshape(nsite * p, *t.shape[2:])  # noqa: E731
    convs = jax.tree.map(flat2, convs)
    states = flat2(states)
    cache["ak"], cache["av"] = akvs
    if rem:
        tail = jax.tree.map(lambda t: t[nsite * p:], stacked)

        def inner_t(x, p_l):
            x, st = blocks.ssm_block_prefill(p_l, cfg, x)
            return x, st
        x, (convs_t, states_t) = jax.lax.scan(inner_t, x, tail)
        convs = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                             convs, convs_t)
        states = jnp.concatenate([states, states_t], axis=0)
    cache["conv"], cache["state"] = convs, states
    return x, cache


# --------------------------------------------------------------- decode


def decode_step(params: dict, cfg: ModelConfig, tokens, cache: dict, *,
                mesh=None, batch_axes=("data",)):
    """tokens [B, 1] -> (logits [B, V], new cache). ``cache["pos"]`` may be
    a scalar (legacy caches) or a per-slot [B] vector; each row attends over
    and writes at its own position."""
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32),
                           (tokens.shape[0],))
    x = _embed_tokens(params, cfg, tokens)

    paged = "pages" in cache
    if cfg.family in ("dense", "vlm") and paged:
        pages = cache["pages"]

        def body_p(x, xs):
            p_l, kp, vp = xs
            x, kp, vp = blocks.attn_block_decode_paged(p_l, cfg, x, kp, vp,
                                                       pages, pos)
            return x, (kp, vp)
        x, (kp, vp) = jax.lax.scan(body_p, x, (params["layers"], cache["k"],
                                               cache["v"]))
        cache = {**cache, "k": kp, "v": vp}
    elif cfg.family == "moe" and paged:
        pages = cache["pages"]
        c0s, c1s = [], []
        off = 0
        if cfg.first_k_dense:
            def body_dp(x, xs):
                p_l, kp, vp = xs
                x, kp, vp = blocks.attn_block_decode_paged(p_l, cfg, x, kp,
                                                           vp, pages, pos)
                return x, (kp, vp)
            nd = cfg.first_k_dense
            x, (kp, vp) = jax.lax.scan(
                body_dp, x, (params["dense_layers"], cache["k"][:nd],
                             cache["v"][:nd]))
            c0s.append(kp); c1s.append(vp); off = nd

        def body_mp(x, xs):
            p_l, kp, vp = xs
            x, kp, vp = blocks.moe_block_decode_paged(p_l, cfg, x, kp, vp,
                                                      pages, pos)
            return x, (kp, vp)
        x, (kp, vp) = jax.lax.scan(
            body_mp, x, (params["moe_layers"], cache["k"][off:],
                         cache["v"][off:]))
        c0s.append(kp); c1s.append(vp)
        cache = {**cache, "k": jnp.concatenate(c0s, axis=0),
                 "v": jnp.concatenate(c1s, axis=0)}
    elif cfg.family in ("dense", "vlm"):
        def body(x, xs):
            p_l, c0, c1 = xs
            x, c0, c1 = blocks.attn_block_decode(p_l, cfg, x, c0, c1, pos)
            return x, (c0, c1)
        keys = ("ckv", "kpe") if cfg.use_mla else ("k", "v")
        x, (c0, c1) = jax.lax.scan(body, x, (params["layers"],
                                             cache[keys[0]], cache[keys[1]]))
        cache = {**cache, keys[0]: c0, keys[1]: c1}
    elif cfg.family == "moe":
        keys = ("ckv", "kpe") if cfg.use_mla else ("k", "v")
        c0s, c1s = [], []
        off = 0
        if cfg.first_k_dense:
            def body_d(x, xs):
                p_l, c0, c1 = xs
                x, c0, c1 = blocks.attn_block_decode(p_l, cfg, x, c0, c1, pos)
                return x, (c0, c1)
            nd = cfg.first_k_dense
            x, (c0, c1) = jax.lax.scan(
                body_d, x, (params["dense_layers"],
                            cache[keys[0]][:nd], cache[keys[1]][:nd]))
            c0s.append(c0); c1s.append(c1); off = nd

        def body_m(x, xs):
            p_l, c0, c1 = xs
            x, c0, c1 = blocks.moe_block_decode(p_l, cfg, x, c0, c1, pos,
                                                mesh=mesh, batch_axes=batch_axes)
            return x, (c0, c1)
        x, (c0, c1) = jax.lax.scan(
            body_m, x, (params["moe_layers"],
                        cache[keys[0]][off:], cache[keys[1]][off:]))
        c0s.append(c0); c1s.append(c1)
        cache = {**cache, keys[0]: jnp.concatenate(c0s, axis=0),
                 keys[1]: jnp.concatenate(c1s, axis=0)}
    elif cfg.family == "ssm":
        def body_s(x, xs):
            p_l, cv, st = xs
            x, cv, st = blocks.ssm_block_decode(p_l, cfg, x, cv, st)
            return x, (cv, st)
        x, (cv, st) = jax.lax.scan(body_s, x, (params["layers"],
                                               cache["conv"], cache["state"]))
        cache = {**cache, "conv": cv, "state": st}
    elif cfg.family == "hybrid":
        x, cache = _hybrid_decode(params, cfg, x, cache, pos)

    x = norms.apply(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x)[:, 0]
    cache = {**cache, "pos": pos + 1}
    return logits, cache


def _hybrid_decode(params, cfg, x, cache, pos):
    p, nsite, rem = _hybrid_split(cfg)
    stacked = params["layers"]
    grouped = jax.tree.map(
        lambda t: t[: nsite * p].reshape(nsite, p, *t.shape[1:]), stacked)
    shared = params["shared_attn"]
    grp2 = lambda t: t[: nsite * p].reshape(nsite, p, *t.shape[1:])  # noqa: E731
    cv_g = jax.tree.map(grp2, cache["conv"])
    st_g = grp2(cache["state"])

    def outer(x, xs):
        grp, cv, st, ak, av = xs

        def inner(x, ys):
            p_l, cvl, stl = ys
            x, cvl, stl = blocks.ssm_block_decode(p_l, cfg, x, cvl, stl)
            return x, (cvl, stl)
        x, (cv, st) = jax.lax.scan(inner, x, (grp, cv, st))
        x, ak, av = blocks.attn_block_decode(shared, cfg, x, ak, av, pos)
        return x, (cv, st, ak, av)

    x, (cv, st, ak, av) = jax.lax.scan(
        outer, x, (grouped, cv_g, st_g, cache["ak"], cache["av"]))
    flat2 = lambda t: t.reshape(nsite * p, *t.shape[2:])  # noqa: E731
    conv = jax.tree.map(flat2, cv)
    state = flat2(st)
    if rem:
        tail = jax.tree.map(lambda t: t[nsite * p:], stacked)

        def inner_t(x, ys):
            p_l, cvl, stl = ys
            x, cvl, stl = blocks.ssm_block_decode(p_l, cfg, x, cvl, stl)
            return x, (cvl, stl)
        x, (cv_t, st_t) = jax.lax.scan(
            inner_t, x, (tail, jax.tree.map(lambda t: t[nsite * p:], cache["conv"]),
                         cache["state"][nsite * p:]))
        conv = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                            conv, cv_t)
        state = jnp.concatenate([state, st_t], axis=0)
    cache = {**cache, "conv": conv, "state": state, "ak": ak, "av": av}
    return x, cache

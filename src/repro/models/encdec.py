"""Encoder-decoder model (seamless-m4t-medium backbone).

The audio frontend is a stub: ``batch["src_embeds"]`` carries precomputed
frame embeddings [B, S_src, D]. Encoder blocks are bidirectional; decoder
blocks have causal self-attention + cross-attention to the encoder output.
Same stacked-params/scan structure as lm.py so AdaGradSelect treats encoder
and decoder blocks as separate arms.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks
from repro.models.layers import attention, mlp, norms
from repro.models.layers import attention_core as core
from repro.models.lm import _logits, _remat, scan_stack, stack_init


# ------------------------------------------------------------- blocks


def enc_block_init(key, cfg: ModelConfig):
    return blocks.attn_block_init(key, cfg)


def enc_block_apply(p_l, cfg: ModelConfig, x):
    h = norms.apply(p_l["ln1"], x, cfg.norm_eps)
    q, k, v = attention._project_qkv(p_l["attn"], cfg, h, jnp.arange(h.shape[1]))
    out = core.chunked_attention(q, k, v, causal=False)
    x = x + jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p_l["attn"]["wo"])
    h = norms.apply(p_l["ln2"], x, cfg.norm_eps)
    return x + mlp.apply(p_l["mlp"], cfg, h), jnp.zeros((), jnp.float32)


def dec_block_init(key, cfg: ModelConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    return {
        "ln1": norms.init(cfg.d_model, dt),
        "self_attn": attention.init(k1, cfg),
        "ln2": norms.init(cfg.d_model, dt),
        "cross_attn": attention.init(k2, cfg),
        "ln3": norms.init(cfg.d_model, dt),
        "mlp": mlp.init(k3, cfg.d_model, cfg.d_ff, cfg),
    }


def _cross_attend(p_attn, cfg: ModelConfig, x, enc_kv, src_len=None):
    """Cross-attention: q from x, (k, v) precomputed from encoder output.
    ``src_len`` ([B] or scalar) masks cache positions beyond each row's true
    encoder length (the slot cache pads sources to max_len // ratio)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p_attn["wq"])
    if cfg.attn_bias:
        q = q + p_attn["bq"]
    k, v = enc_kv
    mask = None
    if src_len is not None:
        sl = jnp.asarray(src_len)
        sl = sl[:, None] if sl.ndim else sl
        mask = jnp.broadcast_to(jnp.arange(k.shape[1])[None, :] < sl,
                                (x.shape[0], k.shape[1]))
    out = core.full_attention(q, k, v, causal=False, kv_len_mask=mask)
    return jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p_attn["wo"])


def _enc_kv(p_attn, cfg: ModelConfig, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p_attn["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p_attn["wv"])
    if cfg.attn_bias:
        k = k + p_attn["bk"]
        v = v + p_attn["bv"]
    return k, v


def dec_block_apply(p_l, cfg: ModelConfig, x, enc_out):
    h = norms.apply(p_l["ln1"], x, cfg.norm_eps)
    # the decoder's self-attention stays on XLA: the flash kernel has not
    # been compiled or measured inside the encoder-decoder step
    h = attention.apply(p_l["self_attn"], cfg, h, flash=False)
    x = x + h
    h = norms.apply(p_l["ln2"], x, cfg.norm_eps)
    x = x + _cross_attend(p_l["cross_attn"], cfg, h,
                          _enc_kv(p_l["cross_attn"], cfg, enc_out))
    h = norms.apply(p_l["ln3"], x, cfg.norm_eps)
    return x + mlp.apply(p_l["mlp"], cfg, h), jnp.zeros((), jnp.float32)


# ------------------------------------------------------------- model API


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.dtype)
    params = {
        "embed": {"tok": (jax.random.normal(keys[0], (cfg.padded_vocab_size,
                                                      cfg.d_model))
                          * cfg.d_model**-0.5).astype(dt)},
        "enc_layers": stack_init(lambda k: enc_block_init(k, cfg), keys[1],
                                 cfg.num_encoder_layers),
        "enc_norm": norms.init(cfg.d_model, dt),
        "dec_layers": stack_init(lambda k: dec_block_init(k, cfg), keys[2],
                                 cfg.num_layers),
        "final_norm": norms.init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": (jax.random.normal(
            keys[3], (cfg.d_model, cfg.padded_vocab_size))
            * cfg.d_model**-0.5).astype(dt)}
    return params


def encode(params, cfg: ModelConfig, src_embeds, masks=None):
    masks = masks or {}
    x = src_embeds.astype(jnp.dtype(cfg.dtype))
    x, aux = scan_stack(cfg, lambda p_l, xx: enc_block_apply(p_l, cfg, xx),
                        x, params["enc_layers"], (masks or {}).get("enc_layers"))
    return norms.apply(params["enc_norm"], x, cfg.norm_eps), aux


def apply_train(params: dict, cfg: ModelConfig, batch: dict, *, mesh=None,
                batch_axes=("data",), masks: dict | None = None):
    masks = masks or {}
    enc_out, aux = encode(params, cfg, batch["src_embeds"], masks)
    x = jnp.take(params["embed"]["tok"], batch["tokens"], axis=0)

    def body(carry, xs):
        x, a = carry
        if cfg.gate_weight_grads and masks.get("dec_layers") is not None:
            from repro.core.gated import gated_block_apply
            p_l, m_l = xs
            y, al = gated_block_apply(
                lambda pp, xx: dec_block_apply(pp, cfg, xx, enc_out), p_l, x, m_l)
        else:
            y, al = dec_block_apply(xs, cfg, x, enc_out)
        return (y, a + al), None

    dmask = masks.get("dec_layers")
    xs = ((params["dec_layers"], dmask) if (cfg.gate_weight_grads and dmask is not None)
          else params["dec_layers"])
    (x, a), _ = jax.lax.scan(_remat(body, cfg), (x, jnp.zeros((), jnp.float32)), xs)
    aux += a
    x = norms.apply(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), aux, {}


# ------------------------------------------------------------- serving


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    dt = jnp.dtype(cfg.dtype)
    kvh, dh = cfg.num_kv_heads, cfg.head_dim
    ld = cfg.num_layers
    src_len = max_len // cfg.frontend_len_ratio
    return {
        "pos": jnp.zeros((batch_size,), jnp.int32),
        "src_len": jnp.zeros((batch_size,), jnp.int32),
        "k": jnp.zeros((ld, batch_size, max_len, kvh, dh), dt),
        "v": jnp.zeros((ld, batch_size, max_len, kvh, dh), dt),
        "ck": jnp.zeros((ld, batch_size, src_len, kvh, dh), dt),
        "cv": jnp.zeros((ld, batch_size, src_len, kvh, dh), dt),
    }


def prefill(params: dict, cfg: ModelConfig, batch: dict, max_len: int, *,
            mesh=None, batch_axes=("data",)):
    """Encodes src, runs the decoder over the target prefix, returns cache
    with self-attn KV + precomputed cross-attn KV."""
    enc_out, _ = encode(params, cfg, batch["src_embeds"])
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    cache = init_cache(cfg, b, max_len)

    def body(x, p_l):
        h = norms.apply(p_l["ln1"], x, cfg.norm_eps)
        h, kv = attention.apply_prefill(p_l["self_attn"], cfg, h,
                                        cache_len=max_len)
        x = x + h
        h = norms.apply(p_l["ln2"], x, cfg.norm_eps)
        ckv = _enc_kv(p_l["cross_attn"], cfg, enc_out)
        x = x + _cross_attend(p_l["cross_attn"], cfg, h, ckv)
        h = norms.apply(p_l["ln3"], x, cfg.norm_eps)
        x = x + mlp.apply(p_l["mlp"], cfg, h)
        return x, (kv, ckv)

    x, (kv, ckv) = jax.lax.scan(body, x, params["dec_layers"])
    cache["k"], cache["v"] = kv
    s_src = enc_out.shape[1]
    src_cache = max_len // cfg.frontend_len_ratio
    if s_src < src_cache:  # pad to the slot-cache length; decode masks by
        # per-slot src_len, so padding never changes the attention output
        pad = [(0, 0), (0, 0), (0, src_cache - s_src), (0, 0), (0, 0)]
        ckv = (jnp.pad(ckv[0], pad), jnp.pad(ckv[1], pad))
    cache["ck"], cache["cv"] = ckv
    cache["src_len"] = jnp.full((b,), s_src, jnp.int32)
    x = norms.apply(params["final_norm"], x, cfg.norm_eps)
    cache["pos"] = jnp.full((b,), s, jnp.int32)
    return _logits(params, cfg, x[:, -1:, :])[:, 0], cache


def insert_slots(cache: dict, src: dict, slots):
    from repro.models import lm
    return lm.insert_slots(cache, src, slots)


def decode_step(params: dict, cfg: ModelConfig, tokens, cache: dict, *,
                mesh=None, batch_axes=("data",)):
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32),
                           (tokens.shape[0],))
    src_len = cache.get("src_len")
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)

    def body(x, xs):
        p_l, k_c, v_c, ck, cv = xs
        h = norms.apply(p_l["ln1"], x, cfg.norm_eps)
        h, k_c, v_c = attention.apply_decode(p_l["self_attn"], cfg, h, k_c,
                                             v_c, pos)
        x = x + h
        h = norms.apply(p_l["ln2"], x, cfg.norm_eps)
        x = x + _cross_attend(p_l["cross_attn"], cfg, h, (ck, cv),
                              src_len=src_len)
        h = norms.apply(p_l["ln3"], x, cfg.norm_eps)
        x = x + mlp.apply(p_l["mlp"], cfg, h)
        return x, (k_c, v_c)

    x, (k_c, v_c) = jax.lax.scan(body, x, (params["dec_layers"], cache["k"],
                                           cache["v"], cache["ck"], cache["cv"]))
    cache = {**cache, "k": k_c, "v": v_c, "pos": pos + 1}
    x = norms.apply(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x)[:, 0], cache

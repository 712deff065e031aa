"""Plain reference of the dense decoder the configurations describe
(Qwen2.5-0.5B): RMSNorm, grouped-query attention with rotary positions,
a SwiGLU feed-forward and tied embeddings, in straightforward
``jax.numpy`` at float32 and ``Precision.HIGHEST``. It imports nothing of
the program and takes nothing the program made: it draws its own weights
from the seed (bench/weights.py), and it trains through
bench/reference/adagradselect.py, the published AdaGradSelect / AdamW
algorithm written out again.

Departures from the published models, shared with the program:
  * rotary positions rotate interleaved pairs (2i, 2i+1) rather than the
    two halves of a head; with random weights the two are the same model
    up to a fixed permutation of the query and key columns.

``mode="fp8"`` is the control: every matmul operand is rounded to
float8_e4m3 with one scale per tensor (the straight-through estimator
carries gradients), the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _q(x, mode: str):
    if mode == "f32":
        return x
    if mode != "fp8":
        raise ValueError(mode)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = jax.lax.stop_gradient(amax / 448.0)
    r = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(r - x)


def _mm(eq: str, a, b, mode: str):
    return jnp.einsum(eq, _q(a, mode), _q(b, mode), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta: float, rot: int):
    """x [S, heads, Dh]; rotates interleaved pairs of the first ``rot``
    dims by position ``pos`` [S]."""
    if rot == 0:
        return x
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = pos.astype(F32)[:, None] * inv                 # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([r.reshape(x[..., :rot].shape), x[..., rot:]], -1)


def _attention(q, k, v, allowed, mode: str):
    """q [S, H, Dh], k/v [T, KVH, Dh], allowed [S, T] bool. Query head h
    reads key/value head h // (H / KVH)."""
    s, h, dh = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, h // kvh, dh) * dh ** -0.5
    scores = _mm("sgkd,tgd->gkst", qg, k, mode)
    scores = jnp.where(allowed, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _mm("gkst,tgd->sgkd", probs, v, mode)
    return out.reshape(s, h, dh)


def _layer(c: dict, w: dict, x, pos, allowed, mode: str):
    """One pre-norm decoder layer over a sequence x [S, D]."""
    h = _rms(x, w["ln1"], c["eps"])
    q = _mm("sd,dhk->shk", h, w["wq"], mode)
    k = _mm("sd,dhk->shk", h, w["wk"], mode)
    v = _mm("sd,dhk->shk", h, w["wv"], mode)
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q, pos, c["theta"], c["rot"])
    k = _rope(k, pos, c["theta"], c["rot"])
    a = _attention(q, k, v, allowed, mode)
    x = x + _mm("shk,hkd->sd", a, w["wo"], mode)
    h = _rms(x, w["ln2"], c["eps"])
    g = jax.nn.silu(_mm("sd,df->sf", h, w["wg"], mode))
    u = _mm("sd,df->sf", h, w["wu"], mode)
    return x + _mm("sf,fd->sd", g * u, w["wd"], mode)


LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
              "wg", "wu", "wd")


def consts(config: dict) -> dict:
    """What the layer math needs from a configuration file."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    dh = int(config.get("head_dim") or d // h)
    rot = int(dh * float(config.get("partial_rotary_factor", 1.0)))
    return {"eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"]), "rot": rot - rot % 2}


def hidden(c: dict, w: dict, tokens, seg, pos, mode: str, as_f32=lambda x: x):
    """Final-norm hidden states [S, D] of one row. ``seg`` [S]: packed
    segment ids (attention stays within equal ids, causal by index)."""
    idx = jnp.arange(tokens.shape[0])
    allowed = (seg[:, None] == seg[None, :]) & (idx[:, None] >= idx[None, :])
    x = as_f32(w["embed"])[tokens]
    layers = {k: w[k] for k in LAYER_KEYS if k in w}

    @jax.checkpoint
    def body(x, wl):
        return _layer(c, jax.tree.map(as_f32, wl), x, pos, allowed, mode), None

    x, _ = jax.lax.scan(body, x, layers)
    return _rms(x, as_f32(w["final_norm"]), c["eps"])


def logits(c: dict, w: dict, h, mode: str, as_f32=lambda x: x):
    return _mm("sd,vd->sv", h, as_f32(w["embed"]), mode)


def row_loss_sum(w, c, tokens, loss_mask, seg, pos, mode):
    """Summed next-token cross entropy of one row over its supervised
    targets (position t predicts t + 1 where loss_mask[t + 1] is 1)."""
    z = logits(c, w, hidden(c, w, tokens, seg, pos, mode), mode)[:-1]
    lse = jax.scipy.special.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum((lse - picked) * loss_mask[1:])

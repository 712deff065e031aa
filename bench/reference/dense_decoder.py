"""Plain reference of the dense decoder the configurations describe
(Qwen2.5-0.5B): RMSNorm, grouped-query attention with rotary positions,
a SwiGLU feed-forward and tied embeddings, in straightforward
``jax.numpy`` at float32 and ``Precision.HIGHEST``. It imports nothing of
the program and takes nothing the program made: it draws its own weights
from the seed (bench/weights.py), and its training step is the published
AdaGradSelect / AdamW algorithm written out again.

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
import numpy as np

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


# ------------------------------------------------------------- training


def block_layout(num_layers: int) -> dict:
    """Block ids of the method's arms: embedding, each layer, final norm."""
    return {"embed": 0, "layers": (1, num_layers + 1),
            "final_norm": num_layers + 1, "num_blocks": num_layers + 2}


def _block_norms(g: dict, nl: int):
    layer_sq = sum(jnp.sum(jnp.square(g[k]), axis=tuple(range(1, g[k].ndim)))
                   for k in LAYER_KEYS if k in g)
    return jnp.concatenate([jnp.sqrt(jnp.sum(jnp.square(g["embed"])))[None],
                            jnp.sqrt(layer_sq),
                            jnp.sqrt(jnp.sum(jnp.square(g["final_norm"])))[None]])


def _topk_mask(scores, k):
    _, idx = jax.lax.top_k(scores, k)
    return jnp.zeros(scores.shape, bool).at[idx].set(True)


def select(sel: dict, method: str, state: dict, norms, step: int,
           program_seed: int):
    """The block mask of one step. ``full``: every block. ``adagradselect``
    (paper Alg. 2): with probability eps_t the top-k blocks by cumulative
    gradient norm, otherwise k blocks drawn from Dirichlet(freq + delta) by
    Gumbel-top-k; eps_t = eps0 exp(-lambda t) within the first epoch.
    Random draws: key = fold_in(PRNGKey(seed), step) split into (eps, dir,
    gumbel, uniform), as the configuration specifies its seeding."""
    nb = norms.shape[0]
    if method == "full":
        return jnp.ones((nb,), bool)
    if method != "adagradselect":
        raise ValueError(f"no reference for method {method!r}")
    k = max(1, int(round(nb * sel["k_percent"] / 100.0)))
    key = jax.random.fold_in(jax.random.PRNGKey(program_seed), step)
    k_eps, k_dir, k_gum, _ = jax.random.split(key, 4)
    explore = _topk_mask(state["cum_norms"] + norms, k)
    probs = jax.random.dirichlet(k_dir, state["freq"] + sel["dirichlet_delta"])
    exploit = _topk_mask(jnp.log(probs + 1e-20)
                         + jax.random.gumbel(k_gum, (nb,)), k)
    eps = (sel["epsilon0"] * np.exp(-sel["epsilon_decay"] * step)
           if step < sel["steps_per_epoch"] else 0.0)
    return jnp.where(jax.random.uniform(k_eps) < eps, explore, exploit)


def learning_rate(opt: dict, t: int) -> float:
    warm = min(1.0, (t + 1) / max(1, opt["warmup_steps"]))
    frac = min(1.0, max(0.0, (t - opt["warmup_steps"])
                        / max(1, opt["total_steps"] - opt["warmup_steps"])))
    factor = {"constant": 1.0, "linear": 1.0 - frac,
              "cosine": 0.5 * (1 + np.cos(np.pi * frac))}[opt["schedule"]]
    return opt["lr"] * warm * factor


def _stored(x, dtype):
    """``x`` rounded to ``dtype`` and held at float32. A cast there and
    back may be folded away by the compiler (excess precision is allowed
    by default); ``reduce_precision`` is not."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _row_mask(mask, name: str, nl: int):
    lay = block_layout(nl)
    if name == "embed":
        return mask[lay["embed"]]
    if name == "final_norm":
        return mask[lay["final_norm"]]
    return mask[1:nl + 1]


def reference_train(c: dict, w0: dict, batches: list, *, nl: int, opt: dict,
                    sel: dict, method: str, program_seed: int,
                    mode: str = "f32", param_dtype=jnp.bfloat16) -> dict:
    """The first ``len(batches)`` steps of the job from weights ``w0`` (the
    stored dtype, e.g. bfloat16). Gradients at float32, clipped by global
    norm, block norms, selection, block-masked AdamW with per-block step
    counts; parameters are kept in ``param_dtype`` as the configuration
    stores them. Returns per-step losses and masks, the per-leaf norm of
    the first step's clipped gradient over the blocks it updated (what the
    optimizer's first moment holds), of the whole first gradient, and of
    each leaf's change after the last step."""
    # a fresh copy: p, m and v are donated to each update
    p = jax.tree.map(lambda x: jnp.array(x, F32, copy=True), w0)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    nb = block_layout(nl)["num_blocks"]
    counts = np.zeros((nb,), np.float32)
    state = {"cum_norms": jnp.zeros((nb,), F32), "freq": jnp.zeros((nb,), F32)}

    @jax.jit(donate_argnums=(1,))
    def row_grad(p, acc, tokens, loss_mask, seg, pos):
        lsum, g = jax.value_and_grad(row_loss_sum)(p, c, tokens, loss_mask,
                                                   seg, pos, mode)
        return lsum, jax.tree.map(jnp.add, acc, g)

    @jax.jit(donate_argnums=(0,))
    def clip_and_norms(g, denom):
        g = jax.tree.map(lambda x: x / denom, g)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        return g, _block_norms(g, nl), {k: jnp.sqrt(jnp.sum(jnp.square(x)))
                                        for k, x in g.items()}

    @jax.jit(donate_argnums=(0, 2, 3))
    def adamw(p, g, m, v, mask, cnt, lr):
        b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
        out = {}
        for k in p:
            sel_ = _row_mask(mask, k, nl)
            c_ = jnp.maximum(_row_mask(cnt, k, nl), 1.0)
            shp = (-1,) + (1,) * (p[k].ndim - 1) if k not in (
                "embed", "final_norm") else ()
            s_, c_ = jnp.reshape(sel_, shp), jnp.reshape(c_, shp)
            m2 = jnp.where(s_, b1 * m[k] + (1 - b1) * g[k], m[k])
            v2 = jnp.where(s_, b2 * v[k] + (1 - b2) * g[k] * g[k], v[k])
            upd = lr * (m2 / (1 - b1 ** c_) / (jnp.sqrt(v2 / (1 - b2 ** c_))
                                                + eps) + wd * p[k])
            p2 = jnp.where(s_, p[k] - upd, p[k])
            out[k] = (_stored(p2, param_dtype), m2, v2)
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})

    losses, masks, first, grad0 = [], [], None, None
    for t, b in enumerate(batches):
        acc = jax.tree.map(jnp.zeros_like, p)
        total = 0.0
        for r in range(b["tokens"].shape[0]):
            lsum, acc = row_grad(p, acc, *(jnp.asarray(b[k][r]) for k in (
                "tokens", "loss_mask", "segment_ids", "positions")))
            total += float(lsum)
        denom = max(float(np.sum(b["loss_mask"][:, 1:])), 1.0)
        g, norms, full = clip_and_norms(acc, denom)
        del acc
        mask = select(sel, method, state, norms, t, program_seed)
        if method == "adagradselect":
            state = {"cum_norms": state["cum_norms"] + norms,
                     "freq": state["freq"] + mask.astype(F32)}
        counts = counts + np.asarray(mask, np.float32)
        p, m, v = adamw(p, g, m, v, mask, jnp.asarray(counts),
                        learning_rate(opt, t))
        losses.append(total / denom)
        masks.append(np.asarray(mask))
        if t == 0:
            grad0 = {k: float(x) for k, x in full.items()}
            first = {k: float(jnp.sqrt(jnp.sum(jnp.square(x))))
                     / (1 - opt["b1"]) for k, x in m.items()}
        del g
    w0f = jax.tree.map(lambda x: x.astype(F32), w0)
    change = {k: float(jnp.sqrt(jnp.sum(jnp.square(p[k] - w0f[k]))))
              for k in p}
    return {"losses": losses, "masks": masks, "first_grad": first,
            "grad0": grad0, "change": change, "counts": counts}


"""Plain reference of the fine-tuning job: the published AdaGradSelect
block selection (paper Alg. 2) and block-masked AdamW, written out again in
straightforward ``jax.numpy`` at float32 over any model whose forward a
family gives as a loss. It imports nothing of the program.

A model's parameters fall into the blocks the method selects among, in
groups (``Group``): a group is one block (every leaf of it whole), or a
stack of ``length`` blocks (row ``i`` of each of its leaves is block
``start + i``).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


@dataclass(frozen=True)
class Group:
    start: int        # block id of the group's first block
    length: int       # blocks in the group
    stacked: bool     # leaves carry the blocks on their first axis
    keys: tuple       # the group's leaves, in the order their norms add


def num_blocks(groups) -> int:
    return sum(g.length for g in groups)


def _block_norms(g: dict, groups) -> jax.Array:
    parts = []
    for grp in groups:
        if grp.stacked:
            sq = sum(jnp.sum(jnp.square(g[k]), axis=tuple(range(1, g[k].ndim)))
                     for k in grp.keys if k in g)
            parts.append(jnp.sqrt(sq))
        else:
            sq = [jnp.sum(jnp.square(g[k])) for k in grp.keys if k in g]
            total = sq[0]
            for x in sq[1:]:
                total = total + x
            parts.append(jnp.sqrt(total)[None])
    return jnp.concatenate(parts)


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


def reference_train(row_loss, w0: dict, batches: list, groups, *, opt: dict,
                    sel: dict, method: str, program_seed: int,
                    param_dtype=jnp.bfloat16) -> dict:
    """The first ``len(batches)`` steps of the job from weights ``w0`` (the
    stored dtype, e.g. bfloat16). ``row_loss(w, tokens, loss_mask, seg,
    pos)``: the summed loss of one row. Gradients at float32, clipped by
    global norm, block norms, selection, block-masked AdamW with per-block
    step counts; parameters are kept in ``param_dtype`` as the
    configuration stores them. Returns per-step losses and masks, the
    per-leaf norm of the first step's clipped gradient over the blocks it
    updated (what the optimizer's first moment holds), of the whole first
    gradient, and of each leaf's change after the last step."""
    where = {k: grp for grp in groups for k in grp.keys}

    def rows(vec, k):
        grp = where[k]
        if grp.stacked:
            return vec[grp.start:grp.start + grp.length]
        return vec[grp.start]

    # a fresh copy: p, m and v are donated to each update
    p = jax.tree.map(lambda x: jnp.array(x, F32, copy=True), w0)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    nb = num_blocks(groups)
    counts = np.zeros((nb,), np.float32)
    state = {"cum_norms": jnp.zeros((nb,), F32), "freq": jnp.zeros((nb,), F32)}

    @jax.jit(donate_argnums=(1,))
    def row_grad(p, acc, tokens, loss_mask, seg, pos):
        lsum, g = jax.value_and_grad(row_loss)(p, tokens, loss_mask, seg, pos)
        return lsum, jax.tree.map(jnp.add, acc, g)

    @jax.jit(donate_argnums=(0,))
    def clip_and_norms(g, denom):
        g = jax.tree.map(lambda x: x / denom, g)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        return g, _block_norms(g, groups), {
            k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in g.items()}

    @jax.jit(donate_argnums=(0, 2, 3))
    def adamw(p, g, m, v, mask, cnt, lr):
        b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
        out = {}
        for k in p:
            sel_ = rows(mask, k)
            c_ = jnp.maximum(rows(cnt, k), 1.0)
            shp = ((-1,) + (1,) * (p[k].ndim - 1) if where[k].stacked
                   else ())
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

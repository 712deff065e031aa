"""Mixture-of-Experts FFN: top-k routing, shared experts, two execution paths.

``moe_impl="dense"``  — oracle: every expert computes every token, outputs are
    combined with the (sparse) routing weights. Exact top-k semantics with no
    capacity drops; used for small configs, tests, and as the reference the EP
    path is validated against.

``moe_impl="ep"``     — production expert parallelism: tokens are sharded over
    the mesh, experts are sharded over the ``model`` axis, and routing happens
    via sort + capacity-bucketed ``all_to_all`` inside ``shard_map`` (the
    deepseek-style dispatch/combine pattern, TPU-ICI native rather than a
    NCCL port). Overflowing tokens beyond capacity are dropped (standard).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers.mlp import _act


# ---------------------------------------------------------------- params


def init(key: jax.Array, cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.dtype)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in**-0.5).astype(dt)

    p = {
        "router": w(ks[0], (d, e), d).astype(jnp.float32),  # router kept f32
        "wg": w(ks[1], (e, d, f), d),
        "wu": w(ks[2], (e, d, f), d),
        "wd": w(ks[3], (e, f, d), f),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": w(sk[0], (d, fs), d),
            "wu": w(sk[1], (d, fs), d),
            "wd": w(sk[2], (fs, d), fs),
        }
    return p


def _route(cfg: ModelConfig, router_w, x_tokens):
    """x_tokens [T, D] -> (gates [T, K] f32, ids [T, K] i32, aux_loss scalar)."""
    logits = x_tokens.astype(jnp.float32) @ router_w          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, ids = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gates = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-9)
    # load-balance auxiliary loss (Switch-style): E * sum_e f_e * p_e
    e = cfg.num_experts
    me = jnp.mean(probs, axis=0)                               # mean prob per expert
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=1), axis=0)
    aux = e * jnp.sum(me * ce)
    return gates, ids, aux


def _expert_ffn(cfg: ModelConfig, wg, wu, wd, x):
    """x [..., D] with per-expert weights already selected."""
    h = _act(cfg.act)(x @ wg) * (x @ wu)
    return h @ wd


def _shared_ffn(cfg: ModelConfig, p, x):
    h = _act(cfg.act)(x @ p["wg"]) * (x @ p["wu"])
    return h @ p["wd"]


# ---------------------------------------------------------------- dense oracle


def apply_dense(params: dict, cfg: ModelConfig, x: jax.Array):
    """[B, S, D] -> ([B, S, D], aux_loss). Every expert runs on every token."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    gates, ids, aux = _route(cfg, params["router"], xt)
    # combine weights [T, E]: sum of gate where expert chosen
    comb = jnp.zeros((xt.shape[0], cfg.num_experts), jnp.float32)
    comb = comb.at[jnp.arange(xt.shape[0])[:, None], ids].add(gates)
    # all experts on all tokens: [E, T, D]
    outs = jax.vmap(lambda wg, wu, wd: _expert_ffn(cfg, wg, wu, wd, xt))(
        params["wg"], params["wu"], params["wd"])
    y = jnp.einsum("etd,te->td", outs.astype(jnp.float32), comb).astype(x.dtype)
    if cfg.num_shared_experts:
        y = y + _shared_ffn(cfg, params["shared"], xt)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------- EP path


def _masked_gather(x, idx, valid):
    """rows = x_padded[idx'] with invalid entries reading a zero pad row —
    self-masking, so no full-size select/where ever materializes (XLA:CPU
    loop-fuses selects with re-reads of every operand per output tile)."""
    n = x.shape[0]
    x_pad = jnp.pad(x, ((0, 1), (0, 0)))
    idx2 = jnp.where(valid, idx, n)          # [rows] int op — cheap
    return x_pad[idx2]


@jax.custom_vjp
def _permute_rows(x, fwd_idx, bwd_idx, fwd_valid, bwd_valid):
    """Gather-only row permutation: out[i] = fwd_valid[i] ? x[fwd_idx[i]] : 0.

    The VJP of a gather is a scatter-add — which XLA:CPU lowers to a serial
    row-update loop and which is the slow path on TPU too. Because our
    dispatch indices form a (partial) permutation, the backward is itself a
    gather with the precomputed inverse index map, so we define it that way:
        dx[j] = bwd_valid[j] ? dout[bwd_idx[j]] : 0.
    Both directions are single fused zero-padded gathers (Megablocks-style
    dispatch)."""
    return _masked_gather(x, fwd_idx, fwd_valid)


def _permute_rows_fwd(x, fwd_idx, bwd_idx, fwd_valid, bwd_valid):
    return _masked_gather(x, fwd_idx, fwd_valid), \
        (fwd_idx, bwd_idx, fwd_valid, bwd_valid)


def _permute_rows_bwd(res, g):
    import numpy as np
    fwd_idx, bwd_idx, fwd_valid, bwd_valid = res
    dx = _masked_gather(g, bwd_idx, bwd_valid)
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return dx, f0(fwd_idx), f0(bwd_idx), f0(fwd_valid), f0(bwd_valid)


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _dispatch_indices(dest, num_buckets, capacity):
    """dest [N] int32 bucket ids -> (slot [N] int32 in [0, buckets*cap], valid [N]).

    Entries are packed in stable order within each bucket; rank >= capacity
    is dropped (valid=False). Invalid entries get slot == buckets*capacity —
    callers must allocate one extra trash row so scatters never clobber
    real slots.
    """
    n = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    d_sorted = dest[order]
    counts = jnp.bincount(dest, length=num_buckets)
    starts = jnp.cumsum(counts) - counts
    rank_sorted = jnp.arange(n) - starts[d_sorted]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    valid = rank < capacity
    slot = jnp.where(valid, dest * capacity + rank, num_buckets * capacity)
    return slot, valid


def _ep_local(cfg: ModelConfig, params, x_loc, *, axis_name, num_shards,
              extra_axes=(), source_mask=None):
    """Body run per-device inside shard_map. x_loc: [t_loc, D].
    ``source_mask``: optional scalar bool — False disables dispatch from this
    device entirely (used by the decode path, where x is model-replicated)."""
    t_loc, d = x_loc.shape
    k = cfg.num_experts_per_tok
    e_loc = cfg.num_experts // num_shards
    gates, ids, aux = _route(cfg, params["router"], x_loc)
    for ax in (axis_name, *extra_axes):
        aux = jax.lax.pmean(aux, ax)

    n = t_loc * k
    fid = ids.reshape(n)                                  # global expert id per entry
    src = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)
    gate_flat = gates.reshape(n)
    dest_shard = fid // e_loc

    cap_send = max(1, int(-(-n // num_shards) * cfg.capacity_factor))
    slot, valid = _dispatch_indices(dest_shard, num_shards, cap_send)
    if source_mask is not None:
        valid = valid & source_mask
        slot = jnp.where(valid, slot, num_shards * cap_send)

    # --- entry-expanded tokens via broadcast (VJP = reshape-sum, no scatter)
    xe = jnp.broadcast_to(x_loc[:, None, :], (t_loc, k, d)).reshape(n, d)

    # --- send buffers via gather-only permutation (index arrays built with
    # cheap int32 scatters; row movement is gathers in fwd AND bwd)
    inv = jnp.full((num_shards * cap_send + 1,), n, jnp.int32)
    inv = inv.at[slot].set(jnp.arange(n, dtype=jnp.int32))    # slot -> entry
    inv = inv[:-1]
    slot_valid = inv < n
    inv_c = jnp.minimum(inv, n - 1)
    slot_c = jnp.minimum(slot, num_shards * cap_send - 1)
    sbuf = _permute_rows(xe, inv_c, slot_c, slot_valid, valid)
    sbuf = sbuf.reshape(num_shards, cap_send, d)
    fid_padded = jnp.concatenate([fid, jnp.zeros((1,), fid.dtype)])
    s_eid = jnp.where(slot_valid, (fid_padded[inv_c] % e_loc).astype(jnp.int32),
                      -1).reshape(num_shards, cap_send)

    # --- all_to_all: row j of rbuf is what shard j sent to me
    rbuf = jax.lax.all_to_all(sbuf, axis_name, 0, 0, tiled=True)
    r_eid = jax.lax.all_to_all(s_eid, axis_name, 0, 0, tiled=True)

    # --- local expert compute with a second capacity bucketing by expert
    rows = rbuf.reshape(num_shards * cap_send, d)
    eids = r_eid.reshape(num_shards * cap_send)
    nr = rows.shape[0]
    r_valid = eids >= 0
    cap_e = max(1, int(-(-(num_shards * cap_send) // e_loc) * cfg.capacity_factor))
    eslot, evalid = _dispatch_indices(jnp.where(r_valid, eids, 0), e_loc, cap_e)
    evalid = evalid & r_valid
    eslot = jnp.where(evalid, eslot, e_loc * cap_e)       # invalids -> trash
    einv = jnp.full((e_loc * cap_e + 1,), nr, jnp.int32)
    einv = einv.at[eslot].set(jnp.arange(nr, dtype=jnp.int32))
    einv = einv[:-1]
    e_valid_slot = einv < nr
    einv_c = jnp.minimum(einv, nr - 1)
    eslot_c = jnp.minimum(eslot, e_loc * cap_e - 1)
    ebuf = _permute_rows(rows, einv_c, eslot_c, e_valid_slot, evalid)
    ebuf = ebuf.reshape(e_loc, cap_e, d)
    h = jax.vmap(lambda wg, wu, wd, xe_: _expert_ffn(cfg, wg, wu, wd, xe_))(
        params["wg"], params["wu"], params["wd"], ebuf)     # [e_loc, cap_e, D]
    out_rows = _permute_rows(h.reshape(e_loc * cap_e, d), eslot_c, einv_c,
                             evalid, e_valid_slot)

    # --- reply all_to_all back to senders (same [shard, cap] layout)
    obuf = out_rows.reshape(num_shards, cap_send, d)
    back = jax.lax.all_to_all(obuf, axis_name, 0, 0, tiled=True)
    back = back.reshape(num_shards * cap_send, d)

    # --- combine at source: entries are token-major, so the combine is a
    # reshape-sum (no scatter-add)
    contrib = _permute_rows(back, slot_c, inv_c, valid, slot_valid)
    y = (contrib.astype(jnp.float32) * gate_flat[:, None]).reshape(
        t_loc, k, d).sum(axis=1)
    y = y.astype(x_loc.dtype)
    if cfg.num_shared_experts:
        y = y + _shared_ffn(cfg, params["shared"], x_loc)
    return y, aux


def apply_ep(params: dict, cfg: ModelConfig, x: jax.Array, mesh,
             batch_axes=("data",), model_axis="model"):
    """[B, S, D] -> ([B, S, D], aux). Tokens sharded over (batch_axes x
    model); experts over ``cfg.ep_axes`` (e.g. ("model",) for <=16-way EP,
    ("model","data") for deepseek's 256-expert 1-per-chip layout). The
    dispatch/combine all_to_all spans exactly the ep_axes plane."""
    ep_axes = tuple(ax for ax in cfg.ep_axes if ax in mesh.shape)
    num_shards = 1
    for ax in ep_axes:
        num_shards *= mesh.shape[ax]
    assert cfg.num_experts % num_shards == 0, (cfg.num_experts, num_shards)
    other_axes = tuple(ax for ax in (*batch_axes, model_axis)
                       if ax not in ep_axes)

    def body(xs, router, wg, wu, wd, shared):
        p = {"router": router, "wg": wg, "wu": wu, "wd": wd}
        if shared is not None:
            p["shared"] = shared
        b_loc, s_loc, d = xs.shape
        y, aux = _ep_local(cfg, p, xs.reshape(b_loc * s_loc, d),
                           axis_name=ep_axes, num_shards=num_shards,
                           extra_axes=other_axes)
        return y.reshape(b_loc, s_loc, d), aux

    shared = params.get("shared")
    espec = P(ep_axes)
    in_specs = (
        P(batch_axes, model_axis, None),           # x: batch over data, seq over model
        P(), espec, espec, espec,
        None if shared is None else P(),
    )
    out_specs = (P(batch_axes, model_axis, None), P())
    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x, params["router"], params["wg"], params["wu"], params["wd"], shared)
    return y, aux


def apply_ep_decode(params: dict, cfg: ModelConfig, x: jax.Array, mesh,
                    batch_axes=("data",), model_axis="model"):
    """Decode-time EP: x [B, 1, D] is *replicated* along the model axis (the
    attention path keeps activations model-replicated at decode). Only the
    model-rank-0 copy dispatches tokens — otherwise every expert shard would
    compute ``model``-many duplicates — and the combined output is psum-
    broadcast back along the model axis."""
    ep_axes = tuple(ax for ax in cfg.ep_axes if ax in mesh.shape)
    num_shards = 1
    for ax in ep_axes:
        num_shards *= mesh.shape[ax]
    other_axes = tuple(ax for ax in (*batch_axes, model_axis)
                       if ax not in ep_axes)

    def body(xs, router, wg, wu, wd, shared):
        p = {"router": router, "wg": wg, "wu": wu, "wd": wd}
        if shared is not None:
            p["shared"] = shared
        b_loc, s_loc, d = xs.shape
        x_loc = xs.reshape(b_loc * s_loc, d)
        is_src = jax.lax.axis_index(model_axis) == 0
        y, aux = _ep_local(cfg, p, jnp.where(is_src, x_loc, 0),
                           axis_name=ep_axes, num_shards=num_shards,
                           extra_axes=other_axes, source_mask=is_src)
        y = jax.lax.psum(jnp.where(is_src, y, 0), model_axis)
        return y.reshape(b_loc, s_loc, d), aux

    shared = params.get("shared")
    espec = P(ep_axes)
    in_specs = (
        P(batch_axes, None, None),
        P(), espec, espec, espec,
        None if shared is None else P(),
    )
    out_specs = (P(batch_axes, None, None), P())
    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(x, params["router"], params["wg"], params["wu"], params["wd"], shared)
    return y, aux


def apply(params: dict, cfg: ModelConfig, x: jax.Array, mesh=None,
          batch_axes=("data",), model_axis="model"):
    if cfg.moe_impl == "ep" and mesh is not None:
        return apply_ep(params, cfg, x, mesh, batch_axes, model_axis)
    return apply_dense(params, cfg, x)

"""Jit-ready wrappers around the Pallas kernels.

Handle shape normalization (flatten/pad to kernel layouts) and backend
dispatch: ``interpret=True`` on CPU (validation), compiled Mosaic on TPU.
``use_kernels(cfg)`` is the one switch that decides whether the training
step and the paged decode path call these at all.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import block_grad_norm as _bgn
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import masked_adamw as _ma
from repro.kernels import rmsnorm as _rn


def use_kernels(cfg) -> bool:
    """Whether the model's main paths run the Pallas kernels, from
    ``cfg.use_pallas``: ``"always"`` / ``"never"`` force the choice and
    ``"auto"`` means exactly when JAX's default backend is a TPU."""
    if cfg.use_pallas == "always":
        return True
    if cfg.use_pallas == "never":
        return False
    if cfg.use_pallas == "auto":
        return jax.default_backend() == "tpu"
    raise ValueError(f"use_pallas must be 'auto', 'always' or 'never', got "
                     f"{cfg.use_pallas!r}")


def _interpret() -> bool:
    """Compiled Mosaic on TPU, the Pallas interpreter on CPU; any other
    backend has no kernel path and raises instead of silently interpreting."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels run on TPU (compiled) or CPU "
                       f"(interpreted), not on backend {backend!r}; set "
                       f"ModelConfig.use_pallas='never' to use the XLA path")


def _lanes(x: jax.Array) -> jax.Array:
    """[L, ...] -> [L, R/128, 128], R padded up to a multiple of 128."""
    nl = x.shape[0]
    flat = x.reshape(nl, -1)
    pad = (-flat.shape[1]) % _ma.LANES
    if pad:
        flat = jnp.pad(flat, [(0, 0), (0, pad)])
    return flat.reshape(nl, -1, _ma.LANES)


def _unlanes(t: jax.Array, shape: tuple) -> jax.Array:
    """Inverse of ``_lanes`` for a result with leading dim ``shape[0]``."""
    r = math.prod(shape[1:])
    return t.reshape(t.shape[0], -1)[:, :r].reshape(shape)


def _multi_device(mesh) -> bool:
    return mesh is not None and mesh.devices.size > 1


def _per_shard(fn, mesh, in_specs, out_specs):
    """GSPMD cannot partition a Mosaic kernel, so on a multi-device mesh
    the kernel runs inside ``shard_map``: each device on its own shard of
    every operand, laid out as ``in_specs`` say (replicated: a full copy)."""
    if not _multi_device(mesh):
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _row_spec(spec: P) -> P:
    """Spec of a per-row [L] vector for a leaf laid out as ``spec``."""
    return P(spec[0]) if len(spec) else P()


def block_grad_sq_norms(g: jax.Array, *, mesh=None, spec=P()) -> jax.Array:
    """g: [L, ...] stacked gradient leaf -> [L] f32 sum of squares. On a
    multi-device ``mesh`` each device reduces its shard of ``g`` (laid out
    as ``spec``; the layer axis may be sharded) and the partial sums add
    over the axes that split a row."""
    axes = tuple(ax for part in spec[1:] if part is not None
                 for ax in (part if isinstance(part, tuple) else (part,)))

    def local(g):
        out = _bgn.block_grad_sq_norms(_lanes(g), interpret=_interpret())
        return jax.lax.psum(out, axes) if axes and _multi_device(mesh) \
            else out

    return _per_shard(local, mesh, (spec,), _row_spec(spec))(g)


def masked_adamw(p, g, m, v, sel, counts, lr, b1, b2, eps, wd, *, mesh=None,
                 spec=P()):
    """Leaf-shaped masked AdamW. p,g,m,v: [L, ...]; sel/counts broadcastable
    [L,1,..] or [L]. Returns (p', m', v') in original shapes. On a
    multi-device ``mesh`` every operand is taken in the layout ``spec``
    (the moments' sharding) and each device updates its own shard."""
    nl = p.shape[0]

    def local(p, g, m, v, sel, cnt, lr):
        p2, m2, v2 = _ma.masked_adamw(
            _lanes(p), _lanes(g), _lanes(m), _lanes(v), sel, cnt, lr, b1,
            b2, eps, wd, interpret=_interpret())
        return (_unlanes(p2, p.shape), _unlanes(m2, m.shape),
                _unlanes(v2, v.shape))

    row = _row_spec(spec)
    return _per_shard(local, mesh, (spec,) * 4 + (row, row, P()),
                      (spec,) * 3)(p, g, m, v, sel.reshape(nl),
                                   counts.reshape(nl), lr)


def banked_masked_adamw(p, g, m, v, slots, sel, counts, lr, b1, b2, eps, wd,
                        *, mesh=None, spec=P()):
    """Banked (slot-indexed) masked AdamW. p, g: [L, ...] full stacked
    leaves; m, v: [cap, ...] moment banks; slots/sel/counts: [cap] (sel == 0
    on sentinel slots). Returns (p_rows', m', v') in bank shape [cap, ...] —
    scatter p_rows' back into the leaf with drop-mode semantics. The kernel
    reads p/g rows through the slots vector (scalar prefetch), so no
    [cap, ...] gather of p or g is ever materialized. On a multi-device
    ``mesh`` leaves and banks are taken in the parameters' layout ``spec``,
    whose layer axis must be whole (slots index global rows)."""
    if len(spec) and spec[0] is not None:
        raise ValueError(f"banked kernel needs an unsharded layer axis, "
                         f"got {spec}")
    cap = m.shape[0]

    def local(p, g, m, v, slots, sel, cnt, lr):
        p2, m2, v2 = _ma.banked_masked_adamw(
            _lanes(p), _lanes(g), _lanes(m), _lanes(v), slots, sel, cnt, lr,
            b1, b2, eps, wd, interpret=_interpret())
        bank_shape = (cap,) + p.shape[1:]
        return (_unlanes(p2, bank_shape), _unlanes(m2, bank_shape),
                _unlanes(v2, bank_shape))

    return _per_shard(local, mesh, (spec,) * 4 + (P(),) * 4, (spec,) * 3)(
        p, g, m, v, slots, sel.reshape(cap), counts.reshape(cap), lr)


def flash_attention(q, k, v, *, causal=True, segment_ids=None, mesh=None,
                    batch_axes=("data",)):
    """q,k,v: [B, S, H, D] (layer layout; kv already head-expanded) ->
    [B, S, H, D]. ``segment_ids``: optional [B, S] packed segment ids
    (0 = pad): attention is block-diagonal over equal segments. Blocks
    follow S and D (``flash_attention.block_sizes``). On a multi-device
    ``mesh`` each device runs the kernel on its shard: the batch split over
    ``batch_axes``, the heads over ``model`` where they divide."""
    b, s, h, d = q.shape
    bq, bk = _fa.block_sizes(s, d)
    interpret = _interpret()

    def local(q, k, v, *seg):
        bl, hl = q.shape[0], q.shape[2]
        fold = lambda t: t.transpose(0, 2, 1, 3).reshape(bl * hl, s, d)  # noqa: E731
        seg = jnp.asarray(seg[0], jnp.int32) if seg else None
        o = _fa.flash_attention(fold(q), fold(k), fold(v), seg, hl, bq, bk,
                                causal, interpret)
        return o.reshape(bl, hl, s, d).transpose(0, 2, 1, 3)

    args = (q, k, v) if segment_ids is None else (q, k, v, segment_ids)
    heads = None
    if _multi_device(mesh) and "model" in mesh.axis_names \
            and h % mesh.shape["model"] == 0:
        heads = "model"
    spec = P(batch_axes, None, heads, None)
    in_specs = (spec,) * 3 + ((P(batch_axes, None),) if len(args) > 3 else ())
    return _per_shard(local, mesh, in_specs, spec)(*args)


def decode_attention(q, k, v, valid_len):
    """q: [B, 1, H, D]; k,v: [B, S, H, D] (head-expanded cache);
    valid_len: scalar i32 or per-row [B] vector -> [B, 1, H, D]."""
    b, s, h, d = k.shape
    qf = q.reshape(b, h, d).reshape(b * h, d)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)  # noqa: E731
    vl = jnp.asarray(valid_len, jnp.int32)
    if vl.ndim:  # [B] -> [B*H], b-major to match the head fold
        vl = jnp.repeat(vl, h)
    o = _dec.decode_attention(qf, fold(k), fold(v), vl,
                              interpret=_interpret())
    return o.reshape(b, 1, h, d)


def paged_decode_attention(q, k_pool, v_pool, page_tables, valid_len, hmap):
    """q: [B, 1, H, D]; k_pool/v_pool: [num_pages, KVH, page_size, D] shared
    pools; page_tables: [B, max_pages] i32; valid_len: [B] i32; hmap: [H]
    q-head -> kv-head map -> [B, 1, H, D]. Unlike the dense wrapper no
    head-expanded [B, S, H, D] view is ever built — the kernel reads pool
    pages through the table and kv heads through hmap."""
    b, _, h, d = q.shape
    o = _dec.paged_decode_attention(q.reshape(b, h, d), k_pool, v_pool,
                                    page_tables, valid_len,
                                    jnp.asarray(hmap, jnp.int32),
                                    interpret=_interpret())
    return o.reshape(b, 1, h, d)


def rmsnorm(x, scale, eps=1e-5):
    """x: [..., D] -> fused RMSNorm over the trailing dim."""
    shape = x.shape
    n = 1
    for d in shape[:-1]:
        n *= d
    flat = x.reshape(n, shape[-1])
    rows = _rn.DEFAULT_ROWS
    while n % rows:
        rows //= 2
    out = _rn.rmsnorm(flat, scale, eps, rows=max(rows, 1),
                      interpret=_interpret())
    return out.reshape(shape)

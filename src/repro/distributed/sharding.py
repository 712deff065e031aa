"""Sharding rules: parameter/optimizer/batch PartitionSpecs per architecture.

Strategy (DESIGN.md 3.4):
  * DP   — batch over ("pod","data")
  * TP   — q-heads over "model" (uneven dims allowed — GSPMD pads), kv
           replicated unless KVH divides the model axis; FFN hidden over
           "model"; vocab/embedding over "model"
  * EP   — MoE expert dim over "model" (shard_map all_to_all inside the layer)
  * SSM  — d_inner/head channels over "model"
  * ZeRO-1 — optimizer moments additionally sharded over "data" on the first
           divisible dim (offload="zero1")

Rules key off canonical leaf paths (utils.trees.path_str) so the same table
covers every family.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.utils.trees import tree_map_with_path


def _model_dim(mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))["model"]


def param_spec(cfg: ModelConfig, path: str, shape: tuple, m: int) -> P:
    """PartitionSpec for one parameter leaf. ``m`` = model-axis size."""
    base = path.split("/")[-1]
    stacked = path.split("/")[0].endswith("layers")
    nd = len(shape)

    def spec(*parts):
        # prepend None for the stacked layer axis
        parts = ((None,) + parts) if stacked else parts
        parts = parts + (None,) * (nd - len(parts))
        return P(*parts[:nd])

    # ---- embeddings / heads
    if path.startswith("embed/"):
        return P("model", None)
    if path.startswith("lm_head/"):
        return P(None, "model")

    # ---- norms, scalars, biases on heads
    if base in ("scale", "A_log", "D", "dt_bias", "conv_b"):
        return spec()
    # ---- attention projections
    if base == "wq":
        return spec(None, "model")          # [.., D, H, Dh]
    if base in ("wk", "wv"):
        kvh = shape[-2]
        return spec(None, "model") if kvh % m == 0 else spec()
    if base in ("bq",):
        return spec("model")
    if base in ("bk", "bv"):
        kvh = shape[-2] if nd >= (2 + (1 if stacked else 0)) else shape[0]
        return spec("model") if kvh % m == 0 else spec()
    if base == "wo":
        return spec("model")                # [.., H, Dh, D] row-parallel
    # ---- MLA
    if base == "wq_a":
        return spec()                       # [D, qr] small, replicate
    if base == "wq_b":
        return spec(None, "model")          # [qr, H, nd+rd]
    if base == "wkv_a":
        return spec()
    if base in ("wk_b", "wv_b"):
        return spec(None, "model")          # [kvr, H, d]
    # ---- MoE
    if "moe" in path.split("/"):
        if base == "router":
            return spec()
        if base in ("wg", "wu", "wd") and "shared" not in path:
            return spec(tuple(cfg.ep_axes))  # experts over the EP plane
        # shared expert: like dense mlp
        if base in ("wg", "wu"):
            return spec(None, "model")
        if base == "wd":
            return spec("model", None)
    # ---- dense MLP
    if base in ("wg", "wu"):
        return spec(None, "model")          # [D, F]
    if base == "wd":
        return spec("model", None)          # [F, D]
    # ---- SSM (split projections; channel dims shard-aligned with heads)
    if base in ("proj_z", "proj_x", "proj_b", "proj_c", "proj_dt"):
        return spec(None, "model")          # [D, channels]
    if base in ("conv_x", "conv_b_mat", "conv_c_mat"):
        return spec(None, "model")          # [K, channels]
    if base in ("cbias_x", "cbias_b", "cbias_c"):
        return spec("model")
    if base == "out_proj":
        return spec("model", None)          # [d_inner, D]
    # ---- MTP projection and anything else
    return spec()


def param_specs(cfg: ModelConfig, params_shapes, mesh):
    m = _model_dim(mesh)
    return tree_map_with_path(
        lambda path, leaf: param_spec(cfg, path, leaf.shape, m), params_shapes)


def param_shardings(cfg: ModelConfig, params_shapes, mesh):
    specs = param_specs(cfg, params_shapes, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def batch_spec(mesh) -> P:
    return P(batch_axes_of(mesh))


def canonical_spec(spec: P) -> P:
    """Strip trailing ``None`` entries: ``P(None,)`` and ``P()`` describe
    the same placement but compare unequal, and a jitted step whose output
    constraint normalizes differently from the initial ``device_put`` would
    recompile on its second call. Canonicalize wherever specs feed a
    sharding that round-trips through a compiled step."""
    parts = list(spec)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def mesh_canonical_spec(spec: P, mesh) -> P:
    """``canonical_spec`` plus dropping axes of mesh size 1: on a pure-DP
    mesh ``P(None, "model")`` places identically to ``P()`` and jax's
    sharding normalization inside jit reflects that — placements built from
    the verbatim rule table would mismatch the step's constrained outputs
    and break compile-once. Single-element tuples collapse to the axis."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    keep = lambda ax: sizes.get(ax, 1) > 1  # noqa: E731
    parts = []
    for pt in spec:
        if isinstance(pt, tuple):
            kept = tuple(a for a in pt if keep(a))
            parts.append(kept[0] if len(kept) == 1 else (kept or None))
        else:
            parts.append(pt if (pt is None or keep(pt)) else None)
    return canonical_spec(P(*parts))


def apply_zero1(specs, params_shapes, mesh, data_axis: str = "data"):
    """Moment specs: additionally shard the first dim that is (a) unsharded
    and (b) divisible by the data-axis size. Falls back to the param spec."""
    d = dict(zip(mesh.axis_names, mesh.devices.shape))[data_axis]

    def one(path, leaf, spec):
        parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
        used = set()
        for pt in parts:
            for ax in (pt if isinstance(pt, tuple) else (pt,)):
                if ax:
                    used.add(ax)
        if data_axis in used:   # e.g. 2D-EP expert weights already use data
            return spec
        for i, (dim, pt) in enumerate(zip(leaf.shape, parts)):
            if pt is None and dim % d == 0 and dim >= d:
                parts[i] = data_axis
                return canonical_spec(P(*parts))
        return spec

    return tree_map_with_path(one, params_shapes, specs)


def sds_with_sharding(shapes, shardings):
    """Attach shardings to a ShapeDtypeStruct pytree (dry-run inputs)."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


# ------------------------------------------------- banked-store ZeRO-1 layout

# Marker used in sharding trees for leaves that intentionally live in host
# RAM as numpy (the banked slot_map and a "host"-policy full store): tree-
# congruent with the TrainState, never device_put. String (not None) so
# pytree mapping over (state, shardings) stays structurally exact.
HOST_RESIDENT = "host"


def data_axis_size(mesh, data_axis: str = "data") -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[data_axis]


def store_specs(partition, store_shapes: dict, mesh,
                data_axis: str = "data") -> dict:
    """ZeRO-1 PartitionSpecs for the banked optimizer's full backing store
    (``core.offload.init_full_store`` under ``offload == "zero1"``).

    Stacked groups shard the leading block axis over ``data`` — each device
    owns ``1/dp`` of the store rows, and the selection-boundary swap
    (``masked_adamw.swap_banked``) only touches the shard(s) holding the
    evicted/admitted block ids. When the block axis does not divide the dp
    degree (or for unstacked groups, where the whole leaf is one block), the
    first divisible dim is sharded instead; fully indivisible leaves stay
    replicated. ``slot_map`` stays host-global: every process plans the same
    swap from the same [num_blocks] vector.
    """
    d = data_axis_size(mesh, data_axis)

    def leaf_spec(stacked: bool, leaf) -> P:
        shape = tuple(leaf.shape)
        start = 0
        if stacked and shape and shape[0] % d == 0:
            return P(data_axis)
        if stacked:
            start = 1  # never split the block axis unevenly
        for i in range(start, len(shape)):
            if shape[i] % d == 0 and shape[i] >= d:
                return P(*((None,) * i + (data_axis,)))
        return P()

    return {g.key: jax.tree.map(lambda leaf, s=g.stacked: leaf_spec(s, leaf),
                                store_shapes[g.key])
            for g in partition.groups}


def store_shardings(partition, store_shapes: dict, mesh,
                    data_axis: str = "data") -> dict:
    specs = store_specs(partition, store_shapes, mesh, data_axis)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))

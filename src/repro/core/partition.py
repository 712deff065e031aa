"""BlockPartition: the paper's "block" taxonomy over a parameter pytree.

A block is (paper §3.1): one transformer block, the embedding table, or the
final norm — plus, in this framework, the hybrid shared-attention block,
encoder blocks (encdec), the untied LM head, and MTP blocks, each as its own
bandit arm.

Stacked parameter groups (leading axis = #layers, produced by scan-over-
layers models) map to consecutive block ids, which is what makes per-step
dynamic selection a cheap runtime vector instead of a recompile.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class Group:
    key: str        # top-level key in the params dict
    start: int      # first block id
    length: int     # number of blocks in the group
    stacked: bool   # True -> every leaf has leading axis == length


@dataclass(frozen=True)
class BlockPartition:
    groups: tuple[Group, ...]
    num_blocks: int

    def group(self, key: str) -> Group:
        for g in self.groups:
            if g.key == key:
                return g
        raise KeyError(key)

    @property
    def block_names(self) -> list[str]:
        names = []
        for g in self.groups:
            if g.length == 1:
                names.append(g.key)
            else:
                names.extend(f"{g.key}[{i}]" for i in range(g.length))
        return names


def _group_order(cfg: ModelConfig) -> list[tuple[str, int, bool]]:
    """(key, length, stacked) in canonical block order."""
    out: list[tuple[str, int, bool]] = [("embed", 1, False)]
    if cfg.family == "encdec":
        out += [("enc_layers", cfg.num_encoder_layers, True),
                ("enc_norm", 1, False),
                ("dec_layers", cfg.num_layers, True)]
    elif cfg.family == "moe":
        if cfg.first_k_dense:
            out.append(("dense_layers", cfg.first_k_dense, True))
        out.append(("moe_layers", cfg.num_layers - cfg.first_k_dense, True))
    elif cfg.family == "hybrid":
        out += [("layers", cfg.num_layers, True), ("shared_attn", 1, False)]
    else:  # dense / vlm / ssm
        out.append(("layers", cfg.num_layers, True))
    out.append(("final_norm", 1, False))
    if not cfg.tie_embeddings:
        out.append(("lm_head", 1, False))
    if cfg.mtp_depth:
        out.append(("mtp", 1, False))
    return out


def build_partition(cfg: ModelConfig) -> BlockPartition:
    groups, start = [], 0
    for key, length, stacked in _group_order(cfg):
        groups.append(Group(key, start, length, stacked))
        start += length
    return BlockPartition(tuple(groups), start)


# ------------------------------------------------------------------ norms


def block_grad_norms(partition: BlockPartition, grads: dict,
                     use_pallas: bool = False, mesh=None,
                     specs=None) -> jax.Array:
    """Per-block gradient L2 norm (paper Alg. 1 lines 1-6): aggregates
    sum-of-squares over every leaf of each block, sqrt at the end. With
    ``use_pallas`` the fused kernel reduces every leaf, an unstacked leaf
    as a stack of one — per shard of the grads' PartitionSpecs ``specs``
    (congruent with grads; None = replicated) on a multi-device ``mesh``.
    Returns [num_blocks] f32."""
    if specs is None:
        specs = jax.tree.map(lambda _: P(), grads)
    if use_pallas:
        from repro.kernels import ops as kops

        def stacked_sq(leaf, spec):
            return kops.block_grad_sq_norms(leaf, mesh=mesh, spec=spec)
    else:
        def stacked_sq(leaf, spec):
            lf = leaf.astype(jnp.float32)
            return jnp.sum(lf * lf, axis=tuple(range(1, lf.ndim)))
    sq = jnp.zeros((partition.num_blocks,), jnp.float32)
    for g in partition.groups:
        sub = grads[g.key]
        leaves = jax.tree.leaves(sub)
        leaf_specs = jax.tree.leaves(specs[g.key],
                                     is_leaf=lambda s: isinstance(s, P))
        if g.stacked:
            acc = jnp.zeros((g.length,), jnp.float32)
            for leaf, spec in zip(leaves, leaf_specs):
                acc = acc + stacked_sq(leaf, spec)
            sq = jax.lax.dynamic_update_slice(sq, acc, (g.start,))
        elif use_pallas:
            s = sum(stacked_sq(x[None], P(None, *spec))[0]
                    for x, spec in zip(leaves, leaf_specs))
            sq = sq.at[g.start].add(s)
        else:
            s = sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
            sq = sq.at[g.start].add(s)
    return jnp.sqrt(sq)


# ------------------------------------------------------------------ masks


def leaf_masks(partition: BlockPartition, params: dict, mask: jax.Array) -> dict:
    """Broadcastable per-leaf selection masks matching the params structure.
    mask: [num_blocks] (bool or 0/1)."""
    m = mask.astype(jnp.float32)
    out = {}
    for g in partition.groups:
        sub = params[g.key]
        if g.stacked:
            seg = jax.lax.dynamic_slice(m, (g.start,), (g.length,))
            out[g.key] = jax.tree.map(
                lambda leaf: seg.reshape((g.length,) + (1,) * (leaf.ndim - 1)),
                sub)
        else:
            out[g.key] = jax.tree.map(lambda leaf: m[g.start], sub)
    return out


def layer_masks_dict(partition: BlockPartition, mask: jax.Array) -> dict:
    """Per-group mask vectors for the model's gate_weight_grads hook:
    {"layers": [L], "shared_attn": scalar, ...} — only body groups."""
    out = {}
    for g in partition.groups:
        if g.key in ("embed", "final_norm", "enc_norm", "lm_head"):
            continue
        if g.stacked:
            out[g.key] = jax.lax.dynamic_slice(
                mask.astype(jnp.float32), (g.start,), (g.length,))
        else:
            out[g.key] = mask[g.start].astype(jnp.float32)
    return out


# ------------------------------------------------------------------ slots
#
# Helpers for the compact banked optimizer state (masked_adamw.py): a
# stacked group's moments live in a [cap, ...] bank whose row ``s`` holds
# the moments of local block ``slots[s]`` (``slots[s] == group.length`` =
# free slot). Both helpers keep every index a runtime vector of static
# shape, so per-step selection changes never trigger recompilation.


def gather_rows(leaf, slots, fill=0):
    """Rows of a stacked leaf [L, ...] at ``slots`` [n] -> [n, ...].
    Out-of-range entries (the ``L`` free-slot sentinel) read as ``fill``
    rows instead of clamping onto a real block."""
    return jnp.asarray(leaf).at[jnp.asarray(slots, dtype=jnp.int32)].get(
        mode="fill", fill_value=fill)


def scatter_rows(leaf, slots, rows):
    """Write ``rows`` [n, ...] into stacked leaf [L, ...] at ``slots`` [n].
    Out-of-range entries are dropped, so free-slot sentinels never land."""
    return jnp.asarray(leaf).at[jnp.asarray(slots, dtype=jnp.int32)].set(
        rows, mode="drop")


def params_per_block(partition: BlockPartition, params: dict) -> np.ndarray:
    """Static count of parameters per block (for the §3.3 memory model)."""
    counts = np.zeros((partition.num_blocks,), np.int64)
    for g in partition.groups:
        for leaf in jax.tree.leaves(params[g.key]):
            shape = leaf.shape
            if g.stacked:
                per = int(np.prod(shape[1:]))
                counts[g.start:g.start + g.length] += per
            else:
                counts[g.start] += int(np.prod(shape))
    return counts

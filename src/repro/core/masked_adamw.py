"""Block-masked AdamW — the paper's "custom AdamW" (Alg. 1 lines 9-13).

Selected blocks take a standard AdamW step (moments + weight decay);
unselected blocks keep parameters AND moments bit-identical. Bias
correction uses *per-block* step counts (an intermittently-updated block's
Adam timescale is its own update count, not the global step) — with
mask == all-ones this reduces exactly to standard AdamW, which the
equivalence test asserts.

Moments are float32 regardless of param dtype. The fused Pallas kernel
(kernels/masked_adamw.py) implements the same update for the TPU path.

Two residency layouts share the update arithmetic (row-for-row identical,
so the dense form stays the trajectory oracle):

* **dense** — ``init_opt_state`` / ``update``: full m/v pytrees congruent
  with params.
* **banked** (paper §3.3) — ``init_banked_opt_state`` / ``swap_banked`` /
  ``banked_update``: device-resident moments are compact [cap]-slot banks
  (one per partition group) backed by a full store (host RAM under
  ``offload == "host"``, see core/offload.py). ``swap_banked`` runs at
  selection-change boundaries outside jit: evicted blocks' rows stream back
  to the store, admitted blocks' rows stream in (zeros on first selection).
  Inside the compiled step every bank index is a runtime vector of static
  shape, so per-step selection never recompiles.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import OptimizerConfig
from repro.core import partition as part_mod
from repro.core.partition import BlockPartition, leaf_masks


def init_opt_state(partition: BlockPartition, params: dict,
                   moment_dtype=jnp.float32) -> dict:
    zeros = lambda p: jax.tree.map(  # noqa: E731
        lambda x: jnp.zeros(x.shape, moment_dtype), p)
    return {
        "m": zeros(params),
        "v": zeros(params),
        "counts": jnp.zeros((partition.num_blocks,), jnp.float32),
    }


def global_grad_norm(grads) -> jax.Array:
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(grads))
    return jnp.sqrt(sq)


def clip_by_global_norm(grads, max_norm: float):
    norm = global_grad_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        grads), norm


def _adamw_rows(cfg: OptimizerConfig, p, g, m, v, sel, cnt, lr,
                use_pallas: bool, mesh=None, spec=P()):
    """The masked-AdamW formula on one leaf (or gathered bank rows of one
    leaf). ``sel``/``cnt`` broadcast against ``p``: per-row [L, 1, ...] for
    a stacked leaf, scalars for an unstacked one. Shared by the dense and
    banked layouts so their arithmetic is identical op for op; with
    ``use_pallas`` the fused kernel applies it, an unstacked leaf running as
    a stack of one, per shard of ``spec`` on a multi-device ``mesh``."""
    if use_pallas:
        from repro.kernels import ops as kops
        hp = (cfg.b1, cfg.b2, cfg.eps, cfg.weight_decay)
        if sel.ndim:
            return kops.masked_adamw(p, g, m, v, sel, cnt, lr, *hp,
                                     mesh=mesh, spec=spec)
        one = lambda x: jnp.asarray(x)[None]  # noqa: E731
        out = kops.masked_adamw(one(p), one(g), one(m), one(v), one(sel),
                                one(cnt), lr, *hp, mesh=mesh,
                                spec=P(None, *spec))
        return tuple(x[0] for x in out)
    mdt = m.dtype
    gf = g.astype(jnp.float32)
    m, v = m.astype(jnp.float32), v.astype(jnp.float32)
    m2 = jnp.where(sel > 0, cfg.b1 * m + (1 - cfg.b1) * gf, m)
    v2 = jnp.where(sel > 0, cfg.b2 * v + (1 - cfg.b2) * gf * gf, v)
    c = jnp.maximum(cnt, 1.0)
    mhat = m2 / (1 - cfg.b1 ** c)
    vhat = v2 / (1 - cfg.b2 ** c)
    pf = p.astype(jnp.float32)
    step = lr * (mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * pf)
    p2 = jnp.where(sel > 0, pf - step, pf)
    return p2.astype(p.dtype), m2.astype(mdt), v2.astype(mdt)


def _unzip3(flat):
    is_t = lambda t: isinstance(t, tuple)  # noqa: E731
    return tuple(jax.tree.map(lambda t, i=i: t[i], flat, is_leaf=is_t)
                 for i in range(3))


def update(cfg: OptimizerConfig, partition: BlockPartition, params: dict,
           grads: dict, opt_state: dict, mask, lr, use_pallas: bool = False,
           mesh=None, specs=None):
    """One masked step. mask: [num_blocks]; lr: scalar (schedule applied by
    the caller). ``mesh``/``specs``: where the kernels run per shard — the
    multi-device mesh and the moments' PartitionSpecs (congruent with
    params; None = replicated). Returns (new_params, new_opt_state)."""
    counts = opt_state["counts"] + mask.astype(jnp.float32)
    masks = leaf_masks(partition, params, mask)
    counts_b = leaf_masks(partition, params, counts)  # per-leaf broadcast
    if specs is None:
        specs = jax.tree.map(lambda _: P(), params)

    def upd(p, g, m, v, sel, cnt, spec):
        return _adamw_rows(cfg, p, g, m, v, sel, cnt, lr, use_pallas, mesh,
                           spec)

    flat = jax.tree.map(upd, params, grads, opt_state["m"], opt_state["v"],
                        masks, counts_b, specs)
    new_params, new_m, new_v = _unzip3(flat)
    return new_params, {"m": new_m, "v": new_v, "counts": counts}


# ---------------------------------------------------- banked residency (§3.3)


def bank_capacity(group, k_slots: int) -> int:
    """Device slots a stacked group needs: selection places at most
    ``k_slots`` blocks anywhere, and at most ``group.length`` of them here."""
    return max(1, min(group.length, k_slots))


def init_banked_opt_state(partition: BlockPartition, params: dict,
                          k_slots: int, moment_dtype=jnp.float32,
                          store_policy: str | None = "host",
                          mesh=None) -> dict:
    """Compact banked optimizer state:

      banks[key]  — per partition group: ``m``/``v`` pytrees with leading
                    axis ``cap = min(len, k_slots)`` (stacked groups) or
                    full leaf shape (unstacked, cap 1), plus ``slots``
                    [cap] i32 — the local block id each slot holds
                    (``group.length`` = free).
      slot_map    — [num_blocks] i32, block id -> slot in its group's bank
                    (-1 = host-resident only). Host-side numpy: it drives
                    ``swap_banked`` and never enters jit.
      counts      — per-block bias-correction step counts (unchanged from
                    the dense layout; tiny, always device-resident).
      store       — full-shape backing store (core/offload.init_full_store);
                    omitted when ``store_policy`` is None (eval_shape
                    projections of the device-resident footprint). Under
                    ``store_policy == "zero1"`` the store is device-resident
                    but sharded 1/dp over ``mesh``'s data axis.

    Nothing is resident initially; the first ``swap_banked`` admits the
    first selection with zero rows from the store (zero-init on first
    selection, matching ``init_opt_state``'s zeros).
    """
    from repro.core import offload
    banks = {}
    for g in partition.groups:
        sub = params[g.key]
        if g.stacked:
            cap = bank_capacity(g, k_slots)
            zeros = lambda x: jnp.zeros((cap,) + tuple(x.shape[1:]),  # noqa: E731
                                        moment_dtype)
        else:
            cap = 1
            zeros = lambda x: jnp.zeros(x.shape, moment_dtype)  # noqa: E731
        banks[g.key] = {
            "m": jax.tree.map(zeros, sub),
            "v": jax.tree.map(zeros, sub),
            "slots": jnp.full((cap,), g.length, jnp.int32),
        }
    opt = {
        "banks": banks,
        "slot_map": np.full((partition.num_blocks,), -1, np.int32),
        "counts": jnp.zeros((partition.num_blocks,), jnp.float32),
    }
    if store_policy is not None:
        opt["store"] = offload.init_full_store(partition, params,
                                               moment_dtype, store_policy,
                                               mesh=mesh)
    return opt


@dataclasses.dataclass(frozen=True)
class GroupSwapPlan:
    """One partition group's slice of a selection-change boundary: which
    local blocks leave the bank (``ev_*``) and which enter (``ad_*``), with
    the slot each occupies/receives. Pure data, computed by ``plan_swap``
    from (slot_map, mask) alone — the async planner plans against a
    *predicted* mask and the plan is only applied if the real selection
    matches, so nothing here may depend on bank/store contents."""
    key: str
    start: int
    length: int
    stacked: bool
    ev_blocks: np.ndarray  # local block ids leaving the bank
    ev_slots: np.ndarray   # the bank rows they occupied
    ad_blocks: np.ndarray  # local block ids entering the bank
    ad_slots: np.ndarray   # the (free) bank rows they receive


def plan_swap(partition: BlockPartition, slot_map, mask,
              caps: dict) -> list[GroupSwapPlan]:
    """Evict/admit plan for one boundary. ``mask``: host bool [num_blocks];
    ``caps``: per-group bank capacity (``{key: bank["slots"].shape[0]}``).
    Groups whose residency already matches the mask are omitted (an
    unchanged selection plans to an empty list — the no-op fast path).
    Raises on per-group bank overflow, same as the paper's slot contract."""
    mask = np.asarray(mask).astype(bool)
    slot_map = np.asarray(slot_map, np.int32)
    plans = []
    for g in partition.groups:
        lo = slice(g.start, g.start + g.length)
        gmask, gslots = mask[lo], slot_map[lo]
        resident = gslots >= 0
        ev_blocks = np.nonzero(resident & ~gmask)[0]
        ad_blocks = np.nonzero(gmask & ~resident)[0]
        if not len(ev_blocks) and not len(ad_blocks):
            continue
        cap = caps[g.key]
        occupied = np.zeros((cap,), bool)
        occupied[gslots[np.nonzero(resident & gmask)[0]]] = True
        free = np.nonzero(~occupied)[0]
        if len(ad_blocks) > len(free):
            raise RuntimeError(
                f"bank overflow in group {g.key!r}: {len(ad_blocks)} "
                f"admissions for {len(free)} free slots (capacity {cap}); "
                f"the selection selected more blocks than the configured "
                f"slot capacity")
        plans.append(GroupSwapPlan(
            key=g.key, start=g.start, length=g.length, stacked=g.stacked,
            ev_blocks=ev_blocks, ev_slots=gslots[ev_blocks],
            ad_blocks=ad_blocks, ad_slots=free[:len(ad_blocks)]))
    return plans


def bank_caps(banks: dict) -> dict:
    """{group key: bank slot capacity} for ``plan_swap``."""
    return {k: int(b["slots"].shape[0]) for k, b in banks.items()}


# boundary traffic is a handful of rows across ~20 bank leaves; fusing the
# whole group into one jitted call keeps it to one dispatch (and one compile
# per (group, row-count) pair) instead of one per leaf
@jax.jit
def _gather_group(leaves, slots):
    return tuple(l.at[slots].get(mode="fill", fill_value=0) for l in leaves)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_group_donated(leaves, slots, rows):
    return tuple(l.at[slots].set(r.astype(l.dtype), mode="drop")
                 for l, r in zip(leaves, rows))


def gather_evictions(plans: list, banks: dict) -> list:
    """Dispatch the device-side reads of evicted blocks' bank rows (one
    fused gather per stacked group, the whole leaves of an unstacked one),
    in the order ``writeback_evictions`` consumes them. Asynchronous: the
    reads run once the banks are ready, behind whatever the device has
    queued."""
    dev = []
    for plan in plans:
        if not len(plan.ev_blocks):
            continue
        bank = banks[plan.key]
        leaves = tuple(jax.tree.leaves(bank["m"]) + jax.tree.leaves(bank["v"]))
        if plan.stacked:
            dev.extend(_gather_group(leaves,
                                     jnp.asarray(plan.ev_slots, jnp.int32)))
        else:
            dev.extend(leaves)
    return dev


def writeback_evictions(plans: list, rows: list, store: dict) -> dict:
    """Stream evicted blocks' bank rows (``gather_evictions(plans, banks)``)
    back into the full store (device -> store side), with one batched
    ``device_get``: a single host sync per boundary, not a round trip per
    bank leaf. In the overlapped timeline the rows are phase B's output,
    so this runs on the background thread. Host (numpy) store leaves are
    updated in place; device leaves functionally. Returns the (possibly
    new) store tree. Admitted blocks' store rows are disjoint from evicted
    ones, so this commutes with ``prefetch_admissions``."""
    from repro.core import offload
    new_store = dict(store)
    host = iter(jax.device_get(rows))
    for plan in plans:
        if not len(plan.ev_blocks):
            continue
        group_store = {}
        for mom in ("m", "v"):
            s_flat, s_def = jax.tree.flatten(store[plan.key][mom])
            out_s = []
            for sl in s_flat:
                rows = next(host)
                if plan.stacked:
                    sl = offload.store_write_rows(sl, plan.ev_blocks, rows)
                else:
                    sl = offload.store_write_leaf(sl, rows)
                out_s.append(sl)
            group_store[mom] = jax.tree.unflatten(s_def, out_s)
        new_store[plan.key] = group_store
    return new_store


def prefetch_admissions(plans: list, store: dict, staging=None) -> dict:
    """Stage admitted blocks' store rows as device arrays, ready to scatter
    into bank slots at commit. Reads only *non-resident* blocks' store rows,
    which cannot change while a selection is in flight — so this is safe to
    run any time after the plan exists, concurrent with phase B (the
    overlapped path's store->device prefetch). ``staging``: optional
    reusable host buffer pool (``core.swap.StagingPool``) so host-store
    reads don't allocate fresh numpy staging on every boundary. Returns,
    once every host->device copy it started has landed,
    ``{key: {"m": [rows per leaf], "v": [...]}}`` in tree-flatten order."""
    from repro.core import offload
    staged = {}
    copies = []
    for plan in plans:
        if not len(plan.ad_blocks):
            continue
        group = {}
        for mom in ("m", "v"):
            s_flat, _ = jax.tree.flatten(store[plan.key][mom])
            rows_out = []
            for i, sl in enumerate(s_flat):
                if plan.stacked:
                    if isinstance(sl, np.ndarray):
                        buf = (staging.take(plan.key, mom, i,
                                            len(plan.ad_blocks), sl)
                               if staging is not None else None)
                        rows = offload.store_read_rows(sl, plan.ad_blocks,
                                                       out=buf)
                        dev = jax.device_put(rows)
                        copies.append(dev)
                    else:
                        dev = offload.store_read_rows(sl, plan.ad_blocks)
                elif isinstance(sl, np.ndarray):
                    dev = jax.device_put(sl)
                    copies.append(dev)
                else:
                    dev = jnp.asarray(sl)
                rows_out.append(dev)
            group[mom] = rows_out
        staged[plan.key] = group
    if copies:
        # one sync for every host->device copy (not one per leaf): pool
        # buffers are reused next boundary, and a caller's timing of this
        # call then holds each copy whole
        jax.block_until_ready(copies)
    return staged


def commit_swap(plans: list, banks: dict, store: dict, slot_map,
                staged: dict, donate: bool = False):
    """Apply a planned boundary: scatter staged admissions into bank rows,
    mark evicted slots free, update ``slot_map``. Device work is one async
    scatter dispatch per changed group, and each changed group's ``slots``
    vector is read back to the host (it blocks until the bank is ready).
    With admissions prefetched and evictions written back in the
    background, this and the join on that job are what the boundary leaves
    on the critical path. ``donate=True`` donates the scattered bank leaves
    (rows written in place instead of copying the whole bank) — only for
    callers that drop their last reference to the input banks, i.e. the
    swap planner's per-step boundary. Returns (banks, slot_map, store)."""
    from repro.core import offload
    slot_map = np.array(slot_map, np.int32)  # fresh copy per boundary
    new_banks = dict(banks)
    for plan in plans:
        bank = banks[plan.key]
        slots_vec = np.array(bank["slots"], np.int32)
        group_bank = {}
        if donate and plan.stacked and len(plan.ad_blocks):
            # fused path: all of the group's m+v leaves in one donated
            # scatter call — staged rows land in place, no bank copies
            m_flat, m_def = jax.tree.flatten(bank["m"])
            v_flat, v_def = jax.tree.flatten(bank["v"])
            old = m_flat + v_flat
            rows = tuple(jnp.asarray(r) for r in
                         staged[plan.key]["m"] + staged[plan.key]["v"])
            new = _scatter_group_donated(
                tuple(old), jnp.asarray(plan.ad_slots, jnp.int32), rows)
            new = [offload._keep_sharding(n, o) for n, o in zip(new, old)]
            group_bank["m"] = jax.tree.unflatten(m_def, new[:len(m_flat)])
            group_bank["v"] = jax.tree.unflatten(v_def, new[len(m_flat):])
        else:
            for mom in ("m", "v"):
                b_flat, b_def = jax.tree.flatten(bank[mom])
                rows = staged.get(plan.key, {}).get(mom)
                out_b = []
                for i, bl in enumerate(b_flat):
                    if len(plan.ad_blocks):
                        if plan.stacked:
                            new_bl = part_mod.scatter_rows(
                                bl, plan.ad_slots,
                                jnp.asarray(rows[i], dtype=bl.dtype))
                        else:
                            new_bl = jnp.asarray(rows[i], dtype=bl.dtype)
                        bl = offload._keep_sharding(new_bl, bl)
                    out_b.append(bl)
                group_bank[mom] = jax.tree.unflatten(b_def, out_b)
        slots_vec[plan.ev_slots] = plan.length
        slots_vec[plan.ad_slots] = plan.ad_blocks
        slot_map[plan.start + plan.ev_blocks] = -1
        slot_map[plan.start + plan.ad_blocks] = plan.ad_slots
        group_bank["slots"] = offload._keep_sharding(jnp.asarray(slots_vec),
                                                     bank["slots"])
        new_banks[plan.key] = group_bank
    return new_banks, slot_map, store


def swap_banked(partition: BlockPartition, banks: dict, store: dict,
                slot_map, mask, staging=None):
    """Selection-change boundary (host side, outside jit): evicted blocks'
    bank rows stream back to the full store, admitted blocks' rows stream in
    (zero rows on first selection). Retained blocks keep their slots, so
    within an interval with an unchanged mask this is a no-op. ``mask``:
    host bool [num_blocks]. Returns (banks, slot_map, store) — host (numpy)
    store leaves are updated in place, device leaves functionally.

    This is the synchronous composition of the boundary's phases —
    ``plan_swap`` -> ``prefetch_admissions`` -> ``gather_evictions`` ->
    ``writeback_evictions`` ->
    ``commit_swap``. The async planner (core/swap.py) runs the first three
    in the background against the *predicted* next selection while phase B
    and the next phase A compute, leaving only ``commit_swap`` on the
    critical path when the prediction hits."""
    plans = plan_swap(partition, slot_map, mask, bank_caps(banks))
    if not plans:
        return dict(banks), np.array(slot_map, np.int32), dict(store)
    staged = prefetch_admissions(plans, store, staging)
    store = writeback_evictions(plans, gather_evictions(plans, banks), store)
    return commit_swap(plans, banks, store, slot_map, staged)


def banked_update(cfg: OptimizerConfig, partition: BlockPartition,
                  params: dict, grads: dict, banks: dict, counts, mask, lr,
                  use_pallas: bool = False, mesh=None, specs=None):
    """One masked AdamW step on the compact banks (jit-safe; every index is
    a runtime vector of static shape). Assumes residency == selection —
    ``swap_banked`` ran at the last selection change, so every masked
    block's moments sit in a bank row. The row arithmetic is
    ``_adamw_rows``, identical to the dense ``update``; given the same
    (grads, mask, lr) sequence the two layouts are trajectory-exact, which
    keeps the dense implementation as the oracle. Non-resident blocks'
    params (and their store moments) are untouched bit for bit.
    ``mesh``/``specs``: where the kernels run per shard — the multi-device
    mesh and the params' PartitionSpecs (None = replicated).
    Returns (new_params, new_banks, new_counts)."""
    mask = jnp.asarray(mask)
    if specs is None:
        specs = jax.tree.map(lambda _: P(), params)
    counts = jnp.asarray(counts) + mask.astype(jnp.float32)
    new_params, new_banks = {}, {}
    for g in partition.groups:
        bank = banks[g.key]
        slots = jnp.asarray(bank["slots"])
        if g.stacked:
            valid = slots < g.length
            gids = g.start + jnp.minimum(slots, g.length - 1)
            sel = jnp.where(valid, mask[gids].astype(jnp.float32), 0.0)
            cnt = counts[gids]

            def upd(p, gr, m, v, spec):
                if use_pallas:
                    # fused path: the kernel fetches p/g rows through the
                    # slots vector (scalar prefetch) — no [cap, ...] gather
                    # of p or g is materialized, only the compact outputs.
                    from repro.kernels import ops as kops
                    p2, m2, v2 = kops.banked_masked_adamw(
                        p, gr, m, v, slots, sel, cnt, lr, cfg.b1, cfg.b2,
                        cfg.eps, cfg.weight_decay, mesh=mesh, spec=spec)
                    return part_mod.scatter_rows(p, slots, p2), m2, v2
                p_rows = part_mod.gather_rows(p, slots)
                g_rows = part_mod.gather_rows(gr, slots)
                shp = (sel.shape[0],) + (1,) * (p_rows.ndim - 1)
                p2, m2, v2 = _adamw_rows(cfg, p_rows, g_rows, m, v,
                                         sel.reshape(shp), cnt.reshape(shp),
                                         lr, False)
                # free-slot sentinels (slots == g.length) are dropped
                return part_mod.scatter_rows(p, slots, p2), m2, v2

            flat = jax.tree.map(upd, params[g.key], grads[g.key],
                                bank["m"], bank["v"], specs[g.key])
        else:
            resident = slots[0] < g.length
            sel = jnp.where(resident, mask[g.start].astype(jnp.float32), 0.0)
            cnt = counts[g.start]

            def upd(p, gr, m, v, spec):
                # scalar sel/cnt broadcast, as for the dense unstacked leaves
                return _adamw_rows(cfg, p, gr, m, v, sel, cnt, lr,
                                   use_pallas, mesh, spec)

            flat = jax.tree.map(upd, params[g.key], grads[g.key],
                                bank["m"], bank["v"], specs[g.key])
        p_new, m_new, v_new = _unzip3(flat)
        new_params[g.key] = p_new
        new_banks[g.key] = {"m": m_new, "v": v_new, "slots": slots}
    return new_params, new_banks, counts


def materialize_moments(partition: BlockPartition, opt: dict):
    """Full m/v pytrees reconstructed from banks + store (host sync; for
    tests, checkpoint inspection and reporting — training never needs the
    dense view). Returns (m, v) congruent with params."""
    out = {"m": {}, "v": {}}
    for g in partition.groups:
        bank = opt["banks"][g.key]
        slots = np.asarray(bank["slots"])
        for mom in ("m", "v"):
            def one(store_leaf, bank_leaf):
                full = np.array(store_leaf)
                if g.stacked:
                    valid = np.nonzero(slots < g.length)[0]
                    if len(valid):
                        full[slots[valid]] = np.asarray(bank_leaf)[valid]
                elif slots[0] == 0:
                    full[...] = np.asarray(bank_leaf)
                return full
            out[mom][g.key] = jax.tree.map(one, opt["store"][g.key][mom],
                                           bank[mom])
    return out["m"], out["v"]

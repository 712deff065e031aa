"""Async double-buffered moment streaming for banked residency.

The synchronous banked step pays a host boundary between its two compiled
phases: ``swap_banked`` plans the evict/admit sets, stages admissions out of
the full store, and waits for evicted bank rows — all while the device
idles. GRASS (PAPERS.md) hides the analogous projected-gradient traffic by
overlapping it with compute; BlockLLM leans on selections drifting slowly
between steps. ``SwapPlanner`` combines both ideas:

* after step t's apply (phase B) has been *dispatched*, the planner asks the
  selection policy where step t+1 will land (``adagradselect.predict_next``
  — exact for schedule/PRNG-driven policies, the cumulative-signal
  approximation for norm-driven ones) and hands the boundary work to a
  single background thread: plan against the predicted mask, prefetch the
  predicted admit rows store->device into staging, and write predicted
  evictions back device->store (the ``np.asarray`` on bank rows blocks on
  phase B's output *inside the thread*, which is exactly the overlap).
  On a multi-device mesh the job runs *inline* on the dispatching thread
  instead — sharded store reads carry collectives, which deadlock if two
  threads enqueue them concurrently — still after phase B's async dispatch;
* at step t+1's boundary ``resolve`` joins the thread (``swap_join``). If
  the prediction matched the real selection (all-or-nothing on the [k]
  indices vector), ``commit_swap`` (``swap_commit``) runs next — it donates
  the scattered bank leaves, so XLA writes the staged rows in place
  instead of copying each bank. The join is not free. The job learns the
  predicted indices only once phase B is done, and by then the training
  thread has queued the next phase A, so the evicted rows' gathers run
  after that phase A and their copy to the host (``swap_evict``, on the
  planner's track) starts only when it ends: the device idles while the
  training thread waits for the copy. On one TPU v5e with the host store
  (qwen2.5-0.5b, 8x1024 rows, every boundary predicted) the gathers came
  ready within a few ms of phase A's end, the copy ran at about 3 GB/s,
  and the join, ending with it, held about 90% of the boundary's host
  time (the commit the rest). Gathers dispatched ahead of phase A would
  let the copy overlap it. A miss falls back to the synchronous swap
  (``swap_sync``) and is counted (``SwapStats.predicted_hit_rate``).

Why the overlap cannot corrupt state:

* admitted blocks are non-resident, so their store rows are frozen while
  the prediction is in flight — prefetch reads stable data;
* predicted evictions write the post-phase-B bank values of *resident*
  blocks; on a mispredict the store rows written are for blocks whose
  authoritative copy is still the bank, so the write is inert (the sync
  fallback re-writes the real evictions);
* evict and admit sets of one boundary are disjoint, so writeback and
  prefetch commute;
* ``resolve``/``quiesce`` join the thread before the next apply donates the
  bank buffers the writeback reads, and before checkpointing snapshots the
  store.

``StagingPool`` keeps the host-side staging buffers (admission reads out of
a host store) alive across boundaries instead of allocating per swap — the
same pool serves the background path and the synchronous fallback.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from repro import obs
from repro.core import adagradselect
from repro.core import masked_adamw as ma


class StagingPool:
    """Reusable numpy staging buffers for host-store admission reads, keyed
    by (group, moment, leaf index) and grown to the high-water row count.
    ``prefetch_admissions`` blocks on the device transfer before a buffer
    can be handed out again, so a single-slot pool per leaf is enough."""

    def __init__(self):
        self._bufs: dict = {}

    def take(self, key: str, mom: str, leaf_idx: int, n: int,
             leaf: np.ndarray) -> np.ndarray:
        k = (key, mom, leaf_idx)
        buf = self._bufs.get(k)
        shape = (n,) + leaf.shape[1:]
        if buf is None or buf.shape[0] < n or buf.shape[1:] != leaf.shape[1:] \
                or buf.dtype != leaf.dtype:
            buf = np.empty(shape, leaf.dtype)
            self._bufs[k] = buf
        return buf

    def nbytes(self) -> int:
        return sum(b.nbytes for b in self._bufs.values())


def _hist():
    return dataclasses.field(default_factory=obs.Histogram, repr=False)


@dataclasses.dataclass
class SwapStats:
    """Boundary accounting + step-phase timing for the banked driver.
    ``boundaries`` counts selection changes that required bank traffic;
    ``predicted_hits`` those fully absorbed by the background dispatch;
    ``sync_swaps`` the fallback (mispredict, overflow-on-predicted-plan, or
    async disabled). Phase timing lives in obs histograms — the one timing
    source of truth (each is timed once via ``obs.timed`` and its trace
    span rides the same measurement), in µs:

    * ``phase_a``: forward/select dispatch and, inside it, ``index_sync``
      (the wait for the [k] indices);
    * ``swap``: the boundary, holding ``join`` (waiting for the planner's
      job) and ``commit`` (``commit_swap`` on a hit, empty plans included),
      or ``sync`` (the synchronous swap of a miss);
    * ``phase_b``: the apply + dispatch issue;
    * ``admit`` / ``evict``: the moment copies store->device and
      device->store, on the planner's thread or inside ``sync``; ``evict``
      starts once the evicted rows' gathers have run on the device.

    ``admit_bytes`` / ``evict_bytes`` count the moment bytes each direction
    moved. The historical accumulated-µs fields (``phase_a_us`` etc., the
    bench JSON schema) are read-only views over the histograms' totals."""
    steps: int = 0
    boundaries: int = 0
    predicted_hits: int = 0
    sync_swaps: int = 0
    dispatches: int = 0
    phase_a: obs.Histogram = _hist()
    index_sync: obs.Histogram = _hist()
    swap: obs.Histogram = _hist()
    join: obs.Histogram = _hist()
    commit: obs.Histogram = _hist()
    sync: obs.Histogram = _hist()
    admit: obs.Histogram = _hist()
    evict: obs.Histogram = _hist()
    phase_b: obs.Histogram = _hist()
    admit_bytes: obs.Counter = dataclasses.field(
        default_factory=obs.Counter, repr=False)
    evict_bytes: obs.Counter = dataclasses.field(
        default_factory=obs.Counter, repr=False)

    @property
    def phase_a_us(self) -> float:
        return self.phase_a.total

    @property
    def swap_us(self) -> float:
        return self.swap.total

    @property
    def phase_b_us(self) -> float:
        return self.phase_b.total

    @property
    def predicted_hit_rate(self) -> float:
        return self.predicted_hits / self.boundaries if self.boundaries else 1.0

    def as_dict(self) -> dict:
        return {"steps": self.steps, "boundaries": self.boundaries,
                "predicted_hits": self.predicted_hits,
                "sync_swaps": self.sync_swaps,
                "dispatches": self.dispatches,
                "phase_a_us": self.phase_a_us, "swap_us": self.swap_us,
                "phase_b_us": self.phase_b_us,
                "predicted_hit_rate": self.predicted_hit_rate,
                "admit_bytes": self.admit_bytes.value,
                "evict_bytes": self.evict_bytes.value}


class SwapPlanner:
    """Owns the background boundary work for one banked trainer. At most one
    job is ever in flight; ``resolve`` (or ``quiesce``) joins it before any
    state the job reads can be donated, checkpointed, or mutated."""

    def __init__(self, partition, select_cfg, num_blocks: int,
                 enabled: bool = True, inline: bool = False):
        self.partition = partition
        self.num_blocks = num_blocks
        self.enabled = enabled
        # On a multi-device mesh the job's store/bank reads are sharded, so
        # they lower to collective-bearing XLA computations. Collectives
        # rendezvous by enqueue order; a second thread issuing them while
        # phase B's collectives are in flight can interleave participants
        # from different executions and deadlock. ``inline`` runs the job on
        # the dispatching thread instead — one enqueue order, and the device
        # still overlaps because phase B was already dispatched async.
        self.inline = inline
        self.staging = StagingPool()
        self.stats = SwapStats()
        # last-planner-wins registry bindings: the active trainer's phase
        # histograms and boundary counters show up in obs.snapshot()
        for name in ("phase_a", "index_sync", "swap", "join", "commit",
                     "sync", "admit", "evict", "phase_b"):
            obs.metrics.register(f"{name}_us", getattr(self.stats, name),
                                 subsystem="swap")
        obs.metrics.register("banked", self.stats.as_dict, subsystem="swap")
        self._pool: ThreadPoolExecutor | None = None
        self._pending = None  # Future | dict -> dict | None
        self._predict = jax.jit(
            lambda st: adagradselect.predict_next(select_cfg, st, num_blocks))

    # ------------------------------------------------------------ dispatch

    def dispatch(self, sel_state: dict, banks: dict, store: dict,
                 slot_map) -> None:
        """Kick off the predicted boundary for the *next* step. Call after
        this step's apply has been dispatched: the job's device reads block
        on apply's outputs in the background thread, not on the main one.
        No-op (beyond the prediction) when async streaming is disabled."""
        if not self.enabled or self._pending is not None:
            return
        pred_idx = self._predict(sel_state)  # async device dispatch
        caps = ma.bank_caps(banks)
        slot_map = np.array(slot_map, np.int32)  # snapshot: host-global map

        def job():
            # the span puts the boundary work on its own timeline track
            # when the job runs on the "swap-planner" background thread
            with obs.span("swap_dispatch_job"):
                idx = np.asarray(pred_idx)
                mask = np.zeros((self.num_blocks,), bool)
                mask[idx[idx < self.num_blocks]] = True
                try:
                    plans = ma.plan_swap(self.partition, slot_map, mask, caps)
                except RuntimeError:
                    # predicted selection overflows the banks — the real one
                    # may not (or will raise on the sync path with context)
                    return {"idx": idx, "failed": True}
                staged, new_store = self._move(plans, banks, store)
                return {"idx": idx, "failed": False, "plans": plans,
                        "staged": staged, "store": new_store}

        if self.inline:
            self._pending = job()
        else:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="swap-planner")
            self._pending = self._pool.submit(job)
        self.stats.dispatches += 1

    # ------------------------------------------------------------- resolve

    def resolve(self, indices, banks: dict, store: dict, slot_map):
        """The selection-change boundary for the *actual* indices of this
        step. Joins any in-flight dispatch; on an exact prediction hit only
        ``commit_swap`` runs here — with the bank leaves *donated*, so the
        staged rows are written in place rather than copying each bank.
        Donation is safe exactly here: the caller hands over its banks and
        uses only the returned ones, and the joined job was the last other
        reader. A miss falls back to the synchronous ``swap_banked`` path
        (same donation, pooled staging). Returns (banks, slot_map, store)."""
        idx = np.asarray(indices)
        with obs.timed(self.stats.join, "swap_join"):
            job = self._take_pending()
        if job is not None and not job["failed"] \
                and np.array_equal(job["idx"], idx):
            if job["plans"]:  # unchanged selections are not boundaries
                self.stats.boundaries += 1
                self.stats.predicted_hits += 1
            with obs.timed(self.stats.commit, "swap_commit"):
                return ma.commit_swap(job["plans"], banks, job["store"],
                                      slot_map, job["staged"], donate=True)
        if job is not None:
            # keep the job's store: predicted-eviction writebacks are inert
            # for still-resident blocks and identical for real evictions
            store = job["store"] if not job["failed"] else store
        with obs.timed(self.stats.sync, "swap_sync"):
            mask = np.zeros((self.num_blocks,), bool)
            mask[idx[idx < self.num_blocks]] = True
            plans = ma.plan_swap(self.partition, slot_map, mask,
                                 ma.bank_caps(banks))
            if not plans:
                return dict(banks), np.array(slot_map, np.int32), dict(store)
            self.stats.boundaries += 1
            self.stats.sync_swaps += 1
            if job is not None:
                # a dispatch was in flight but missed (or overflowed)
                obs.instant("swap_mispredict",
                            {"predicted": job["idx"].tolist(),
                             "actual": idx.tolist()} if not job["failed"]
                            else {"failed_plan": True})
            staged, store = self._move(plans, banks, store)
            return ma.commit_swap(plans, banks, store, slot_map, staged,
                                  donate=True)

    def _move(self, plans: list, banks: dict, store: dict):
        """The boundary's copies for ``plans``: admitted blocks' moments
        store->device, then evicted ones device->store, each timed and its
        bytes counted. Returns (staged admissions, store)."""
        admit = _moved(plans, store, "ad_blocks")
        evict = _moved(plans, store, "ev_blocks")
        # dispatch the evicted rows' gathers before the admissions' copies;
        # they run once phase B's banks are ready, behind whatever the
        # device has queued by then
        rows = ma.gather_evictions(plans, banks)
        with obs.timed(self.stats.admit, "swap_admit", admit):
            staged = ma.prefetch_admissions(plans, store, self.staging)
        # wait for the gathers outside the span (the device_get inside
        # would block on them anyway), so it times the copy to the host
        jax.block_until_ready(rows)
        with obs.timed(self.stats.evict, "swap_evict", evict):
            store = ma.writeback_evictions(plans, rows, store)
        self.stats.admit_bytes.inc(sum(admit["bytes"]))
        self.stats.evict_bytes.inc(sum(evict["bytes"]))
        return staged, store

    # ------------------------------------------------------------- barrier

    def quiesce(self) -> None:
        """Join and discard any in-flight dispatch. Must run before
        checkpointing (the job holds references into banks/store) and at
        the end of training. Discarding loses nothing: staged admissions
        are re-derivable and predicted-eviction writebacks are inert."""
        self._take_pending()

    def close(self) -> None:
        self.quiesce()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def _take_pending(self):
        job, self._pending = self._pending, None
        if job is None or isinstance(job, dict):  # inline jobs store results
            return job
        return job.result()


def _moved(plans: list, store: dict, side: str) -> dict:
    """Per group that moves blocks on ``side`` (``"ad_blocks"`` or
    ``"ev_blocks"``): its key, the blocks and their moment bytes (first
    and second moments, from the store's row sizes)."""
    out = {"group": [], "blocks": [], "bytes": []}
    for plan in plans:
        n = len(getattr(plan, side))
        if not n:
            continue
        row = sum(leaf.nbytes // (leaf.shape[0] if plan.stacked else 1)
                  for leaf in jax.tree.leaves(store[plan.key]))
        out["group"].append(plan.key)
        out["blocks"].append(n)
        out["bytes"].append(n * row)
    return out

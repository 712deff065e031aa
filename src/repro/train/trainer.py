"""Training loop: logging, checkpointing, straggler watchdog, eval, restore.

Method-agnostic: the fine-tuning method (full FT, AdaGradSelect and the
other selection policies, LoRA, ...) is resolved through the
``repro.methods`` registry, which supplies the TrainState, the compiled step
function, and eval/accounting hooks — the trainer never inspects the method
name. Runs the same code path single-device (tests/examples) and distributed:
with ``mesh=...`` the trainer shards the batch over the mesh's batch axes
(global_batch must divide the dp degree), places the TrainState per the
method's ``state_shardings()`` tree (params/moments sharded or replicated,
HOST_RESIDENT leaves left in host RAM), and hands the sharding tree to
``make_step`` so compiled steps pin their outputs to the same layout
(compile-once under data parallelism). Fault-tolerance contract:
  * `checkpoint_every` saves are async + atomic and include the full
    TrainState (method state included) plus the data cursor: for legacy
    pure-f(step) sources the cursor IS the step counter; streaming pipelines
    (repro.data.pipeline) serialize their record cursor into the checkpoint
    meta and resume the packed stream exactly;
  * on start, `maybe_restore()` resumes from the latest checkpoint;

Data enters through an iterator seam: ``_batch_stream`` yields
``(host_batch, cursor_after)`` pairs (legacy ``batch_at`` sources ride a
StepIndexedAdapter), and with ``prefetch_depth > 0`` a background
``Prefetcher`` builds and device_puts up to that many batches ahead
(respecting the mesh batch sharding) so host batch construction overlaps
device compute. Prefetch on/off changes timing only — trajectories are
bit-identical.
  * a step-time EWMA watchdog flags stragglers (> tau * EWMA) and calls the
    configurable `on_straggler` hook (default: log; production: abort to the
    last checkpoint so the scheduler can replace the slow host).

Scalar materialization is deferred to `log_every` boundaries: between
boundaries the loop only enqueues compiled steps (losses are kept as device
scalars), and at a boundary a single `block_until_ready` drains the pipeline
so the per-step timing EWMA stays honest (boundary timings are the window
average). `log_every=0` — or passing a custom `on_straggler` hook, which
needs true per-step times so a single slow step is never averaged away —
syncs every step (the exact-timing mode benchmarks rely on).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

from repro import methods, obs
from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import TrainConfig
from repro.data import loader as data_loader


@dataclass
class TrainLog:
    steps: list = field(default_factory=list)
    losses: list = field(default_factory=list)
    step_times: list = field(default_factory=list)
    metrics: list = field(default_factory=list)


def _place_state(state, shardings):
    """device_put every leaf onto its sharding; HOST_RESIDENT markers (the
    banked slot_map / "host"-policy store) stay numpy in host RAM."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s)
        if isinstance(s, jax.sharding.Sharding) else x,
        state, shardings)


class Trainer:
    def __init__(self, tcfg: TrainConfig, *, mesh=None, batch_axes=("data",),
                 method: str | None = None, data_source=None,
                 batch_shardings=None, on_straggler=None,
                 prefetch_depth: int = 0):
        self.tcfg = tcfg
        self.mesh = mesh
        self.method_name = method or tcfg.method
        self.method = methods.build(self.method_name, tcfg)
        self.sel_cfg = getattr(self.method, "sel_cfg", tcfg.select)
        self.batch_shardings = batch_shardings
        self._watchdog_active = on_straggler is not None
        self.on_straggler = on_straggler or (lambda step, dt, ewma: None)
        init_kw = {"mesh": mesh} if mesh is not None else {}
        self.state = self.method.init_state(tcfg.model, tcfg.optimizer,
                                            tcfg.seed, **init_kw)

        # -- data-parallel placement: shard/replicate the TrainState per the
        # method's sharding tree and shard the batch over the mesh's batch
        # axes. The same code path runs single-device when mesh is None.
        self.state_shardings = None
        step_kw = {}
        if mesh is not None and hasattr(self.method, "state_shardings"):
            self.state_shardings = self.method.state_shardings(
                tcfg.model, tcfg.optimizer, self.state, mesh)
            self.state = _place_state(self.state, self.state_shardings)
            step_kw["state_shardings"] = self.state_shardings
        if mesh is not None and batch_shardings is None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            baxes = tuple(a for a in batch_axes if a in mesh.axis_names)
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            dp = 1
            for a in baxes:
                dp *= sizes[a]
            if tcfg.global_batch % max(1, dp):
                raise ValueError(
                    f"global_batch={tcfg.global_batch} must be divisible by "
                    f"the data-parallel degree {dp} (mesh axes {baxes})")
            self._batch_sharding = NamedSharding(mesh, P(baxes))
        else:
            self._batch_sharding = None

        self.step_fn = self.method.make_step(
            tcfg.model, tcfg.optimizer, mesh=mesh, batch_axes=batch_axes,
            **step_kw)
        self.data = data_source or data_loader.make_source(
            "synthetic_math", seq_len=tcfg.seq_len,
            global_batch=tcfg.global_batch, seed=tcfg.seed)
        self.prefetch_depth = prefetch_depth
        self.ckpt = (CheckpointManager(tcfg.checkpoint_dir, tcfg.checkpoint_keep)
                     if tcfg.checkpoint_dir else None)
        self.log = TrainLog()
        self._ewma = None
        self._data_cursor = None  # cursor AFTER the last consumed batch

        # always-on registry instruments (host-side, sub-µs; the tracing/
        # selection-telemetry syncs below are gated on obs.enabled())
        self._m_steps = obs.metrics.counter("steps", subsystem="train")
        self._m_step_time = obs.metrics.histogram("step_time_us",
                                                  subsystem="train")
        self._m_stragglers = obs.metrics.counter("stragglers",
                                                 subsystem="train")
        self._m_loss = obs.metrics.gauge("last_loss", subsystem="train")

    # ------------------------------------------------------------- resume
    def maybe_restore(self) -> int:
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return 0
        # shardings re-place restored (numpy) leaves onto the current mesh —
        # the sharded-store round-trip and elastic resharding both land here
        self.state, step = self.ckpt.restore(
            self.state, shardings=self.state_shardings)
        # streaming sources (data/pipeline) resume their record stream from
        # the cursor saved next to the TrainState; pure-f(step) sources need
        # only the step counter (their "cursor" is implicit)
        cursor = self.ckpt.load_meta(step).get("data_cursor")
        if cursor is not None and hasattr(self.data, "restore_cursor"):
            self.data.restore_cursor(cursor)
        return step

    # ------------------------------------------------------------- loop
    def _device_batch(self, batch: dict):
        if self.batch_shardings is not None:
            return jax.tree.map(jax.device_put, batch, self.batch_shardings)
        if self._batch_sharding is not None:
            return jax.tree.map(
                lambda x: jax.device_put(x, self._batch_sharding), batch)
        return batch

    def _batch_stream(self, step0: int, steps: int):
        """(host_batch, cursor_after) pairs for the next ``steps`` steps.

        Streaming pipelines (anything with ``.batches``) iterate from their
        committed cursor; legacy pure-``f(step)`` sources go through the
        StepIndexedAdapter. Either way the generator never mutates source
        state — the loop commits consumption via ``restore_cursor`` — so a
        prefetcher may run it arbitrarily far ahead."""
        if hasattr(self.data, "batches"):
            return self.data.batches(steps)
        from repro.data.pipeline import StepIndexedAdapter
        return StepIndexedAdapter(self.data, step0).batches(steps)

    def train(self, steps: int | None = None, start_step: int | None = None):
        tcfg = self.tcfg
        steps = steps if steps is not None else tcfg.steps
        step0 = start_step if start_step is not None else int(self.state["step"])
        last = step0 + steps - 1
        pending = []  # (step, device-scalar loss) since the last boundary
        t0 = time.perf_counter()
        from repro.data.pipeline import Prefetcher
        fetch = Prefetcher(self._batch_stream(step0, steps),
                           self._device_batch, depth=self.prefetch_depth)
        try:
            self._train_loop(tcfg, fetch, step0, steps, last, pending, t0)
        finally:
            fetch.close()
            # async banked streaming: join any in-flight boundary dispatch
            # before the caller can read/checkpoint/donate the state it
            # references (the job mutates the host store in place)
            planner = getattr(self.step_fn, "swap_planner", None)
            if planner is not None:
                planner.quiesce()
            # commit consumption: read-ahead must not advance the stream
            # past what the loop actually trained on
            if (self._data_cursor is not None
                    and hasattr(self.data, "restore_cursor")):
                self.data.restore_cursor(self._data_cursor)
        if self.ckpt is not None:
            self.ckpt.wait()
        return self.log

    def _train_loop(self, tcfg, fetch, step0, steps, last, pending, t0):
        sel_trace = obs.selection_trace()
        for step in range(step0, step0 + steps):
            batch, self._data_cursor = next(fetch)
            if not pending:
                t0 = time.perf_counter()
            with obs.span("train_step", {"step": step} if obs.enabled()
                          else None):
                self.state, metrics = self.step_fn(self.state, batch)
            pending.append((step, metrics["loss"]))

            # selection telemetry (obs-enabled only: pulling the mask off
            # the device is a host sync the disabled contract forbids). The
            # recorded mask is the one this step's update applied, so the
            # accumulated counts reproduce state["opt"]["counts"] exactly.
            if sel_trace is not None and metrics.get("mask") is not None:
                sel_trace.record(step, np.asarray(metrics["mask"]),
                                 np.asarray(metrics["block_norms"])
                                 if metrics.get("block_norms") is not None
                                 else None)

            at_log = tcfg.log_every and step % tcfg.log_every == 0
            if (at_log or step == last or not tcfg.log_every
                    or self._watchdog_active):
                with obs.span("log_sync"):
                    jax.block_until_ready(metrics["loss"])
                dt = (time.perf_counter() - t0) / len(pending)
                # steps/losses/step_times extend together at the boundary so
                # the lists never misalign if the loop exits mid-window
                self.log.steps.extend(s for s, _ in pending)
                self.log.losses.extend(float(np.asarray(x))
                                       for _, x in pending)
                self.log.step_times.extend([dt] * len(pending))
                self._m_steps.inc(len(pending))
                self._m_step_time.record(dt * 1e6)
                self._m_loss.set(self.log.losses[-1])
                pending = []

                # straggler watchdog (EWMA of step time, warmup-excluded)
                if step > step0 + 2:
                    self._ewma = dt if self._ewma is None else \
                        0.9 * self._ewma + 0.1 * dt
                    if self._ewma and dt > tcfg.straggler_tau * self._ewma:
                        self._m_stragglers.inc()
                        obs.instant("straggler",
                                    {"step": step, "dt_s": dt,
                                     "ewma_s": self._ewma})
                        self.on_straggler(step, dt, self._ewma)

            if at_log:
                small = {k: np.asarray(v).tolist() for k, v in metrics.items()
                         if np.ndim(v) == 0}
                self.log.metrics.append({"step": step, **small})
            if (self.ckpt is not None and tcfg.checkpoint_every
                    and (step + 1) % tcfg.checkpoint_every == 0):
                # an in-flight boundary dispatch holds references into the
                # banks/store about to be snapshotted (and writes the host
                # store in place) — barrier it out before saving
                planner = getattr(self.step_fn, "swap_planner", None)
                if planner is not None:
                    planner.quiesce()
                # the data cursor rides along in meta.json: restoring this
                # checkpoint resumes the record stream exactly after the
                # batch consumed at `step` (no skips, no repeats)
                self.ckpt.save(step + 1, self.state,
                               extra_meta={"data_cursor": self._data_cursor})

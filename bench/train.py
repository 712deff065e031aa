"""Training cells: one ``repro.train.trainer.Trainer`` built once, fed by
the program's packer and prefetcher from a seeded ``RecordSource``.

Set-up builds the trainer, puts the seeded weights in its state and drives
it through its first steps with its own ``train`` call and feed; the
numbers compared with the reference are read from those steps. The same
object then runs the measured window: ``Trainer.train(n)`` with ``n`` steps
worth ``--seconds`` at the cell's nominal step time (``step_s`` in its
workload file, measured on the chip when the cell was added: every run
trains the same steps), ending on ``block_until_ready`` of the last step's
outputs. After the window the program's state is freed and the reference
runs the first steps again.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time

import numpy as np

import harness
import traffic
import weights
import work

CHECK_STEPS = 3
# host spans (repro.obs) that label the device's idle gaps; a gap takes the
# innermost span open at its midpoint
SPAN_LABELS = ("phase_a", "swap", "phase_b", "log_sync", "train_step",
               "index_sync", "swap_join", "swap_commit", "swap_sync",
               "data_wait")


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _Records:
    """``repro.data.pipeline.RecordSource`` over bench/traffic.SftRecords."""

    def __init__(self, gen, record_cls):
        self._gen, self._cls = gen, record_cls
        self.num_records = gen.num_records

    def record_at(self, index: int):
        p, c = self._gen.arrays(index)
        return self._cls(prompt=p, completion=c)


def train_config(cell, seed: int):
    from repro.configs.base import OptimizerConfig, SelectConfig, TrainConfig
    job, sysw = cell.traffic["job"], cell.workload
    opt = OptimizerConfig(**job["optimizer"],
                          moment_residency=sysw["moment_residency"],
                          offload=sysw["offload"],
                          async_swap=sysw["async_swap"])
    return TrainConfig(model=cell.family.program_config(cell.config),
                       method=job["method"],
                       select=SelectConfig(**job["select"]), optimizer=opt,
                       seq_len=job["seq_len"],
                       global_batch=job["global_batch"],
                       steps=job["optimizer"]["total_steps"],
                       seed=program_seed(cell, seed))


def _explores(cands: np.ndarray, sel: dict, steps: int) -> np.ndarray:
    """[candidates, steps] bool: whether AdaGradSelect's draw at each step
    explores (top-k of cumulative norms) under each candidate seed. The
    draw of step t is ``uniform(split(fold_in(PRNGKey(s), t), 4)[0]) <
    eps0 * exp(-decay * t)`` (zero from the second epoch on), as the
    configuration specifies its seeding."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draws(cands):
        t = jnp.arange(steps)
        eps = sel["epsilon0"] * jnp.exp(-sel["epsilon_decay"]
                                        * t.astype(jnp.float32))
        eps = jnp.where(t < sel["steps_per_epoch"], eps, 0.0)

        def one(s):
            key = jax.random.PRNGKey(s)
            u = jax.vmap(lambda i: jax.random.uniform(
                jax.random.split(jax.random.fold_in(key, i), 4)[0]))(t)
            return u < eps
        return jax.vmap(one)(cands)
    return np.asarray(draws(jnp.asarray(cands, jnp.int32)))


def program_seed(cell, seed: int) -> int:
    """The seed handed to the program (its PRNGKey takes 31 bits).

    For AdaGradSelect it is the workload's ``selection_seed``: every seed
    then makes the same selections, so the boundary moves the same blocks
    and the window does the same work; ``seed`` varies the weights and the
    rows. The workload's seed is one under which the checked steps explore,
    exploit, explore (step 1 evicts blocks that step 2 brings back from
    the store)."""
    if cell.traffic["job"]["method"] != "adagradselect":
        return int(seed) % (2 ** 31)
    return int(cell.workload["selection_seed"])


def make_feed(cell, seed: int):
    """The program's packer over the seeded records (pure in its cursor)."""
    from repro.data.pipeline import SFTPipeline
    from repro.data.pipeline.records import Record
    job = cell.traffic["job"]
    gen = traffic.SftRecords(cell.traffic["records"], seed,
                             int(cell.config["vocab_size"]), job["seq_len"])
    return SFTPipeline(_Records(gen, Record), seq_len=job["seq_len"],
                       global_batch=job["global_batch"], pack=True)


def first_batches(feed) -> list:
    """The batches of the first CHECK_STEPS steps."""
    out, cur = [], 0
    for _ in range(CHECK_STEPS):
        b, cur = feed.build(cur)
        out.append(b)
    return out


def data_mesh(cell, devs):
    """The program's data-parallel mesh over the cell's chips:
    ``("data", "model")`` with a model axis of 1, as its launcher's
    ``--mesh data`` lays it out. None on one chip, where the trainer runs
    without a mesh."""
    if cell.chips == 1:
        return None
    from jax.sharding import AxisType, Mesh
    grid = np.array(devs[:cell.chips]).reshape(cell.chips, 1)
    return Mesh(grid, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def build(cell, seed: int, devs=None):
    """The trainer with the seeded weights in its state, and its feed."""
    import jax

    from repro.models import registry
    from repro.train.trainer import Trainer

    tcfg = train_config(cell, seed)
    mcfg = tcfg.model
    feed = make_feed(cell, seed)
    mesh = data_mesh(cell, devs)
    trainer = Trainer(tcfg, data_source=feed,
                      prefetch_depth=cell.workload["prefetch_depth"],
                      **({"mesh": mesh} if mesh is not None else {}))
    # the program's own random parameters leave before the seeded ones
    # arrive, so the two never share the device (set-up would otherwise
    # set the peak memory)
    trainer.state["params"] = None
    shardings = (None if trainer.state_shardings is None
                 else trainer.state_shardings["params"])
    params = weights.program_tree(seed, cell.family.leaves(cell.config),
                                  mcfg.dtype, shardings)
    weights.check_against(params, jax.eval_shape(
        lambda: registry.get(mcfg).init(jax.random.PRNGKey(0), mcfg)))
    trainer.state["params"] = params
    return trainer, feed


def _rows_norm(x, rows):
    """L2 norm of the rows of ``x`` that ``rows`` marks, in one fused
    program (no copy of the rows is made)."""
    import jax.numpy as jnp
    sq = jnp.square(x.astype(jnp.float32))
    return jnp.sqrt(jnp.sum(jnp.where(
        rows.reshape((-1,) + (1,) * (x.ndim - 1)), sq, 0.0)))


def first_grad_norms(trainer, cell) -> dict:
    """Per published leaf, the norm of the first step's gradient as the
    optimizer got it: its first moment after one step over (1 - b1)."""
    import jax
    import jax.numpy as jnp

    from repro.core import partition as part_mod
    b1 = trainer.tcfg.optimizer.b1
    groups = {g.key: g for g in
              part_mod.build_partition(trainer.tcfg.model).groups}
    opt = trainer.state["opt"]
    norm = jax.jit(_rows_norm)
    out = {}
    for lf in cell.family.leaves(cell.config):
        if "m" in opt:                                  # dense moments
            leaf = weights.at_path(opt["m"], lf.path)
            rows = np.ones((leaf.shape[0],), bool)
        else:                                           # banked moments:
            group = groups[lf.path[0]]                  # rows of resident
            bank = opt["banks"][group.key]              # blocks only
            leaf = weights.at_path(bank["m"], lf.path[1:])
            slots = np.asarray(bank["slots"])
            if group.stacked:
                rows = slots < group.length
            else:
                rows = np.full((leaf.shape[0],),
                               bool(slots[0] < group.length))
        out[lf.name] = float(norm(leaf, jnp.asarray(rows))) / (1 - b1)
    return out


def _warm_swap_shapes(trainer) -> None:
    """Compile the banked boundary's row moves for every row count the
    window can ask for, so that nothing compiles inside it: the gather and
    scatter of each stacked group's bank rows; where the store is on the
    device (``zero1``), the boundary's own store reads and writes of every
    group too, on copies of what it would donate."""
    import jax
    import jax.numpy as jnp

    from repro.core import masked_adamw as ma
    from repro.core import partition as part_mod
    opt = trainer.state["opt"]
    gather = getattr(ma, "_gather_group", None)
    scatter = getattr(ma, "_scatter_group_donated", None)
    if "banks" not in opt or gather is None or scatter is None:
        return
    for g in part_mod.build_partition(trainer.tcfg.model).groups:
        bank = opt["banks"][g.key]
        cap = int(bank["slots"].shape[0])
        if not isinstance(jax.tree.leaves(opt["store"][g.key])[0],
                          np.ndarray):
            for n in range(1, cap + 1):
                _warm_device_store(ma, g, n, bank, opt["store"])
            continue
        if not g.stacked:
            continue
        leaves = tuple(jax.tree.leaves(bank["m"]) + jax.tree.leaves(bank["v"]))
        for n in range(1, cap + 1):
            # the boundary turns numpy slot lists into device indices
            jnp.asarray(np.zeros((n,), np.int64), jnp.int32)
            idx = jnp.zeros((n,), jnp.int32)
            rows = gather(leaves, idx)
            jax.block_until_ready(scatter(tuple(jnp.copy(x) for x in leaves),
                                          idx, rows))


def _warm_device_store(ma, group, n: int, bank: dict, store: dict) -> None:
    """One boundary of ``n`` blocks of ``group`` each way through the
    program's own moves, with every result dropped: the store writes are
    functional on the device, and the commit donates a copy of the
    bank."""
    import jax
    import jax.numpy as jnp
    blocks = np.arange(n)
    plan = ma.GroupSwapPlan(key=group.key, start=group.start,
                            length=group.length, stacked=group.stacked,
                            ev_blocks=blocks, ev_slots=blocks,
                            ad_blocks=blocks, ad_slots=blocks)
    rows = ma.gather_evictions([plan], {group.key: bank})
    staged = ma.prefetch_admissions([plan], store)
    jax.block_until_ready(rows)
    new_store = ma.writeback_evictions([plan], rows, store)
    del rows
    if group.stacked:
        bank = {"m": jax.tree.map(jnp.copy, bank["m"]),
                "v": jax.tree.map(jnp.copy, bank["v"]),
                "slots": bank["slots"]}
    banks, _, _ = ma.commit_swap([plan], {group.key: bank}, store,
                                 np.full((group.start + group.length,), -1,
                                         np.int32), staged, donate=True)
    jax.block_until_ready((banks, new_store))


def _window_tokens(feed, cursor: int, steps: int) -> tuple[int, int]:
    """Non-pad tokens and causal query-key pairs of the ``steps`` batches
    trained from ``cursor`` (the packer is pure in its cursor)."""
    tokens = pairs = 0
    for _ in range(steps):
        batch, cursor = feed.build(cursor)
        seg = batch["segment_ids"]
        tokens += int(np.count_nonzero(seg))
        for row in seg:
            _, lens = np.unique(row[row > 0], return_counts=True)
            pairs += work.causal_pairs(lens)
    return tokens, pairs


def _restored(masks) -> int:
    """Blocks selected at the first step, left out at the second (evicted
    from the bank) and selected again at the third (restored from the
    store)."""
    m0, m1, m2 = (np.asarray(m, bool) for m in masks)
    return int(np.sum(m0 & ~m1 & m2))


def _compare(prog: dict, ref: dict, limits: dict, cell) -> list:
    """The numbers compared, each a worst case, and their limits."""
    checks = []
    gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                    ref["losses"]))
    checks.append(harness.Check("loss_gap", gap, limits["loss_gap"]))
    g0 = ref["grad0"]
    med = float(np.median(list(g0.values())))
    # a leaf whose gradient is nought to rounding (a key bias under
    # softmax) moves under Adam by round-off alone: left out by this rule
    keep = [k for k, x in g0.items() if x >= 1e-3 * med]
    for name, key in (("grad_gap", "first_grad"), ("change_gap", "change")):
        r = {k: ref[key][k] for k in keep}
        scale = float(np.median(list(r.values())))
        worst = max(abs(prog[key][k] - r[k]) / max(r[k], scale)
                    for k in keep)
        checks.append(harness.Check(name, worst, limits[name]))
    if cell.traffic["job"]["method"] == "adagradselect":
        # the checked steps must have moved moments out of the bank and
        # back, or the comparison says nothing of the banked swap
        checks.append(harness.Check("blocks_restored",
                                    _restored(prog["masks"]), 1,
                                    at_least=True))
    _say(f"[check] leaves compared {keep}; left out "
         f"{sorted(set(g0) - set(keep))}")
    for k in sorted(g0):
        _say(f"[leaf] {k}: grad0 ref {g0[k]:.4e} | first grad prog "
             f"{prog['first_grad'][k]:.6e} ref {ref['first_grad'][k]:.6e} | "
             f"change prog {prog['change'][k]:.6e} ref {ref['change'][k]:.6e}")
    for name, masks in (("program", prog["masks"]), ("reference",
                                                     ref["masks"])):
        _say(f"[check] selected blocks by step, {name}: "
             f"{[np.flatnonzero(m).tolist() for m in masks]}")
    return checks


def reference_readings(cell, seed: int, batches: list, mode: str = "f32"):
    """The reference's first steps of the same job from the same seed."""
    job = cell.traffic["job"]
    return cell.family.reference_train(
        cell.config, seed, batches, opt=job["optimizer"], sel=job["select"],
        method=job["method"], program_seed=program_seed(cell, seed),
        mode=mode)


def setup_steps(trainer, feed, cell, seed: int) -> dict:
    """Steps 1..CHECK_STEPS through the trainer's own call and feed, and
    what the comparison reads from them. The parameters after the last go
    to the host: their change is measured once the program has left the
    device (``change_norms``)."""
    import jax
    log = trainer.train(steps=1)
    first = first_grad_norms(trainer, cell)
    mask0 = np.asarray(trainer.state["sel"]["mask"], np.float32)
    trainer.train(steps=CHECK_STEPS - 1)
    counts = np.asarray(trainer.state["opt"]["counts"])
    mask2 = np.asarray(trainer.state["sel"]["mask"], np.float32)
    # the middle step's mask from the per-block counts of all three
    masks = [mask0, counts - mask0 - mask2, mask2]
    return {"losses": list(log.losses[:CHECK_STEPS]), "first_grad": first,
            "params": jax.device_get(trainer.state["params"]),
            "batches": first_batches(feed), "masks": masks}


def change_norms(prog: dict, cell, seed: int) -> None:
    """``prog["change"]``: per leaf, how far the checked steps moved the
    parameters from the seeded weights. Runs after ``release``, so that
    nothing of the check shares the device with the program's state."""
    prog["change"] = weights.change_norms(
        prog.pop("params"), seed, cell.family.leaves(cell.config))


def _say_memory(stage: str, devs, t_start: float) -> None:
    stats = devs[0].memory_stats() or {}
    _say(f"[memory] {stage} at {time.perf_counter() - t_start:.1f}s: in "
         f"use {stats.get('bytes_in_use', 0)}, peak "
         f"{stats.get('peak_bytes_in_use', 0)}")


def release(trainer) -> None:
    planner = getattr(trainer.step_fn, "swap_planner", None)
    if planner is not None:
        planner.close()
    trainer.state = None
    gc.collect()


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devs) -> str:
    import jax
    import jax.numpy as jnp

    trainer, feed = build(cell, seed, devs)
    _say_memory("built", devs, t_start)
    prog = setup_steps(trainer, feed, cell, seed)
    _say_memory("after the first steps", devs, t_start)
    _warm_swap_shapes(trainer)
    _say_memory("warmed", devs, t_start)
    steps = max(2, int(round(seconds / cell.workload["step_s"])))
    stats = getattr(trainer.step_fn, "swap_stats", None)
    swap0 = (stats.swap.total, stats.steps, stats.boundaries,
             stats.predicted_hits) if stats is not None else None
    counts0 = np.asarray(trainer.state["opt"]["counts"])
    cursor0 = feed.cursor()["record"]
    tracer = harness.Trace(cell, trace)
    tracer.start()
    _say(f"[window] start: {steps} steps")
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with jax.profiler.TraceAnnotation("bench_window"):
        trainer.train(steps=steps)
        jax.block_until_ready((trainer.state["params"],
                               trainer.state["opt"]))
    window_s = time.perf_counter() - t0
    tracer.stop()
    _say(f"[window] {steps} steps in {window_s}s")
    _say_memory("after the window", devs, t_start)
    device = harness.device_report(devs)
    counts1 = np.asarray(trainer.state["opt"]["counts"])
    tokens, pairs = _window_tokens(feed, cursor0, steps)
    # shares of a peak are of the cell's chips together: ``chips`` times
    # one chip's peak over the window (a roofline reader's kernel time is
    # per chip)
    ctx = {"kind": "train", "window_s": window_s, "steps": steps,
           "tokens": tokens, "chips": len(devs),
           "flops": cell.family.train_flops(cell.config, tokens, pairs),
           "peaks": harness.peaks(device["kind"], cell.root)}
    if stats is not None:
        ctx["swap"] = {"swap_us": stats.swap.total - swap0[0],
                       "steps": stats.steps - swap0[1],
                       "boundaries": stats.boundaries - swap0[2],
                       "predicted_hits": stats.predicted_hits - swap0[3]}
        _say(f"[window] selection changed at {ctx['swap']['boundaries']} of "
             f"{ctx['swap']['steps']} steps; the swap planner had staged "
             f"{ctx['swap']['predicted_hits']} of them")
    per_block = np.array(cell.family.block_params(cell.config), np.int64)
    p_bytes = jnp.dtype(cell.config["torch_dtype"]).itemsize
    m_bytes = jnp.dtype(cell.traffic["job"]["optimizer"].get(
        "moment_dtype", "float32")).itemsize
    updated = int(np.sum((counts1 - counts0) * per_block))
    ctx["optimizer_update_bytes"] = work.adamw_bytes(updated, p_bytes, p_bytes,
                                                     m_bytes)
    ctx["grad_norm_bytes"] = steps * work.grad_read_bytes(
        int(per_block.sum()), p_bytes)
    breakdown = None
    if trace:
        metrics, breakdown = harness.traced_metrics(
            cell, ctx, tracer.reduce(SPAN_LABELS), device)
    else:
        metrics = harness.end_to_end(cell, {
            "train_tokens_per_s": tokens / window_s,
            "train_peak_hbm_gib": device["memory_peak_bytes"] / 2 ** 30,
            "setup_s": setup_s})
    release(trainer)
    del trainer
    gc.collect()
    t_ref = time.perf_counter()
    with contextlib.redirect_stdout(sys.stderr):
        change_norms(prog, cell, seed)
        ref = reference_readings(cell, seed, prog["batches"])
    _say(f"[check] reference took {time.perf_counter() - t_ref:.1f}s")
    _say(f"[check] losses program {prog['losses']} reference {ref['losses']}")
    checks = _compare(prog, ref, cell.workload["limits"], cell)
    return harness.result_line(checks=checks, attempted=steps, failed=0,
                               metrics=metrics, device=device,
                               breakdown=breakdown)

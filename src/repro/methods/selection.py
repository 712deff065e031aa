"""The masked-selection method family: one generic step factory, many
policies.

``full`` / ``adagradselect`` / ``topk_grad`` / ``random`` / ``lisa`` /
``grass`` share this implementation — grads -> per-block norms -> in-jit
policy selection (core/adagradselect registry) -> block-masked AdamW. One
compiled program serves every selection outcome: masks are runtime inputs,
so per-step dynamic selection never recompiles.

Two optimizer-state residency layouts (``opt_cfg.moment_residency``):

* ``"device"`` (default, the trajectory oracle): one fused jitted step;
  ``state["opt"] = {"m", "v", "counts"}`` with full-shape f32 moments.
* ``"banked"`` (paper §3.3): ``state["opt"] = {"banks", "slot_map",
  "counts", "store"}`` — only selected blocks' moments are device-resident,
  in compact [k]-slot banks backed by a full store (host RAM under
  ``opt_cfg.offload == "host"``). The step is two compiled phases around a
  selection-change boundary: phase A (forward + backward + in-jit
  selection) yields the mask, the boundary streams evicted/admitted
  blocks' moments store<->banks, phase B applies the banked AdamW on bank
  rows (fused slot-indexed Pallas path included). Under
  ``opt_cfg.async_swap`` (default) the boundary is overlapped: a
  ``core.swap.SwapPlanner`` prefetches the *predicted* next admit set and
  writes predicted evictions back in a background thread while phase B
  runs, so a correct prediction leaves only the bank commit on the
  critical path and a miss falls back to the synchronous swap
  (``step_fn.swap_stats.predicted_hit_rate``). Both phases compile exactly
  once — bank slots and selected indices are runtime vectors of static
  shape, identical with the async bit on or off.

With ``model_cfg.gate_weight_grads`` the mask is decided BEFORE backward
from the policy's cumulative signal and frozen blocks' weight grads are
lax.cond-gated away (DESIGN 3.3); the observed norms are then fed back via
``adagradselect.observe``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import (ModelConfig, OptimizerConfig, SelectConfig,
                                TrainConfig)
from repro.core import (adagradselect, masked_adamw, offload,
                        partition as part_mod, swap as swap_mod)
from repro.core.offload import optimizer_memory_report
from repro.methods import registry
from repro.methods.base import TrainableReport
from repro.models import registry as model_registry
from repro.optim.schedules import learning_rate
from repro.train import step as step_mod


def _constrain(tree, shardings):
    """Pin a traced pytree to its sharding tree inside jit (maxtext-style
    output constraints): GSPMD otherwise *infers* output layouts, and a
    layout that differs from the input's would make the next step's call
    signature — and therefore a recompile — depend on the previous step.
    ``HOST_RESIDENT`` markers (and any non-Sharding entry) pass through."""
    if shardings is None:
        return tree
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s)
        if isinstance(s, jax.sharding.Sharding) else x,
        tree, shardings)


def _kernel_layout(mesh, state_shardings, use_pallas, *path):
    """``(mesh, specs)`` the Pallas kernels run in: each runs per shard of
    the state at ``state_shardings[path[0]][path[1]]...``, laid out as
    ``specs``. ``(None, None)`` when the kernels are off or there is no
    mesh; ``specs`` is None when the state has no sharding tree."""
    if not use_pallas or mesh is None:
        return None, None
    if state_shardings is None:
        return mesh, None
    tree = state_shardings
    for key in path:
        tree = tree[key]
    return mesh, jax.tree.map(lambda s: s.spec, tree)


@dataclasses.dataclass(frozen=True)
class SelectionMethod:
    """FinetuneMethod for block-masked fine-tuning under one policy."""

    name: str
    sel_cfg: SelectConfig

    # -------------------------------------------------------------- state
    def slot_capacity(self, model_cfg: ModelConfig) -> int:
        """Static bank-slot / selected-index capacity: the policy's k plus
        any always-include blocks, capped at num_blocks."""
        nb = model_cfg.num_blocks
        return min(nb, self.sel_cfg.num_selected(nb)
                   + len(self.sel_cfg.always_include))

    def init_state(self, model_cfg: ModelConfig, opt_cfg: OptimizerConfig,
                   seed: int = 0, mesh=None) -> dict:
        return step_mod.init_train_state(
            model_cfg, seed, moment_dtype=jnp.dtype(opt_cfg.moment_dtype),
            policy=self.sel_cfg.policy,
            select_k=self.slot_capacity(model_cfg),
            moment_residency=opt_cfg.moment_residency,
            store_policy=opt_cfg.offload, mesh=mesh)

    # ---------------------------------------------------------- sharding
    def state_shardings(self, model_cfg: ModelConfig,
                        opt_cfg: OptimizerConfig, state: dict, mesh) -> dict:
        """Sharding tree congruent with ``init_state``'s TrainState for
        data-parallel (or DP x TP) training on ``mesh``.

        Params follow ``distributed.sharding.param_specs`` (replicated on a
        pure-DP mesh, TP-sharded where the model axis is >1). Dense moments
        follow the params' specs, additionally ZeRO-1-sharded over ``data``
        under ``offload == "zero1"``. Banked residency keeps the compact
        [k]-slot banks replicated (they are the working set every device
        updates) while the full store shards 1/dp over ``data`` under
        ``offload == "zero1"``. Host-resident leaves (``slot_map``, a
        ``"host"``-policy store) carry the ``HOST_RESIDENT`` marker instead
        of a sharding — they are numpy, never device_put.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.distributed import sharding as shard_rules

        rep = NamedSharding(mesh, P())
        replicate = lambda tree: jax.tree.map(lambda _: rep, tree)  # noqa: E731
        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        canon = lambda specs: jax.tree.map(  # noqa: E731
            lambda s: shard_rules.mesh_canonical_spec(s, mesh), specs,
            is_leaf=is_spec)
        as_shardings = lambda specs: jax.tree.map(  # noqa: E731
            lambda s: NamedSharding(mesh, s), specs, is_leaf=is_spec)
        # canonical specs (no trailing Nones / size-1 axes) so step outputs
        # pinned with with_sharding_constraint compare equal to the initial
        # device_put and every compiled phase stays compile-once
        p_specs = canon(shard_rules.param_specs(model_cfg, state["params"],
                                                mesh))
        p_shard = as_shardings(p_specs)
        out = {"params": p_shard, "sel": replicate(state["sel"]),
               "step": rep}
        opt = state["opt"]
        if opt_cfg.moment_residency == "device":
            if (opt_cfg.offload == "host"
                    and offload.host_memory_kind_supported()):
                # pinned_host memory kinds (TPU/GPU only)
                m_shard = offload.moment_shardings(
                    "host", p_specs, mesh, params_shapes=state["params"])
            else:
                m_specs = p_specs
                if opt_cfg.offload == "zero1":
                    m_specs = canon(shard_rules.apply_zero1(
                        p_specs, state["params"], mesh))
                m_shard = as_shardings(m_specs)
            out["opt"] = {"m": m_shard, "v": m_shard, "counts": rep}
        else:
            partition = part_mod.build_partition(model_cfg)
            opt_sh = {"banks": replicate(opt["banks"]),
                      "slot_map": shard_rules.HOST_RESIDENT,
                      "counts": rep}
            if "store" in opt:
                if opt_cfg.offload == "host":
                    opt_sh["store"] = jax.tree.map(
                        lambda _: shard_rules.HOST_RESIDENT, opt["store"])
                elif opt_cfg.offload == "zero1":
                    opt_sh["store"] = as_shardings(canon(
                        shard_rules.store_specs(partition, opt["store"],
                                                mesh)))
                else:
                    opt_sh["store"] = replicate(opt["store"])
            out["opt"] = opt_sh
        return out

    # --------------------------------------------------------------- step
    def make_step(self, model_cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                  mesh=None, batch_axes=("data",), donate: bool = True,
                  state_shardings=None):
        """-> ``(state, batch) -> (state, metrics)``.

        Dense residency: one jitted function. Banked residency: a Python
        driver around two jitted phases (exposed as ``.forward_select`` /
        ``.apply`` attributes) with the host-side moment swap in between.

        With ``state_shardings`` (Trainer passes the ``state_shardings()``
        tree when it runs on a mesh) every compiled phase pins its state
        outputs to the same layout it consumes, so step N+1 sees exactly the
        shardings step N produced and each phase keeps the compile-once
        guarantee under data parallelism. The batch arrives sharded over the
        data axis; because the loss is a global mean inside one jitted
        (GSPMD) program, gradients are mean-reduced over ``data`` *before*
        the in-jit selection — every device sees identical block norms and
        picks identical blocks by construction.

        Block grad-norm and (banked) masked AdamW run as Pallas kernels
        exactly when ``kernels.ops.use_kernels(model_cfg)`` says so — the
        same switch that picks the paged decode kernel when serving. On a
        mesh they run per shard, in the layout of the state they touch.
        """
        from repro.kernels import ops as kops
        use_pallas = kops.use_kernels(model_cfg)
        kmesh, p_specs = _kernel_layout(mesh, state_shardings, use_pallas,
                                        "params")
        sel_cfg = self.sel_cfg
        model = model_registry.get(model_cfg)
        partition = part_mod.build_partition(model_cfg)
        gate = model_cfg.gate_weight_grads

        def forward_select(params, sel_state, batch):
            """Shared phase A: loss, clipped grads, per-block norms, and the
            in-jit policy selection (traced into the fused dense step and
            compiled standalone for the banked step)."""
            # gate mode decides the mask BEFORE backward (cumulative signal)
            pre_mask = None
            if gate:
                pre_mask, sel_state = adagradselect.select(
                    sel_cfg, sel_state,
                    jnp.zeros((partition.num_blocks,), jnp.float32),
                    partition.num_blocks)

            def loss_fn(p, mb):
                masks = (part_mod.layer_masks_dict(partition, pre_mask)
                         if gate else None)
                return step_mod.model_loss(model, model_cfg, p, mb,
                                           mesh=mesh, batch_axes=batch_axes,
                                           masks=masks)

            (loss, metrics), grads = step_mod.accumulate_grads(
                loss_fn, params, batch, opt_cfg.microbatch,
                jnp.dtype(opt_cfg.accum_dtype))

            grads, gnorm = masked_adamw.clip_by_global_norm(
                grads, opt_cfg.grad_clip)
            block_norms = part_mod.block_grad_norms(
                partition, grads, use_pallas=use_pallas, mesh=kmesh,
                specs=p_specs)
            if gate:
                mask = pre_mask
                # observe norms post-hoc (only computed blocks contribute)
                sel_state = adagradselect.observe(sel_cfg, sel_state,
                                                  block_norms)
            else:
                mask, sel_state = adagradselect.select(
                    sel_cfg, sel_state, block_norms, partition.num_blocks)
            return grads, mask, sel_state, loss, metrics, gnorm, block_norms

        def step_metrics(metrics, loss, gnorm, lr, mask, block_norms, step):
            return {**metrics, "loss": loss, "grad_norm": gnorm, "lr": lr,
                    "epsilon": adagradselect.epsilon(sel_cfg, step),
                    "num_selected": jnp.sum(mask.astype(jnp.int32)),
                    "mask": mask, "block_norms": block_norms}

        if opt_cfg.moment_residency == "banked":
            return self._make_banked_step(
                opt_cfg, partition, forward_select, step_metrics,
                use_pallas=use_pallas, donate=donate,
                state_shardings=state_shardings, mesh=mesh)
        if opt_cfg.moment_residency != "device":
            raise ValueError(
                f"unknown moment_residency {opt_cfg.moment_residency!r}")
        _, m_specs = _kernel_layout(mesh, state_shardings, use_pallas,
                                    "opt", "m")

        def step_fn(state, batch):
            grads, mask, sel_state, loss, metrics, gnorm, block_norms = \
                forward_select(state["params"], state["sel"], batch)
            lr = learning_rate(opt_cfg, state["step"])
            params, opt = masked_adamw.update(
                opt_cfg, partition, state["params"], grads, state["opt"],
                mask, lr, use_pallas=use_pallas, mesh=kmesh, specs=m_specs)
            new_state = {"params": params, "opt": opt, "sel": sel_state,
                         "step": state["step"] + 1}
            new_state = _constrain(new_state, state_shardings)
            return new_state, step_metrics(metrics, loss, gnorm, lr, mask,
                                           block_norms, state["step"])

        return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    def _make_banked_step(self, opt_cfg, partition, forward_select,
                          step_metrics, *, use_pallas, donate,
                          state_shardings=None, mesh=None):
        shd = state_shardings
        kmesh, p_specs = _kernel_layout(mesh, shd, use_pallas, "params")

        def fwd_fn(params, sel_state, batch):
            out = forward_select(params, sel_state, batch)
            grads, mask, sel_state, loss, metrics, gnorm, block_norms = out
            if shd is not None:
                grads = _constrain(grads, shd["params"])
                sel_state = _constrain(sel_state, shd["sel"])
            return grads, mask, sel_state, loss, metrics, gnorm, block_norms

        fwd = jax.jit(fwd_fn)

        # zero1/none stores re-place through their sharding tree after a
        # checkpoint restore; "host" stores carry markers, not shardings
        store_sh = None
        if shd is not None and isinstance(shd["opt"].get("store"), dict):
            leaves = jax.tree.leaves(shd["opt"]["store"])
            if leaves and isinstance(leaves[0], jax.sharding.Sharding):
                store_sh = shd["opt"]["store"]

        def apply_fn(params, grads, banks, counts, mask, step):
            lr = learning_rate(opt_cfg, step)
            params, banks, counts = masked_adamw.banked_update(
                opt_cfg, partition, params, grads, banks, counts, mask, lr,
                use_pallas=use_pallas, mesh=kmesh, specs=p_specs)
            if shd is not None:
                params = _constrain(params, shd["params"])
                banks = _constrain(banks, shd["opt"]["banks"])
                counts = _constrain(counts, shd["opt"]["counts"])
            return params, banks, counts, lr

        # params/banks/counts are replaced 1:1 -> donate; grads have no
        # same-shaped output (moments are compact), donating them only warns
        apply = jax.jit(apply_fn,
                        donate_argnums=(0, 2, 3) if donate else ())

        nb = partition.num_blocks
        planner = swap_mod.SwapPlanner(
            partition, self.sel_cfg, nb, enabled=opt_cfg.async_swap,
            # sharded store/bank reads carry collectives: keep the boundary
            # job on this thread so its enqueue order can't interleave with
            # phase B's (see SwapPlanner.__init__)
            inline=mesh is not None and mesh.devices.size > 1)
        stats = planner.stats

        def step_fn(state, batch):
            # phase timing goes through obs.timed — one measurement feeds
            # both the SwapStats histograms (the bench JSON fields are views
            # over them) and, when tracing is on, the phase_a/swap/phase_b
            # spans of the Perfetto timeline
            with obs.timed(stats.phase_a, "phase_a"):
                grads, mask, sel_state, loss, metrics, gnorm, block_norms = \
                    fwd(state["params"], state["sel"], batch)
                # selection-change boundary: stream moments store<->banks.
                # The policy's static-shape [k] indices vector is the one
                # host sync the paper's design pays (k ids, not a
                # [num_blocks] mask).
                idx = np.asarray(sel_state["indices"])
            opt = state["opt"]
            with obs.timed(stats.swap, "swap"):
                store = offload.ensure_store_residency(opt["store"],
                                                       opt_cfg.offload,
                                                       shardings=store_sh)
                # joins any in-flight dispatch; a prediction hit leaves only
                # the commit (a few async scatters) on the critical path, a
                # miss falls back to the synchronous swap (counted in stats)
                banks, slot_map, store = planner.resolve(
                    idx, opt["banks"], store, opt["slot_map"])
            with obs.timed(stats.phase_b, "phase_b"):
                params, banks, counts, lr = apply(
                    state["params"], grads, banks, opt["counts"], mask,
                    state["step"])
                # phase B is in flight: predict step t+1's selection and
                # stage its boundary in the background (device reads inside
                # the job block on apply's outputs there, not here)
                planner.dispatch(sel_state, banks, store, slot_map)
            stats.steps += 1
            new_state = {"params": params,
                         "opt": {"banks": banks, "slot_map": slot_map,
                                 "counts": counts, "store": store},
                         "sel": sel_state, "step": state["step"] + 1}
            return new_state, step_metrics(metrics, loss, gnorm, lr, mask,
                                           block_norms, state["step"])

        # expose the compiled phases (dry-run lowering, recompile tests)
        # and the planner (trainer quiesce hooks, bench stats)
        step_fn.forward_select = fwd
        step_fn.apply = apply
        step_fn.swap_planner = planner
        step_fn.swap_stats = stats
        return step_fn

    # --------------------------------------------------------------- eval
    def eval_params(self, model_cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    state: dict) -> dict:
        return state["params"]

    # ------------------------------------------------------------- report
    def trainable_param_report(self, model_cfg: ModelConfig,
                               state: dict) -> TrainableReport:
        partition = part_mod.build_partition(model_cfg)
        rep = optimizer_memory_report(partition, state["params"],
                                      self.sel_cfg.k_percent,
                                      opt_state=state["opt"])
        k = self.sel_cfg.num_selected(partition.num_blocks)
        return TrainableReport(
            method=self.name, num_params_total=rep.p_total,
            num_params_trainable=rep.p_selected, opt_bytes=rep.mem_selective,
            opt_bytes_resident=rep.mem_measured_device,
            detail=f"policy={self.sel_cfg.policy} "
                   f"k={self.sel_cfg.k_percent:.0f}% "
                   f"({k}/{partition.num_blocks} blocks/step) "
                   f"resident={rep.mem_measured_device}B "
                   f"host={rep.mem_measured_host}B")


def _selection_factory(policy: str, name: str | None = None, **overrides):
    def factory(tcfg: TrainConfig) -> SelectionMethod:
        sel = dataclasses.replace(tcfg.select, policy=policy, **overrides)
        return SelectionMethod(name=name or policy, sel_cfg=sel)
    return factory


# full FT selects every block every step; k=100% makes the memory/trainable
# accounting agree with that.
registry.register("full", "all")(
    _selection_factory("all", name="full", k_percent=100.0))
registry.register("adagradselect")(_selection_factory("adagradselect"))
registry.register("topk_grad")(_selection_factory("topk_grad"))
registry.register("random")(_selection_factory("random"))
registry.register("lisa")(_selection_factory("lisa"))
registry.register("grass")(_selection_factory("grass"))

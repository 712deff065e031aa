"""LoRA as a registered FinetuneMethod (paper §4.2 baseline).

Rank-r adapters on the attention/MLP projections, trained with standard
AdamW while the base weights stay frozen (merge-on-forward, see
optim/lora.py). state = {"base", "lora", "opt", "step"}.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, OptimizerConfig
from repro.core import masked_adamw
from repro.methods import registry
from repro.methods.base import TrainableReport
from repro.models import registry as model_registry
from repro.optim import adamw as plain_adamw
from repro.optim import lora as lora_mod
from repro.optim.schedules import learning_rate
from repro.train import step as step_mod


class LoRAMethod:
    """FinetuneMethod: adapter-only training, frozen base."""

    name = "lora"

    # -------------------------------------------------------------- state
    def init_state(self, model_cfg: ModelConfig, opt_cfg: OptimizerConfig,
                   seed: int = 0, mesh=None) -> dict:
        # mesh accepted per the FinetuneMethod protocol; LoRA state is tiny
        # (adapters + their moments) and stays replicated under DP
        model = model_registry.get(model_cfg)
        base = model.init(jax.random.PRNGKey(seed), model_cfg)
        lora_p = lora_mod.init_lora(jax.random.PRNGKey(seed + 1), base,
                                    model_cfg, opt_cfg.lora_rank)
        return {"base": base, "lora": lora_p,
                "opt": plain_adamw.init_opt_state(lora_p),
                "step": jnp.zeros((), jnp.int32)}

    # --------------------------------------------------------------- step
    def make_step(self, model_cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                  mesh=None, batch_axes=("data",), donate: bool = True,
                  state_shardings=None):
        model = model_registry.get(model_cfg)
        rank, alpha = opt_cfg.lora_rank, opt_cfg.lora_alpha

        def step_fn(state, batch):
            def loss_fn(lp, mb):
                merged = lora_mod.merge(state["base"], lp, model_cfg, rank,
                                        alpha)
                return step_mod.model_loss(model, model_cfg, merged, mb,
                                           mesh=mesh, batch_axes=batch_axes)

            (loss, metrics), grads = step_mod.accumulate_grads(
                loss_fn, state["lora"], batch, opt_cfg.microbatch)
            grads, gnorm = masked_adamw.clip_by_global_norm(
                grads, opt_cfg.grad_clip)
            lr = learning_rate(opt_cfg, state["step"])
            lora_p, opt = plain_adamw.update(opt_cfg, state["lora"], grads,
                                             state["opt"], lr)
            new_state = {"base": state["base"], "lora": lora_p, "opt": opt,
                         "step": state["step"] + 1}
            metrics = {**metrics, "loss": loss, "grad_norm": gnorm, "lr": lr}
            return new_state, metrics

        return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    # --------------------------------------------------------------- eval
    def eval_params(self, model_cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    state: dict) -> dict:
        return lora_mod.merge(state["base"], state["lora"], model_cfg,
                              opt_cfg.lora_rank, opt_cfg.lora_alpha)

    # ------------------------------------------------------------- report
    def trainable_param_report(self, model_cfg: ModelConfig,
                               state: dict) -> TrainableReport:
        from repro.core.offload import resident_opt_bytes
        total = sum(int(jnp.size(x)) for x in jax.tree.leaves(state["base"]))
        n_lora = lora_mod.num_lora_params(state["lora"])
        return TrainableReport(
            method=self.name, num_params_total=total,
            num_params_trainable=n_lora,
            opt_bytes=2 * n_lora * 4,  # f32 m + v on adapters only
            opt_bytes_resident=resident_opt_bytes(state["opt"])["device"],
            detail=f"adapters on {len(state['lora'])} leaf groups")


registry.register("lora")(lambda tcfg: LoRAMethod())

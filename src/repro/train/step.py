"""Method-agnostic training-step building blocks.

This module owns the pieces every fine-tuning method shares: the masked
next-token loss, microbatch gradient accumulation (``accumulate_grads``
scans over batch slices inside the step), and TrainState initialization /
shape inference for the masked-selection family. The per-method step
factories themselves live in ``repro.methods`` — ``methods/selection.py``
for the block-masked family (full / adagradselect / topk_grad / random /
lisa / grass) and ``methods/lora.py`` for LoRA; they are resolved through
the string-keyed registry in ``methods/registry.py``.

``make_train_step`` / ``make_lora_train_step`` / ``init_lora_state`` remain
as thin compatibility shims over the registry so existing callers and
checkpointed workflows keep working.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, OptimizerConfig, SelectConfig
from repro.core import adagradselect, masked_adamw, partition as part_mod
from repro.models import registry


# ----------------------------------------------------------------- loss


def next_token_loss(logits, tokens, loss_mask, shift: int = 1):
    """Masked CE: position t predicts token t+shift. Computed as
    gathered-logit minus logsumexp so no [B,S,V] f32 tensor is ever
    materialized (the f32 reduction fuses)."""
    if shift:
        logits = logits[:, :-shift]
        targets = tokens[:, shift:]
        mask = loss_mask[:, shift:]
    else:
        targets, mask = tokens, loss_mask
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ll = picked.astype(jnp.float32) - lse
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return -jnp.sum(ll * mask) / denom


def model_loss(model, cfg: ModelConfig, params, batch, *, mesh=None,
               batch_axes=("data",), masks=None):
    logits, aux, extra = model.apply_train(params, cfg, batch, mesh=mesh,
                                           batch_axes=batch_axes, masks=masks)
    loss = next_token_loss(logits, batch["tokens"], batch["loss_mask"])
    total = loss + aux
    if "mtp_logits" in extra:
        mtp = next_token_loss(extra["mtp_logits"], batch["tokens"],
                              batch["loss_mask"], shift=2)
        total = total + cfg.mtp_loss_weight * mtp
    return total, {"ce_loss": loss, "aux_loss": aux}


def accumulate_grads(loss_fn, params, batch, n_micro: int,
                     accum_dtype=jnp.float32):
    """Mean grads over microbatches via lax.scan (gradient accumulation)."""
    if n_micro <= 1:
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

    def resh(x):
        return x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:])

    micro = jax.tree.map(resh, batch)

    def body(carry, mb):
        acc, loss_acc, m_acc = carry
        (loss, met), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(jnp.add, acc, g)
        m_acc = jax.tree.map(jnp.add, m_acc, met)
        return (acc, loss_acc + loss, m_acc), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
    met0 = {"ce_loss": jnp.zeros((), jnp.float32),
            "aux_loss": jnp.zeros((), jnp.float32)}
    (gacc, loss, macc), _ = jax.lax.scan(
        body, (zeros, jnp.zeros((), jnp.float32), met0), micro)
    scale = 1.0 / n_micro
    grads = jax.tree.map(lambda g, p: (g * scale).astype(p.dtype), gacc, params)
    met = jax.tree.map(lambda m: m * scale, macc)
    return (loss * scale, met), grads


# ----------------------------------------------------------------- state


def init_train_state(model_cfg: ModelConfig, seed: int = 0,
                     moment_dtype=jnp.float32,
                     policy: str = "adagradselect",
                     select_k: int | None = None,
                     moment_residency: str = "device",
                     store_policy: str = "host",
                     mesh=None) -> dict:
    """TrainState for the masked-selection family: params + masked-AdamW
    moments + the policy's selection-state pytree.

    ``moment_residency == "device"`` (default): ``state["opt"]`` is the
    dense layout ``{"m", "v", "counts"}`` with full-shape moments.
    ``moment_residency == "banked"``: ``state["opt"]`` is the compact
    layout ``{"banks", "slot_map", "counts", "store"}`` — [k]-slot device
    moment banks over a full store placed per ``store_policy`` ("host" ->
    host RAM; "zero1" -> device, sharded 1/dp over ``mesh``'s data axis;
    see masked_adamw.init_banked_opt_state). ``select_k`` caps
    the slot count (and the selection state's static ``indices`` length);
    default: ``num_blocks``."""
    model = registry.get(model_cfg)
    partition = part_mod.build_partition(model_cfg)
    params = model.init(jax.random.PRNGKey(seed), model_cfg)
    if moment_residency == "banked":
        if store_policy == "zero1" and mesh is None:
            # an UNSHARDED device store on top of the banks would be
            # strictly worse than dense zero1 — the sharded layout needs a
            # mesh to place its 1/dp shards, so reject instead of degrading
            raise ValueError(
                "moment_residency='banked' with offload='zero1' requires a "
                "mesh (the full store is sharded 1/dp over the data axis); "
                "pass Trainer(..., mesh=...) / launch.train --mesh, use "
                "offload='host' for the paper's host-resident store, or "
                "moment_residency='device' to keep dense ZeRO-1 moments")
        store = {"host": "host", "zero1": "zero1"}.get(store_policy, "device")
        k = select_k if select_k is not None else partition.num_blocks
        opt = masked_adamw.init_banked_opt_state(
            partition, params, k, moment_dtype, store_policy=store,
            mesh=mesh)
    elif moment_residency == "device":
        opt = masked_adamw.init_opt_state(partition, params, moment_dtype)
    else:
        raise ValueError(f"unknown moment_residency {moment_residency!r}; "
                         f"expected 'device' or 'banked'")
    return {
        "params": params,
        "opt": opt,
        "sel": adagradselect.init_state(partition.num_blocks, seed,
                                        policy=policy, k=select_k),
        "step": jnp.zeros((), jnp.int32),
    }


def train_state_shapes(model_cfg: ModelConfig, seed: int = 0,
                       policy: str = "adagradselect"):
    return jax.eval_shape(partial(init_train_state, model_cfg, policy=policy),
                          seed)


# ----------------------------------------------- compatibility shims


def make_train_step(model_cfg: ModelConfig, sel_cfg: SelectConfig,
                    opt_cfg: OptimizerConfig, *, mesh=None,
                    batch_axes=("data",), donate: bool = True):
    """Shim -> methods/selection.py (kept for existing callers)."""
    from repro.methods.selection import SelectionMethod
    method = SelectionMethod(name=sel_cfg.policy, sel_cfg=sel_cfg)
    return method.make_step(model_cfg, opt_cfg, mesh=mesh,
                            batch_axes=batch_axes, donate=donate)


def make_lora_train_step(model_cfg: ModelConfig, opt_cfg: OptimizerConfig, *,
                         mesh=None, batch_axes=("data",), donate: bool = True):
    """Shim -> methods/lora.py (kept for existing callers)."""
    from repro.methods.lora import LoRAMethod
    return LoRAMethod().make_step(model_cfg, opt_cfg, mesh=mesh,
                                  batch_axes=batch_axes, donate=donate)


def init_lora_state(model_cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    seed: int = 0) -> dict:
    """Shim -> methods/lora.py (kept for existing callers)."""
    from repro.methods.lora import LoRAMethod
    return LoRAMethod().init_state(model_cfg, opt_cfg, seed)

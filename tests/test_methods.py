"""Fine-tuning method registry: resolution, smoke runs, golden-seed parity.

The GOLDEN table pins each method's 5-step trajectory on this exact tiny
config (losses, a parameter fingerprint and the final selection mask). It
was recaptured on 2026-10-16 under jax 0.9.0 (CPU), because parameter
initialization under the jax 0.9 PRNG differs from the jax 0.4 line the
previous table came from: every method's first loss moved from 3.947706 to
3.814510 before any update ran. The capture ran the same Trainer code path
on the commit before and after kernel bring-up and got identical values;
the comparison tolerance is unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import methods
from repro.configs.base import (ModelConfig, OptimizerConfig, SelectConfig,
                                TrainConfig)
from repro.train.trainer import Trainer

GOLDEN_MODEL = ModelConfig(
    name="golden-tiny", family="dense", num_layers=3, d_model=32, num_heads=2,
    num_kv_heads=2, head_dim=16, d_ff=64, vocab_size=32, dtype="float32",
    remat="none", tie_embeddings=False)

ALL_METHODS = ("full", "adagradselect", "topk_grad", "random", "lora",
               "lisa", "grass")

# 5 steps, seed 0, on GOLDEN_MODEL (see module docstring)
GOLDEN = {
    "adagradselect": {
        "losses": [3.81451, 3.474582, 3.010671, 2.880186, 2.712668],
        "fp": 4611.5908203125,
        "final_mask": [0, 1, 0, 0, 0, 1],
    },
    "topk_grad": {
        "losses": [3.81451, 3.474582, 3.008594, 2.856374, 2.746737],
        "fp": 4612.943359375,
        "final_mask": [0, 1, 0, 0, 0, 1],
    },
    "random": {
        "losses": [3.81451, 3.4462, 3.204596, 3.029714, 2.893373],
        "fp": 4607.29248046875,
        "final_mask": [0, 1, 0, 0, 0, 1],
    },
    "full": {
        "losses": [3.81451, 3.326064, 2.887514, 2.729954, 2.578287],
        "fp": 4636.58154296875,
        "final_mask": [1, 1, 1, 1, 1, 1],
    },
    "lora": {
        "losses": [3.81451, 3.559687, 3.07767, 3.021049, 2.884983],
        "fp": 489.8881530761719,
        "final_mask": None,
    },
}


def _tcfg(steps=5):
    return TrainConfig(
        model=GOLDEN_MODEL,
        select=SelectConfig(policy="adagradselect", k_percent=40,
                            steps_per_epoch=10, epsilon_decay=0.05),
        optimizer=OptimizerConfig(lr=1e-2, schedule="constant", warmup_steps=0,
                                  lora_rank=4),
        seq_len=48, global_batch=4, steps=steps, seed=0, log_every=0)


def _fingerprint(tree):
    return float(sum(jnp.sum(jnp.abs(leaf.astype(jnp.float32)))
                     for leaf in jax.tree.leaves(tree)))


# ------------------------------------------------------------- resolution


def test_registry_resolves_all_methods():
    for name in ALL_METHODS:
        assert methods.get_method(name) is not None, name
    assert "all" in methods.available()  # full-FT alias


def test_registry_unknown_method_raises_with_alternatives():
    with pytest.raises(KeyError, match="available"):
        methods.get_method("does_not_exist")


def test_built_methods_satisfy_protocol():
    tcfg = _tcfg()
    for name in ALL_METHODS:
        m = methods.build(name, tcfg)
        assert isinstance(m, methods.FinetuneMethod), name


def test_trainer_is_method_agnostic():
    """The trainer must never branch on the method name."""
    import inspect
    src = inspect.getsource(Trainer)
    assert "lora" not in src and 'method ==' not in src


# ------------------------------------------------------------- smoke runs


@pytest.mark.parametrize("name", ALL_METHODS)
def test_every_method_runs_three_steps_finite(name):
    tr = Trainer(_tcfg(3), method=name)
    log = tr.train()
    assert len(log.losses) == 3
    assert np.isfinite(log.losses).all(), (name, log.losses)
    params = tr.method.eval_params(GOLDEN_MODEL, tr.tcfg.optimizer, tr.state)
    assert all(np.isfinite(np.asarray(leaf, np.float32)).all()
               for leaf in jax.tree.leaves(params)), name


@pytest.mark.parametrize("name", ALL_METHODS)
def test_trainable_param_report(name):
    tr = Trainer(_tcfg(1), method=name)
    rep = tr.method.trainable_param_report(GOLDEN_MODEL, tr.state)
    assert rep.num_params_total > 0
    assert 0 < rep.num_params_trainable <= rep.num_params_total
    assert rep.opt_bytes > 0
    full = 0.0 if name in ("lora",) else rep.trainable_fraction
    if name == "full":
        assert rep.num_params_trainable == rep.num_params_total, full


def test_method_from_train_config_field():
    tr = Trainer(_tcfg().__class__(**{**_tcfg().__dict__, "method": "random"}))
    assert tr.method_name == "random"
    assert tr.sel_cfg.policy == "random"


# ---------------------------------------------------------- golden parity


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_golden_seed_parity(name):
    """The registry refactor must reproduce the pre-refactor trajectories."""
    golden = GOLDEN[name]
    tr = Trainer(_tcfg(5), method=name)
    log = tr.train()
    np.testing.assert_allclose(log.losses, golden["losses"],
                               rtol=0, atol=2e-6, err_msg=name)
    params = tr.state["params"] if name != "lora" else tr.state["lora"]
    np.testing.assert_allclose(_fingerprint(params), golden["fp"],
                               rtol=1e-6, err_msg=name)
    if golden["final_mask"] is not None:
        mask = np.asarray(tr.state["sel"]["mask"]).astype(int).tolist()
        assert mask == golden["final_mask"], name


def test_all_ones_mask_reduces_to_plain_adamw():
    """Training with the 'full' method must equal a hand-rolled loop on the
    reference (unmasked) AdamW — i.e. mask == all-ones keeps the masked
    optimizer on the plain-AdamW path end to end."""
    from repro.core import masked_adamw
    from repro.data import loader as data_loader
    from repro.models import registry as model_registry
    from repro.optim import adamw as plain_adamw
    from repro.optim.schedules import learning_rate
    from repro.train import step as step_mod

    tcfg = _tcfg(3)
    ocfg = tcfg.optimizer
    tr = Trainer(tcfg, method="full")
    tr.train()

    model = model_registry.get(GOLDEN_MODEL)
    params = model.init(jax.random.PRNGKey(tcfg.seed), GOLDEN_MODEL)
    opt = plain_adamw.init_opt_state(params)
    data = data_loader.make_source("synthetic_math", seq_len=tcfg.seq_len,
                                   global_batch=tcfg.global_batch,
                                   seed=tcfg.seed)

    def loss_fn(p, b):
        return step_mod.model_loss(model, GOLDEN_MODEL, p, b)

    for step in range(3):
        batch = data.batch_at(step)
        (_, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        grads, _ = masked_adamw.clip_by_global_norm(grads, ocfg.grad_clip)
        lr = learning_rate(ocfg, jnp.asarray(step))
        params, opt = plain_adamw.update(ocfg, params, grads, opt, lr)

    # atol covers jit-vs-eager fusion drift; exact masked==plain equality at
    # the update level is asserted in test_masked_adamw.py
    for a, b in zip(jax.tree.leaves(tr.state["params"]),
                    jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-4)


# ------------------------------------------------------- zero1 moment wiring


def test_moment_shardings_zero1_uses_concrete_shapes():
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import offload

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    shapes = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
    specs = {"w": P(None, "model")}
    sh = offload.moment_shardings("zero1", specs, mesh, params_shapes=shapes)
    assert sh["w"].spec == P("data", "model")
    with pytest.raises(ValueError, match="params_shapes"):
        offload.moment_shardings("zero1", specs, mesh)

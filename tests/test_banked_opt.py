"""Banked optimizer state ([k]-slot device moment banks + host-resident
full store) against the dense masked-AdamW oracle: trajectory exactness,
swap semantics, static shapes, residency accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (ModelConfig, OptimizerConfig, SelectConfig,
                                TrainConfig)
from repro.core import adagradselect, masked_adamw, offload
from repro.core import partition as pmod
from repro.models import registry
from repro.train.trainer import Trainer

TINY = ModelConfig(name="banked-tiny", family="dense", num_layers=4,
                   d_model=16, num_heads=2, num_kv_heads=2, head_dim=8,
                   d_ff=32, vocab_size=17, dtype="float32", remat="none",
                   tie_embeddings=False)

ALL_POLICIES = adagradselect.available_policies()


def _grads_like(params, step: int):
    """Deterministic synthetic grads that vary per step."""
    def one(path_seed, p):
        base = jnp.cos(1.0 * step + path_seed
                       + jnp.arange(p.size, dtype=jnp.float32))
        return (0.01 * base.reshape(p.shape)).astype(p.dtype)
    leaves, treedef = jax.tree.flatten(params)
    return jax.tree.unflatten(
        treedef, [one(float(i), p) for i, p in enumerate(leaves)])


def _sel_cfg(policy: str) -> SelectConfig:
    return SelectConfig(policy=policy, k_percent=40, steps_per_epoch=4,
                        epsilon_decay=0.1, lisa_interval=3,
                        always_include=(0,))


def _tcfg(residency: str, steps: int = 6, policy: str = "adagradselect",
          **opt_kw) -> TrainConfig:
    return TrainConfig(
        model=TINY,
        select=SelectConfig(policy=policy, k_percent=40, steps_per_epoch=10,
                            epsilon_decay=0.05),
        optimizer=OptimizerConfig(
            lr=1e-2, schedule="constant", warmup_steps=0,
            moment_residency=residency,
            offload="host" if residency == "banked" else "none", **opt_kw),
        seq_len=48, global_batch=4, steps=steps, seed=0, log_every=0)


# ----------------------------------------------------- oracle parity


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_banked_bit_exact_vs_dense_oracle(policy):
    """Multi-interval run: identical (grads, mask, lr) sequences through the
    banked layout and the dense ``masked_adamw.update`` oracle must give
    bit-exact params AND moments at every step — including across lisa
    interval boundaries and re-admission of previously evicted blocks."""
    part = pmod.build_partition(TINY)
    model = registry.get(TINY)
    params = model.init(jax.random.PRNGKey(0), TINY)
    sel_cfg = _sel_cfg(policy)
    nb = part.num_blocks
    cap = min(nb, sel_cfg.num_selected(nb) + len(sel_cfg.always_include))
    ocfg = OptimizerConfig(lr=1e-2, weight_decay=0.01)

    params_d, opt_d = params, masked_adamw.init_opt_state(part, params)
    params_b = params
    opt_b = masked_adamw.init_banked_opt_state(part, params, cap)
    sel_state = adagradselect.init_state(nb, seed=3, policy=policy, k=cap)

    for step in range(7):
        grads = _grads_like(params_b, step)
        norms = pmod.block_grad_norms(part, grads)
        mask, sel_state = adagradselect.select(sel_cfg, sel_state, norms, nb)
        assert sel_state["indices"].shape == (cap,)

        params_d, opt_d = masked_adamw.update(ocfg, part, params_d, grads,
                                              opt_d, mask, 1e-2)
        banks, slot_map, store = masked_adamw.swap_banked(
            part, opt_b["banks"], opt_b["store"], opt_b["slot_map"],
            np.asarray(mask))
        params_b, banks, counts = masked_adamw.banked_update(
            ocfg, part, params_b, grads, banks, opt_b["counts"], mask, 1e-2)
        opt_b = {"banks": banks, "slot_map": slot_map, "counts": counts,
                 "store": store}

        for a, b in zip(jax.tree.leaves(params_d), jax.tree.leaves(params_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        m_full, v_full = masked_adamw.materialize_moments(part, opt_b)
        for a, b in zip(jax.tree.leaves(opt_d["m"]), jax.tree.leaves(m_full)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(opt_d["v"]), jax.tree.leaves(v_full)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(opt_d["counts"]),
                                      np.asarray(opt_b["counts"]))


def test_banked_trainer_matches_dense_trainer():
    """End-to-end: the banked two-phase step reproduces the fused dense
    step's trajectory through the real Trainer."""
    t_dense = Trainer(_tcfg("device"), method="adagradselect")
    t_bank = Trainer(_tcfg("banked"), method="adagradselect")
    ld, lb = t_dense.train(), t_bank.train()
    np.testing.assert_allclose(ld.losses, lb.losses, rtol=0, atol=2e-6)
    for a, b in zip(jax.tree.leaves(t_dense.state["params"]),
                    jax.tree.leaves(t_bank.state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    part = pmod.build_partition(TINY)
    m_full, _ = masked_adamw.materialize_moments(part, t_bank.state["opt"])
    for a, b in zip(jax.tree.leaves(t_dense.state["opt"]["m"]),
                    jax.tree.leaves(m_full)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_async_swap_trajectory_bit_exact(policy):
    """The overlapped boundary may never change the trajectory. For every
    registered policy: banked + async streaming == banked synchronous ==
    the dense trainer, bit for bit — a prediction hit commits exactly the
    rows the synchronous path would have staged, and a miss falls back to
    that path. Also pins the planner's accounting: async-on dispatches,
    async-off never does."""
    t_dense = Trainer(_tcfg("device", policy=policy), method=policy)
    t_sync = Trainer(_tcfg("banked", policy=policy, async_swap=False),
                     method=policy)
    t_async = Trainer(_tcfg("banked", policy=policy, async_swap=True),
                      method=policy)
    ld, ls, la = t_dense.train(), t_sync.train(), t_async.train()
    np.testing.assert_array_equal(ld.losses, la.losses)
    np.testing.assert_array_equal(ls.losses, la.losses)
    for a, b in zip(jax.tree.leaves(t_sync.state["params"]),
                    jax.tree.leaves(t_async.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(t_dense.state["params"]),
                    jax.tree.leaves(t_async.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    part = pmod.build_partition(TINY)
    m_sync, v_sync = masked_adamw.materialize_moments(part,
                                                      t_sync.state["opt"])
    m_async, v_async = masked_adamw.materialize_moments(part,
                                                        t_async.state["opt"])
    for a, b in zip(jax.tree.leaves((m_sync, v_sync)),
                    jax.tree.leaves((m_async, v_async))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    on, off = t_async.step_fn.swap_stats, t_sync.step_fn.swap_stats
    assert on.dispatches > 0
    assert off.dispatches == 0 and off.predicted_hits == 0
    # the overlapped driver still compiles each phase exactly once
    assert t_async.step_fn.forward_select._cache_size() == 1
    assert t_async.step_fn.apply._cache_size() == 1


@pytest.mark.parametrize("residency", ["device", "banked"])
def test_kernel_switch_trains_like_xla_path(residency):
    """ModelConfig.use_pallas is the one switch: 'always' runs block
    grad-norm and (banked) masked AdamW as kernels inside the trainer's
    step and follows the XLA ('never') trajectory."""
    runs = {}
    for mode in ("never", "always"):
        tcfg = _tcfg(residency, steps=4)
        tcfg = tcfg.__class__(**{**tcfg.__dict__,
                                 "model": TINY.replace(use_pallas=mode)})
        tr = Trainer(tcfg)
        runs[mode] = (tr.train().losses, tr.state["params"])
    np.testing.assert_allclose(runs["always"][0], runs["never"][0],
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(runs["always"][1]),
                    jax.tree.leaves(runs["never"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_banked_pallas_path_matches_dense_pallas():
    """Fused Pallas kernel on bank rows == dense Pallas on full leaves."""
    part = pmod.build_partition(TINY)
    model = registry.get(TINY)
    params = model.init(jax.random.PRNGKey(1), TINY)
    ocfg = OptimizerConfig(lr=1e-2)
    nb, cap = part.num_blocks, 3
    mask = jnp.zeros((nb,), jnp.bool_).at[jnp.array([1, 2, 4])].set(True)

    params_d, opt_d = masked_adamw.update(
        ocfg, part, params, _grads_like(params, 0),
        masked_adamw.init_opt_state(part, params), mask, 1e-2,
        use_pallas=True)
    opt_b = masked_adamw.init_banked_opt_state(part, params, cap)
    banks, slot_map, store = masked_adamw.swap_banked(
        part, opt_b["banks"], opt_b["store"], opt_b["slot_map"],
        np.asarray(mask))
    params_b, banks, counts = masked_adamw.banked_update(
        ocfg, part, params, _grads_like(params, 0), banks, opt_b["counts"],
        mask, 1e-2, use_pallas=True)
    for a, b in zip(jax.tree.leaves(params_d), jax.tree.leaves(params_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


# ----------------------------------------------------- swap semantics


def test_swap_zero_init_and_eviction_writeback():
    part = pmod.build_partition(TINY)
    model = registry.get(TINY)
    params = model.init(jax.random.PRNGKey(0), TINY)
    ocfg = OptimizerConfig(lr=1e-2)
    nb = part.num_blocks
    opt = masked_adamw.init_banked_opt_state(part, params, 2)
    assert (opt["slot_map"] == -1).all()  # nothing resident initially

    # step 1: blocks 1 and 2 selected — admitted with zero moments
    mask1 = np.zeros((nb,), bool)
    mask1[[1, 2]] = True
    banks, slot_map, store = masked_adamw.swap_banked(
        part, opt["banks"], opt["store"], opt["slot_map"], mask1)
    g = part.group("layers")
    assert set(slot_map[[1, 2]]) == {0, 1} and (slot_map[[0, 3]] == -1).all()
    p2, banks, counts = masked_adamw.banked_update(
        ocfg, part, params, _grads_like(params, 0), banks, opt["counts"],
        jnp.asarray(mask1), 1e-2)
    leaf = jax.tree.leaves(banks["layers"]["m"])[0]
    assert np.abs(np.asarray(leaf)).sum() > 0  # moments were written

    # step 2: block 1 evicted (moments go back to the store bit-exact),
    # block 3 admitted (zero rows — first selection)
    mask2 = np.zeros((nb,), bool)
    mask2[[2, 3]] = True
    m_before, _ = masked_adamw.materialize_moments(
        part, {"banks": banks, "store": store, "slot_map": slot_map})
    banks2, slot_map2, store2 = masked_adamw.swap_banked(
        part, banks, store, slot_map, mask2)
    assert slot_map2[1] == -1 and slot_map2[3] >= 0
    b1 = 1 - g.start  # local index of block 1 in the layers group
    for st_leaf, m_leaf in zip(jax.tree.leaves(store2["layers"]["m"]),
                               jax.tree.leaves(m_before["layers"])):
        np.testing.assert_array_equal(np.asarray(st_leaf)[b1],
                                      np.asarray(m_leaf)[b1])
    slots2 = np.asarray(banks2["layers"]["slots"])
    s3 = int(np.nonzero(slots2 == (3 - g.start))[0][0])
    for bl in jax.tree.leaves(banks2["layers"]["m"]):
        assert (np.asarray(bl)[s3] == 0).all()  # zero-init on first selection

    # unchanged mask within an interval: swap is a no-op
    banks3, slot_map3, _ = masked_adamw.swap_banked(
        part, banks2, store2, slot_map2, mask2)
    np.testing.assert_array_equal(slot_map3, slot_map2)
    assert banks3["layers"] is banks2["layers"]


def test_swap_overflow_raises():
    part = pmod.build_partition(TINY)
    model = registry.get(TINY)
    params = model.init(jax.random.PRNGKey(0), TINY)
    opt = masked_adamw.init_banked_opt_state(part, params, 1)  # 1 slot
    mask = np.zeros((part.num_blocks,), bool)
    mask[[1, 2]] = True  # two layer blocks into one slot
    with pytest.raises(RuntimeError, match="bank overflow"):
        masked_adamw.swap_banked(part, opt["banks"], opt["store"],
                                 opt["slot_map"], mask)


# ----------------------------------------------------- static shapes


def test_banked_step_compiles_once_across_selection_changes():
    """Per-step selection (random policy redraws every step) must never
    recompile either banked phase: masks/slots are runtime vectors."""
    tr = Trainer(_tcfg("banked", steps=5, policy="random"), method="random")
    tr.train()
    fwd, apply = tr.step_fn.forward_select, tr.step_fn.apply
    if hasattr(fwd, "_cache_size"):
        assert fwd._cache_size() == 1
        assert apply._cache_size() == 1


def test_selected_indices_static_shape_and_padding():
    mask = jnp.array([True, False, True, False, False, True])
    idx = adagradselect.selected_indices(mask, 4)
    assert idx.shape == (4,)
    assert idx.tolist() == [0, 2, 5, 6]  # padded with num_blocks


# ----------------------------------------------------- residency accounting


def test_banked_resident_bytes_under_half_of_full():
    """Acceptance criterion: k~1/3 of blocks -> measured device-resident
    optimizer bytes <= 50% of the full-FT dense baseline."""
    deep = TINY.replace(num_layers=12, tie_embeddings=True)  # 14 blocks
    tcfg = TrainConfig(
        model=deep, select=SelectConfig(k_percent=33.0),
        optimizer=OptimizerConfig(moment_residency="banked", offload="host"),
        seq_len=32, global_batch=2, steps=1, log_every=0)
    from repro import methods
    banked_state = methods.build("adagradselect", tcfg).init_state(
        deep, tcfg.optimizer)
    dense_opt = masked_adamw.init_opt_state(
        pmod.build_partition(deep),
        banked_state["params"])
    banked = offload.resident_opt_bytes(banked_state["opt"])
    dense = offload.resident_opt_bytes(dense_opt)
    assert banked["device"] <= 0.5 * dense["device"], (banked, dense)
    assert banked["host"] > 0  # the full store lives in host RAM


def test_banked_rejects_zero1_store_without_mesh():
    """Without a mesh there is nothing to shard the store over — an
    unsharded device store on top of the banks would be strictly worse than
    dense ZeRO-1, so init still rejects (with the mesh hint). With a mesh
    the store shards 1/dp instead: tests/test_sharded_train.py."""
    from repro.train import step as step_mod
    with pytest.raises(ValueError, match="zero1.*mesh|mesh.*zero1"):
        step_mod.init_train_state(TINY, moment_residency="banked",
                                  store_policy="zero1")


def test_ensure_store_residency_after_restore_roundtrip():
    """Checkpoint restore materializes every leaf as numpy; the step must
    re-place a device-resident store back on device (and leave a host
    store alone)."""
    part = pmod.build_partition(TINY)
    model = registry.get(TINY)
    params = model.init(jax.random.PRNGKey(0), TINY)
    store_dev = offload.init_full_store(part, params, policy="device")
    as_restored = jax.tree.map(np.asarray, store_dev)  # all-numpy
    back = offload.ensure_store_residency(as_restored, "none")
    assert not isinstance(jax.tree.leaves(back)[0], np.ndarray)
    store_host = offload.init_full_store(part, params, policy="host")
    same = offload.ensure_store_residency(store_host, "host")
    assert jax.tree.leaves(same)[0] is jax.tree.leaves(store_host)[0]


def test_trainable_report_resident_column():
    t_dense = Trainer(_tcfg("device", steps=1), method="adagradselect")
    t_bank = Trainer(_tcfg("banked", steps=1), method="adagradselect")
    rd = t_dense.method.trainable_param_report(TINY, t_dense.state)
    rb = t_bank.method.trainable_param_report(TINY, t_bank.state)
    assert rb.opt_bytes_resident < rd.opt_bytes_resident
    assert rd.opt_bytes == rb.opt_bytes  # §3.3 model unchanged by residency


# ----------------------------------------------------- trainer log fix


def test_trainlog_lists_stay_aligned_on_midwindow_exit():
    """steps/losses/step_times extend atomically at sync boundaries, so an
    exception mid-window cannot leave the lists misaligned."""
    tr = Trainer(_tcfg("device", steps=10), method="random")
    tr.tcfg = tr.tcfg.__class__(**{**tr.tcfg.__dict__, "log_every": 4})
    real_step, calls = tr.step_fn, []

    def exploding(state, batch):
        calls.append(1)
        if len(calls) == 6:
            raise RuntimeError("boom")
        return real_step(state, batch)

    tr.step_fn = exploding
    with pytest.raises(RuntimeError, match="boom"):
        tr.train(steps=10)
    assert len(tr.log.steps) == len(tr.log.losses) == len(tr.log.step_times)

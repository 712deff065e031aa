"""Async swap planner + the boundary primitives it composes.

Four layers:

* property tests for the OOB-sentinel contracts the fused kernel and the
  swap lean on — ``gather_rows`` fills out-of-range slots with zeros,
  ``scatter_rows`` drops out-of-range rows;
* property tests for ``adagradselect.predict_next`` — always a subset-legal
  static-shape [k] vector (ascending, padded with num_blocks, never more
  than the slot capacity), deterministic given the state, and *exact* for
  policies whose next selection ignores the next step's norms;
* unit tests for the boundary decomposition (plan/prefetch/writeback/
  commit == the synchronous ``swap_banked``) and the ``StagingPool``;
* planner behavior: prediction hit == synchronous result bit for bit,
  misprediction falls back (and is counted), quiesce drains the in-flight
  job, disabled planner never dispatches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.configs.base import ModelConfig, SelectConfig
from repro.core import adagradselect, masked_adamw, offload, swap
from repro.core import partition as pmod
from repro.models import registry

TINY = ModelConfig(name="swap-tiny", family="dense", num_layers=4,
                   d_model=16, num_heads=2, num_kv_heads=2, head_dim=8,
                   d_ff=32, vocab_size=17, dtype="float32", remat="none",
                   tie_embeddings=False)


# ------------------------------------------------- gather/scatter OOB


@settings(max_examples=20, deadline=None)
@given(length=st.integers(min_value=1, max_value=6),
       cap=st.integers(min_value=1, max_value=6),
       seed=st.integers(min_value=0, max_value=10_000))
def test_gather_rows_fills_oob_with_zeros(length, cap, seed):
    rng = np.random.RandomState(seed)
    leaf = jnp.asarray(rng.randn(length, 3).astype(np.float32))
    slots = jnp.asarray(rng.randint(0, length + 3, size=(cap,)), jnp.int32)
    rows = np.asarray(pmod.gather_rows(leaf, slots))
    for i, s in enumerate(np.asarray(slots)):
        if s < length:
            np.testing.assert_array_equal(rows[i], np.asarray(leaf)[s])
        else:  # sentinel (free slot / padded index) -> fill value
            np.testing.assert_array_equal(rows[i], 0.0)


@settings(max_examples=20, deadline=None)
@given(length=st.integers(min_value=1, max_value=6),
       cap=st.integers(min_value=1, max_value=6),
       seed=st.integers(min_value=0, max_value=10_000))
def test_scatter_rows_drops_oob(length, cap, seed):
    rng = np.random.RandomState(seed)
    leaf = rng.randn(length, 3).astype(np.float32)
    rows = rng.randn(cap, 3).astype(np.float32)
    slots = rng.randint(0, length + 3, size=(cap,)).astype(np.int32)
    out = np.asarray(pmod.scatter_rows(jnp.asarray(leaf),
                                       jnp.asarray(slots),
                                       jnp.asarray(rows)))
    touched = set()
    # later duplicate slots win under .at[].set; iterate in order
    expected = leaf.copy()
    for i, s in enumerate(slots):
        if s < length:
            expected[s] = rows[i]
            touched.add(int(s))
    np.testing.assert_array_equal(out, expected)
    for r in range(length):
        if r not in touched:
            np.testing.assert_array_equal(out[r], leaf[r])


# --------------------------------------------------- predict_next


def _rand_state(policy: str, nb: int, cap: int, seed: int, steps: int):
    """A reachable policy state: init + a few real select iterations."""
    cfg = SelectConfig(policy=policy, k_percent=40, steps_per_epoch=6,
                       epsilon_decay=0.1, lisa_interval=3)
    st_ = adagradselect.init_state(nb, seed=seed, policy=policy, k=cap)
    rng = np.random.RandomState(seed)
    for _ in range(steps):
        norms = jnp.asarray(rng.rand(nb).astype(np.float32))
        _, st_ = adagradselect.select(cfg, st_, norms, nb)
    return cfg, st_


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=1000),
       steps=st.integers(min_value=0, max_value=4))
def test_predict_next_is_subset_legal_and_deterministic(seed, steps):
    nb, cap = 7, 3
    for policy in adagradselect.available_policies():
        cfg, state = _rand_state(policy, nb, cap, seed, steps)
        pred = np.asarray(adagradselect.predict_next(cfg, state, nb))
        # static-shape [cap] i32, ascending, padded with nb, ids in range
        assert pred.shape == (cap,) and pred.dtype == np.int32, policy
        assert (np.diff(pred) >= 0).all(), policy
        assert (pred >= 0).all() and (pred <= nb).all(), policy
        real = pred[pred < nb]
        assert len(np.unique(real)) == len(real), policy  # no duplicate ids
        assert len(real) <= cap, policy  # never exceeds slot capacity
        # deterministic and pure: same state -> same prediction
        pred2 = np.asarray(adagradselect.predict_next(cfg, state, nb))
        np.testing.assert_array_equal(pred, pred2)


@pytest.mark.parametrize("policy", ("random", "lisa", "all"))
def test_predict_next_exact_for_norm_independent_policies(policy):
    """Policies whose next selection ignores the next step's gradient norms
    must be predicted exactly — the PRNG keys are deterministic in
    (key, step) and predict_next folds them as the next select will."""
    nb, cap = 7, 3
    cfg, state = _rand_state(policy, nb, cap, seed=5, steps=2)
    rng = np.random.RandomState(99)
    for _ in range(5):
        pred = np.asarray(adagradselect.predict_next(cfg, state, nb))
        norms = jnp.asarray(rng.rand(nb).astype(np.float32))
        _, state = adagradselect.select(cfg, state, norms, nb)
        np.testing.assert_array_equal(pred, np.asarray(state["indices"]))


# ----------------------------------------------- boundary decomposition


def _banked_fixture(cap=2, seed=0):
    part = pmod.build_partition(TINY)
    model = registry.get(TINY)
    params = model.init(jax.random.PRNGKey(seed), TINY)
    opt = masked_adamw.init_banked_opt_state(part, params, cap)
    return part, params, opt


def _mask(nb, ids):
    m = np.zeros((nb,), bool)
    m[list(ids)] = True
    return m


def test_plan_swap_disjoint_and_capacity():
    part, _, opt = _banked_fixture(cap=2)
    nb = part.num_blocks
    banks, slot_map, store = masked_adamw.swap_banked(
        part, opt["banks"], opt["store"], opt["slot_map"], _mask(nb, [1, 2]))
    plans = masked_adamw.plan_swap(part, slot_map, _mask(nb, [2, 3]),
                                   masked_adamw.bank_caps(banks))
    for p in plans:
        assert not set(p.ev_blocks) & set(p.ad_blocks)
        cap = masked_adamw.bank_caps(banks)[p.key]
        assert (p.ad_slots < cap).all() and (p.ev_slots < cap).all()
    # unchanged mask -> empty plan (the no-op fast path)
    assert masked_adamw.plan_swap(part, slot_map, _mask(nb, [1, 2]),
                                  masked_adamw.bank_caps(banks)) == []


def test_decomposed_boundary_equals_swap_banked():
    """plan -> prefetch -> writeback -> commit must equal the one-call
    ``swap_banked`` (same banks, slot_map, and store) — the async planner
    stages exactly what the synchronous path would."""
    part, params, opt = _banked_fixture(cap=2)
    nb = part.num_blocks
    banks, slot_map, store = masked_adamw.swap_banked(
        part, opt["banks"], opt["store"], opt["slot_map"], _mask(nb, [1, 2]))
    # write recognizable moments so eviction traffic is observable
    banks = jax.tree.map(
        lambda x: x + 1.0 if x.dtype == jnp.float32 and x.ndim > 1 else x,
        banks)

    import copy
    mask2 = _mask(nb, [2, 3])
    b_ref, sm_ref, st_ref = masked_adamw.swap_banked(
        part, banks, copy.deepcopy(store), slot_map, mask2)

    plans = masked_adamw.plan_swap(part, slot_map, mask2,
                                   masked_adamw.bank_caps(banks))
    staged = masked_adamw.prefetch_admissions(plans, store,
                                              swap.StagingPool())
    store2 = masked_adamw.writeback_evictions(
        plans, masked_adamw.gather_evictions(plans, banks), store)
    b_new, sm_new, st_new = masked_adamw.commit_swap(plans, banks, store2,
                                                     slot_map, staged)
    np.testing.assert_array_equal(sm_new, sm_ref)
    for a, b in zip(jax.tree.leaves(b_ref), jax.tree.leaves(b_new)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(st_ref), jax.tree.leaves(st_new)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_staging_pool_reuses_buffers():
    pool = swap.StagingPool()
    leaf = np.zeros((8, 4), np.float32)
    b1 = pool.take("g", "m", 0, 2, leaf)
    b2 = pool.take("g", "m", 0, 2, leaf)
    assert b1 is b2 and b1.shape == (2, 4)
    b3 = pool.take("g", "m", 0, 3, leaf)  # grow: new allocation
    assert b3.shape == (3, 4) and b3 is not b1
    assert pool.take("g", "m", 0, 2, leaf) is b3  # view served from grown
    assert pool.nbytes() == b3.nbytes
    # store_read_rows honors the pool buffer
    src = np.arange(32, dtype=np.float32).reshape(8, 4)
    out = offload.store_read_rows(src, np.array([1, 3]),
                                  out=pool.take("g", "m", 0, 2, src))
    np.testing.assert_array_equal(out, src[[1, 3]])


# ------------------------------------------------------- planner


def _sel_cfg(policy="random"):
    return SelectConfig(policy=policy, k_percent=40, steps_per_epoch=6,
                        epsilon_decay=0.1, lisa_interval=3)


def test_planner_hit_equals_sync_swap():
    """Dispatch with the state that generates the next selection, resolve
    with that exact selection: the committed banks/slot_map/store must be
    bit-identical to the synchronous swap, and the boundary must count as a
    predicted hit (no sync fallback)."""
    import copy
    part, params, opt = _banked_fixture(cap=3)
    nb = part.num_blocks
    cfg = _sel_cfg("random")
    sel = adagradselect.init_state(nb, seed=1, policy="random", k=3)
    _, sel = adagradselect.select(cfg, sel,
                                  jnp.zeros((nb,), jnp.float32), nb)
    idx0 = np.asarray(sel["indices"])
    banks, slot_map, store = masked_adamw.swap_banked(
        part, opt["banks"], opt["store"], opt["slot_map"],
        _mask(nb, idx0[idx0 < nb]))

    planner = swap.SwapPlanner(part, cfg, nb, enabled=True)
    planner.dispatch(sel, banks, store, slot_map)
    # the actual next selection (what the next phase A will compute)
    _, sel_next = adagradselect.select(cfg, sel,
                                       jnp.zeros((nb,), jnp.float32), nb)
    idx1 = np.asarray(sel_next["indices"])
    ref = masked_adamw.swap_banked(part, banks, copy.deepcopy(store),
                                  slot_map, _mask(nb, idx1[idx1 < nb]))
    got = planner.resolve(idx1, banks, store, slot_map)
    planner.close()
    np.testing.assert_array_equal(got[1], ref[1])
    for a, b in zip(jax.tree.leaves(ref[0]), jax.tree.leaves(got[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ref[2]), jax.tree.leaves(got[2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert planner.stats.predicted_hits >= 1
    assert planner.stats.sync_swaps == 0


def test_planner_mispredict_falls_back_and_counts():
    part, params, opt = _banked_fixture(cap=2)
    nb = part.num_blocks
    cfg = _sel_cfg("random")
    sel = adagradselect.init_state(nb, seed=1, policy="random", k=2)
    _, sel = adagradselect.select(cfg, sel,
                                  jnp.zeros((nb,), jnp.float32), nb)
    banks, slot_map, store = opt["banks"], opt["slot_map"], opt["store"]
    planner = swap.SwapPlanner(part, cfg, nb, enabled=True)
    planner.dispatch(sel, banks, store, slot_map)
    # resolve with a selection the policy would never predict here
    pred = np.asarray(adagradselect.predict_next(cfg, sel, nb))
    wrong = np.sort((pred + 1) % nb).astype(np.int32)
    # reference before resolve: the planner's commit donates bank leaves
    import copy
    ref = masked_adamw.swap_banked(part, banks, copy.deepcopy(store),
                                   slot_map, _mask(nb, wrong[wrong < nb]))
    got = planner.resolve(wrong, banks, store, slot_map)
    planner.close()
    assert planner.stats.sync_swaps == 1
    assert planner.stats.predicted_hits == 0
    # fallback result still matches the plain synchronous swap
    np.testing.assert_array_equal(got[1], ref[1])


def test_planner_disabled_never_dispatches():
    part, params, opt = _banked_fixture(cap=2)
    nb = part.num_blocks
    cfg = _sel_cfg("random")
    sel = adagradselect.init_state(nb, seed=0, policy="random", k=2)
    planner = swap.SwapPlanner(part, cfg, nb, enabled=False)
    planner.dispatch(sel, opt["banks"], opt["store"], opt["slot_map"])
    assert planner._pending is None and planner.stats.dispatches == 0
    idx = np.asarray(sel["indices"])
    planner.resolve(idx, opt["banks"], opt["store"], opt["slot_map"])
    assert planner.stats.sync_swaps == 1  # boundary still served, sync
    planner.close()


def test_planner_quiesce_drains_pending():
    part, params, opt = _banked_fixture(cap=2)
    nb = part.num_blocks
    cfg = _sel_cfg("random")
    sel = adagradselect.init_state(nb, seed=0, policy="random", k=2)
    planner = swap.SwapPlanner(part, cfg, nb, enabled=True)
    planner.dispatch(sel, opt["banks"], opt["store"], opt["slot_map"])
    assert planner._pending is not None
    planner.quiesce()
    assert planner._pending is None
    planner.close()

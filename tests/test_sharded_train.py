"""Data-parallel sharded training vs the single-device trajectory oracle.

Three layers of coverage:

* pure-spec unit tests (no devices): the ZeRO-1 store layout rules and the
  canonicalization that keeps sharded steps compile-once;
* in-process dp=1 tests (run everywhere, incl. tier-1 on one device): a
  (1,1) mesh exercises the full placement/constraint machinery — state
  sharding trees, banked+zero1 store, checkpoint marker handling — with
  trivial shardings;
* dp=8 subprocess tests (forced host device count, the multi-device CI
  job): dense and banked residency, >= 2 selection intervals, >= 2
  policies, pinned against the unsharded oracle trajectory; per-device
  sharded-store bytes ~ 1/8 of the replicated layout; both banked phases
  compile exactly once under shardings; the sharded store round-trips
  through checkpoints.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (MeshConfig, ModelConfig, OptimizerConfig,
                                SelectConfig, TrainConfig)
from repro.core import partition as pmod
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.train.trainer import Trainer

# every dim divisible by 8 so the dp=8 store shards exactly 1/8
TINY = ModelConfig(name="sharded-tiny", family="dense", num_layers=8,
                   d_model=16, num_heads=2, num_kv_heads=2, head_dim=8,
                   d_ff=32, vocab_size=24, dtype="float32", remat="none",
                   tie_embeddings=False)


def _tcfg(residency: str, offload_policy: str, policy: str = "adagradselect",
          steps: int = 8, **tkw) -> TrainConfig:
    return TrainConfig(
        model=TINY,
        select=SelectConfig(policy=policy, k_percent=40, steps_per_epoch=10,
                            epsilon_decay=0.05, lisa_interval=3),
        optimizer=OptimizerConfig(lr=1e-2, schedule="constant",
                                  warmup_steps=0,
                                  moment_residency=residency,
                                  offload=offload_policy),
        seq_len=48, global_batch=8, steps=steps, seed=0, log_every=0, **tkw)


# ------------------------------------------------------------ spec units


class _FakeMesh:
    axis_names = ("data", "model")
    devices = np.empty((8, 1))


def test_store_specs_shard_block_axis_when_divisible():
    part = pmod.build_partition(TINY)
    shapes = {g.key: {"m": {"w": jax.ShapeDtypeStruct((g.length, 16, 32),
                                                      jnp.float32)
                            if g.stacked else
                            jax.ShapeDtypeStruct((16, 32), jnp.float32)},
                      "v": {"w": jax.ShapeDtypeStruct((g.length, 16, 32),
                                                      jnp.float32)
                            if g.stacked else
                            jax.ShapeDtypeStruct((16, 32), jnp.float32)}}
              for g in part.groups}
    specs = sh.store_specs(part, shapes, _FakeMesh())
    layers = part.group("layers")
    assert layers.length == 8  # block axis divides dp=8 -> P("data")
    assert tuple(specs["layers"]["m"]["w"]) == ("data",)
    # unstacked: first divisible dim ([16, 32] -> dim 0, 16 % 8 == 0)
    assert tuple(specs["embed"]["m"]["w"]) == ("data",)


def test_store_specs_fall_back_off_the_block_axis():
    cfg = TINY.replace(num_layers=4)  # 4 rows cannot split over dp=8
    part = pmod.build_partition(cfg)
    lshape = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
    shapes = {g.key: {"m": {"w": lshape}, "v": {"w": lshape}}
              for g in part.groups}
    specs = sh.store_specs(part, shapes, _FakeMesh())
    # block axis indivisible -> next divisible dim (16 % 8 == 0 at dim 1)
    assert tuple(specs["layers"]["m"]["w"]) == (None, "data")
    # nothing divisible -> replicated
    odd = {g.key: {"m": {"w": jax.ShapeDtypeStruct((3, 5, 7), jnp.float32)},
                   "v": {"w": jax.ShapeDtypeStruct((3, 5, 7), jnp.float32)}}
           for g in part.groups}
    assert tuple(sh.store_specs(part, odd, _FakeMesh())["layers"]["m"]["w"]) \
        == ()


def test_canonical_specs():
    from jax.sharding import PartitionSpec as P
    assert sh.canonical_spec(P(None, None)) == P()
    assert sh.canonical_spec(P(None, "model")) == P(None, "model")

    class DPOnly:
        axis_names = ("data", "model")
        devices = np.empty((8, 1))

    assert sh.mesh_canonical_spec(P(None, "model"), DPOnly()) == P()
    assert sh.mesh_canonical_spec(P("data", "model"), DPOnly()) == P("data")
    assert sh.mesh_canonical_spec(P(("data", "model"),), DPOnly()) \
        == P("data")


# ------------------------------------------------------- dp=1 in-process


def _dp1_mesh():
    return make_mesh(MeshConfig((1, 1), ("data", "model")))


@pytest.mark.parametrize("residency,offload_policy",
                         [("device", "none"), ("device", "zero1"),
                          ("banked", "host"), ("banked", "zero1")])
def test_dp1_mesh_matches_unsharded_oracle(residency, offload_policy):
    """The mesh code path on a (1,1) mesh must reproduce the plain
    single-device trajectory exactly — placement, output constraints, and
    the sharded-store init are all exercised with trivial shardings."""
    oracle = Trainer(_tcfg("device", "none", steps=5))
    lo = oracle.train()
    tr = Trainer(_tcfg(residency, offload_policy, steps=5), mesh=_dp1_mesh())
    lg = tr.train()
    np.testing.assert_allclose(lo.losses, lg.losses, rtol=0, atol=2e-6)
    for a, b in zip(jax.tree.leaves(oracle.state["params"]),
                    jax.tree.leaves(tr.state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_banked_zero1_requires_mesh():
    """The PR-3 rejection survives only for the genuinely-degenerate case:
    banked + zero1 WITHOUT a mesh (an unsharded device store). With a mesh
    the store init shards over the data axis instead of raising."""
    with pytest.raises(ValueError, match="mesh"):
        Trainer(_tcfg("banked", "zero1"))
    tr = Trainer(_tcfg("banked", "zero1", steps=1), mesh=_dp1_mesh())
    leaf = jax.tree.leaves(tr.state["opt"]["store"])[0]
    assert not isinstance(leaf, np.ndarray)  # device-resident, sharded


def test_mesh_batch_sharding_constructed():
    """With a mesh the trainer builds a batch sharding over the data axes
    (dp=1 divides everything; the indivisible-batch error is covered by the
    dp=8 subprocess test)."""
    t = Trainer(_tcfg("device", "none", steps=1), mesh=_dp1_mesh())
    assert t._batch_sharding is not None


# ------------------------------------------------------ dp=8 subprocess

_DP8_PRELUDE = """
import jax, numpy as np
from repro.configs.base import ModelConfig, OptimizerConfig, SelectConfig, TrainConfig
from repro.train.trainer import Trainer
from repro.launch.mesh import make_data_mesh
from repro.core import offload

TINY = ModelConfig(name="sharded-tiny", family="dense", num_layers=8,
                   d_model=16, num_heads=2, num_kv_heads=2, head_dim=8,
                   d_ff=32, vocab_size=24, dtype="float32", remat="none",
                   tie_embeddings=False)

def tcfg(residency, offload_p, policy="adagradselect", steps=8,
         async_swap=True, **tkw):
    return TrainConfig(model=TINY,
        select=SelectConfig(policy=policy, k_percent=40, steps_per_epoch=10,
                            epsilon_decay=0.05, lisa_interval=3),
        optimizer=OptimizerConfig(lr=1e-2, schedule="constant", warmup_steps=0,
                                  moment_residency=residency, offload=offload_p,
                                  async_swap=async_swap),
        seq_len=48, global_batch=8, steps=steps, seed=0, log_every=0, **tkw)

mesh = make_data_mesh()
assert mesh.devices.shape == (8, 1), mesh.devices.shape
"""


def test_dp8_matches_single_device_oracle(multidevice):
    """dense + banked x {adagradselect, lisa} on a dp=8 mesh, 8 steps
    (>= 2 lisa intervals): losses and final params pinned against the
    unsharded oracle; both banked phases compile exactly once; the zero1
    store measures 1/8 per device; a wrong global batch raises."""
    out = multidevice(_DP8_PRELUDE + """
oracle = {}
for pol in ("adagradselect", "lisa"):
    o = Trainer(tcfg("device", "none", pol))
    oracle[pol] = (o.train(), o.state)

combos = [("device", "none", "adagradselect"), ("device", "zero1", "lisa"),
          ("banked", "host", "lisa"), ("banked", "zero1", "adagradselect"),
          ("banked", "zero1", "lisa")]
for res, off, pol in combos:
    tr = Trainer(tcfg(res, off, pol), mesh=mesh)
    lg = tr.train()
    lo, ostate = oracle[pol]
    np.testing.assert_allclose(lo.losses, lg.losses, atol=2e-5)
    for a, b in zip(jax.tree.leaves(ostate["params"]),
                    jax.tree.leaves(tr.state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    if res == "banked":
        assert tr.step_fn.forward_select._cache_size() == 1
        assert tr.step_fn.apply._cache_size() == 1
    elif hasattr(tr.step_fn, "_cache_size"):
        assert tr.step_fn._cache_size() == 1
    print("PARITY", res, off, pol)

# per-device resident store bytes: zero1 ~ 1/8 of the replicated layout
t_z = Trainer(tcfg("banked", "zero1", steps=1), mesh=mesh)
t_r = Trainer(tcfg("banked", "none", steps=1), mesh=mesh)
bz = offload.resident_opt_bytes(t_z.state["opt"]["store"])
br = offload.resident_opt_bytes(t_r.state["opt"]["store"])
ratio = bz["device_per_device"] / br["device_per_device"]
assert ratio <= 0.130, (bz, br)
print("STORE_RATIO %.4f" % ratio)

bad = tcfg("device", "none")
bad = TrainConfig(**{**bad.__dict__, "global_batch": 6})
try:
    Trainer(bad, mesh=mesh)
    raise SystemExit("should have raised on indivisible global batch")
except ValueError as e:
    assert "divisible" in str(e)
print("OK", len(combos))
""", num_devices=8, timeout=560)
    assert "OK 5" in out
    assert "STORE_RATIO 0.125" in out


def test_dp8_async_swap_parity(multidevice):
    """banked + zero1 on dp=8: the overlapped boundary must be bit-identical
    to the synchronous one under sharded stores — losses, params, AND
    materialized moments — for two policies, with the planner actually
    dispatching (and hitting) on the async side and never on the sync
    side. Both banked phases still compile exactly once either way."""
    out = multidevice(_DP8_PRELUDE + """
from repro.core import masked_adamw
from repro.core import partition as pmod

part = pmod.build_partition(TINY)
for pol in ("adagradselect",):
    runs = {}
    for flag in (False, True):
        tr = Trainer(tcfg("banked", "zero1", async_swap=flag), mesh=mesh,
                     method=pol)
        log = tr.train()
        m, v = masked_adamw.materialize_moments(part, tr.state["opt"])
        runs[flag] = (log, tr, m, v)
        assert tr.step_fn.forward_select._cache_size() == 1, (pol, flag)
        assert tr.step_fn.apply._cache_size() == 1, (pol, flag)
    (ls, ts, ms, vs), (la, ta, ma, va) = runs[False], runs[True]
    np.testing.assert_array_equal(ls.losses, la.losses)
    for a, b in zip(jax.tree.leaves(ts.state["params"]),
                    jax.tree.leaves(ta.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves((ms, vs)), jax.tree.leaves((ma, va))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    on, off = ta.step_fn.swap_stats, ts.step_fn.swap_stats
    assert on.dispatches > 0 and off.dispatches == 0, (pol, on, off)
    print("ASYNC_PARITY", pol, "hit_rate=%.2f" % on.predicted_hit_rate)
print("OK async")
""", num_devices=8, timeout=560)
    assert "OK async" in out
    assert "ASYNC_PARITY" in out


def test_dp8_kernels_per_shard_match_xla_path(multidevice):
    """use_pallas='always' on dp=8: GSPMD cannot partition a Pallas kernel,
    so the step runs each kernel per shard (shard_map) in the layout of the
    state it touches — ZeRO-1-sharded dense moments (layer axis split over
    data) and replicated banks. Both follow the XLA path's trajectory and
    both banked phases still compile once."""
    out = multidevice(_DP8_PRELUDE + """
import dataclasses
for res in ("device", "banked"):
    runs = {}
    for mode in ("never", "always"):
        t = tcfg(res, "zero1", steps=4)
        t = dataclasses.replace(t, model=TINY.replace(use_pallas=mode))
        tr = Trainer(t, mesh=mesh)
        runs[mode] = (tr.train().losses, tr.state["params"])
        if res == "banked":
            assert tr.step_fn.forward_select._cache_size() == 1
            assert tr.step_fn.apply._cache_size() == 1
    np.testing.assert_allclose(runs["always"][0], runs["never"][0],
                               atol=2e-5)
    for a, b in zip(jax.tree.leaves(runs["always"][1]),
                    jax.tree.leaves(runs["never"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    print("KERNEL_PARITY", res)
print("OK kernels")
""", num_devices=8, timeout=560)
    assert "OK kernels" in out


def test_dp8_sharded_checkpoint_roundtrip(multidevice):
    """banked + zero1 on dp=8: mid-run save, restore into a fresh trainer
    (store re-sharded onto the mesh), continue — identical params to the
    uninterrupted run (gather-on-save / re-place-on-restore)."""
    out = multidevice(_DP8_PRELUDE + """
import tempfile
full = Trainer(tcfg("banked", "zero1", "lisa"), mesh=mesh)
full.train()

d = tempfile.mkdtemp()
t1 = Trainer(tcfg("banked", "zero1", "lisa", steps=4, checkpoint_dir=d,
                  checkpoint_every=4), mesh=mesh)
t1.train()
t2 = Trainer(tcfg("banked", "zero1", "lisa", checkpoint_dir=d), mesh=mesh)
start = t2.maybe_restore()
assert start == 4, start
leaf = jax.tree.leaves(t2.state["opt"]["store"])[0]
assert "data" in str(leaf.sharding), leaf.sharding  # re-sharded on restore
t2.train(steps=4, start_step=start)
for a, b in zip(jax.tree.leaves(full.state["params"]),
                jax.tree.leaves(t2.state["params"])):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("OK roundtrip")
""", num_devices=8, timeout=560)
    assert "OK roundtrip" in out


def test_flash_attention_layer_runs_per_shard(multidevice):
    """use_pallas='always' on a 2 x 2 (data, model) mesh: the training
    attention layer runs the flash kernel per shard (batch over data, heads
    over model) and matches the XLA path, forward and gradients."""
    out = multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh
from repro.configs.base import MeshConfig
from repro.models.layers import attention

mesh = make_mesh(MeshConfig(shape=(2, 2), axes=("data", "model")))
cfg = ModelConfig(name="flash-mesh", family="dense", num_layers=1,
                  d_model=64, num_heads=4, num_kv_heads=2, head_dim=64,
                  d_ff=128, vocab_size=32, dtype="float32")
params = attention.init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 256, 64))
seg = jnp.asarray(np.repeat(np.arange(1, 5), 64)[None].repeat(4, 0), jnp.int32)
x = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
runs = {}
for mode in ("always", "never"):
    c = cfg.replace(use_pallas=mode)
    def loss(p, x):
        o = attention.apply(p, c, x, segment_ids=seg, mesh=mesh)
        return jnp.sum(o ** 2), o
    (_, o), g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                           has_aux=True))(params, x)
    runs[mode] = (o, g)
np.testing.assert_allclose(np.asarray(runs["always"][0]),
                           np.asarray(runs["never"][0]), rtol=1e-5, atol=1e-5)
for a, b in zip(jax.tree.leaves(runs["always"][1]),
                jax.tree.leaves(runs["never"][1])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)
print("OK flash mesh")
""", num_devices=4, timeout=300)
    assert "OK flash mesh" in out

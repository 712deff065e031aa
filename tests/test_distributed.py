"""Distribution: sharding rules, dry-run cells on a tiny mesh, gradient
compression, HLO cost analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_smoke_config
from repro.distributed import sharding as sh
from repro.launch.hlo_cost import analyze_text


def test_hlo_cost_scan_trip_counts():
    def body(x, _):
        return x @ x, None

    def scanned(x):
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    txt = jax.jit(scanned).lower(x).compile().as_text()
    ct = analyze_text(txt, 1)
    assert abs(ct.flops - 10 * 2 * 128**3) / (10 * 2 * 128**3) < 1e-6
    assert ct.unknown_trip_whiles == 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_specs_resolve(arch, multidevice=None):
    """Every leaf gets a spec whose sharded dims divide-or-pad legally."""
    cfg = get_smoke_config(arch)
    from repro.models import registry
    model = registry.get(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k, cfg), jax.random.PRNGKey(0))
    # a fake mesh-dims view is enough to exercise the rule table
    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((2, 4))
    specs = sh.param_specs(cfg, shapes, FakeMesh())
    n_sharded = sum(
        1 for s in jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index"))
        if any(p is not None for p in s))
    assert n_sharded > 0


@pytest.mark.parametrize("m", [1, 2, 4])
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_spec_rules_every_family(arch, m):
    """The rules table, leaf by leaf, over every registered model family
    (dense / MoE / SSM / MLA / hybrid / encdec / vlm) x model-axis sizes
    {1, 2, 4}: every leaf must get a spec that (a) fits the leaf's rank,
    (b) names only mesh axes, (c) never repeats an axis, and (d) follows
    the kv-head rule — kv projections shard over "model" iff the kv-head
    count divides the axis, else they fall back to replicated."""
    from jax.sharding import PartitionSpec as P

    from repro.utils.trees import path_str, tree_leaves_with_path

    cfg = get_smoke_config(arch)
    from repro.models import registry
    model = registry.get(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k, cfg),
                            jax.random.PRNGKey(0))

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((2, m))

    flat_shapes = dict(tree_leaves_with_path(shapes))
    specs = sh.param_specs(cfg, shapes, FakeMesh())
    flat_specs = {
        path_str(p): s for p, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]}
    assert set(flat_specs) == set(flat_shapes)
    for path, spec in flat_specs.items():
        leaf = flat_shapes[path]
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        used = []
        for part in spec:
            for ax in (part if isinstance(part, tuple) else (part,)):
                if ax is not None:
                    used.append(ax)
        assert all(ax in ("data", "model") for ax in used), (path, spec)
        assert len(used) == len(set(used)), f"axis repeated: {path} {spec}"
        base = path.split("/")[-1]
        stacked = path.split("/")[0].endswith("layers")
        if base in ("wk", "wv"):
            kvh = leaf.shape[-2]
            model_sharded = any(
                ax == "model"
                for part in spec
                for ax in (part if isinstance(part, tuple) else (part,)))
            assert model_sharded == (kvh % m == 0), \
                f"kv rule violated: {path} kvh={kvh} m={m} spec={spec}"
        if stacked and len(spec) > 0:
            # the stacked layer axis is never sharded by the param rules
            assert spec[0] is None, (path, spec)


def test_dryrun_cells_tiny_mesh(multidevice):
    """Lower+compile train/prefill/decode for representative archs on a
    (2,4) mesh in a subprocess — the structural core of deliverable (e)."""
    out = multidevice("""
import sys
sys.argv = ["dryrun"]
from repro.launch.dryrun import run_cell
from repro.configs import get_smoke_config
ok = 0
cells = [("llama3.2-1b", "train_4k"), ("qwen3-moe-30b-a3b", "train_4k"),
         ("mamba2-2.7b", "decode_32k"), ("zamba2-7b", "decode_32k"),
         ("seamless-m4t-medium", "prefill_32k"), ("paligemma-3b", "train_4k"),
         ("deepseek-v3-671b", "train_4k")]
for arch, shape in cells:
    cfg = get_smoke_config(arch).replace(ssm_chunk=32)
    r = run_cell(arch, shape, "tiny", cfg_override=cfg, verbose=False)
    assert r["status"] == "ok", (arch, shape, r.get("error"), r.get("traceback"))
    assert r["roofline"]["flops_per_chip"] > 0
    ok += 1
print("OK", ok)
""", num_devices=8, timeout=560)
    assert "OK 7" in out


def test_grad_compression_error_feedback(multidevice):
    out = multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import (compressed_grad_sync,
                                           init_error_state,
                                           quantize_with_feedback)
# error feedback: repeated quantization converges to within one bf16
# quantum / n (the EF residual bound)
g = jnp.full((64,), 1.0 + 2**-12, jnp.float32)  # not bf16-representable
err = jnp.zeros_like(g)
tot = jnp.zeros_like(g)
for _ in range(64):
    q, err = quantize_with_feedback(g, err)
    tot = tot + q.astype(jnp.float32)
np.testing.assert_allclose(np.asarray(tot / 64), np.asarray(g), atol=2**-8/32)

# shard_map psum path: values exact for bf16-representable grads; the
# payload enters the reduce through a bf16 quantization (XLA:CPU promotes
# the wire dtype to f32 — TPU keeps bf16 — so we assert the quantize
# convert exists, not the wire dtype)
mesh = jax.make_mesh((4,), ("data",))
def body(g, e):
    return compressed_grad_sync({"g": g}, {"g": e}, mesh, axes=("data",))
g_loc = jnp.arange(8.0)
f = jax.jit(jax.shard_map(body, mesh=mesh,
                          in_specs=(P("data"), P("data")),
                          out_specs=(P("data"), P("data")), check_vma=False))
synced, e2 = f(jnp.tile(g_loc, 4).reshape(32), jnp.zeros(32))
np.testing.assert_allclose(np.asarray(synced["g"][:8]), np.asarray(g_loc))
hlo = f.lower(jnp.zeros(32), jnp.zeros(32)).compile().as_text()
assert "all-reduce" in hlo and "bf16[" in hlo
print("OK")
""")
    assert "OK" in out


def test_mesh_device_count_error_message():
    """make_mesh / make_production_mesh raise an actionable error (with the
    XLA_FLAGS hint) when the device count does not match, instead of jax's
    opaque failure."""
    from repro.configs.base import MeshConfig
    from repro.launch import mesh as mesh_mod

    n = len(jax.devices())
    bad = MeshConfig((n + 1, 1), ("data", "model"))
    with pytest.raises(ValueError) as ei:
        mesh_mod.make_mesh(bad)
    msg = str(ei.value)
    assert f"needs {n + 1} devices" in msg
    assert f"found {n}" in msg
    assert f"--xla_force_host_platform_device_count={n + 1}" in msg
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        mesh_mod.make_production_mesh()


def test_zero1_moment_sharding(multidevice):
    out = multidevice("""
import jax, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_smoke_config
from repro.configs.base import MeshConfig
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import registry
cfg = get_smoke_config("llama3.2-1b")
mesh = make_mesh(MeshConfig((2, 4), ("data", "model")))
model = registry.get(cfg)
shapes = jax.eval_shape(lambda k: model.init(k, cfg), jax.random.PRNGKey(0))
specs = sh.param_specs(cfg, shapes, mesh)
z1 = sh.apply_zero1(specs, shapes, mesh)
import jax.tree_util as jtu
n_extra = 0
for s0, s1 in zip(jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index")),
                  jax.tree.leaves(z1, is_leaf=lambda x: hasattr(x, "index"))):
    if tuple(s0) != tuple(s1):
        n_extra += 1
        assert "data" in [p for p in s1 if p]
assert n_extra > 0, "zero1 sharded nothing"
print("OK", n_extra)
""")
    assert "OK" in out

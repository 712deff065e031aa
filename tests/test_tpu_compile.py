"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: these tests
describe a ``v5e:2x2`` topology, lower each kernel at qwen2.5-0.5b widths
onto one of its chips and compile it. That refuses what interpret mode
cannot see — blocks that break the (8, 128) tiling, scalar loads from
VMEM, kernels that overflow VMEM. Nothing runs, so numerics stay with
tests/test_kernels.py.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and a decision made
while collecting would give parallel test workers different test lists.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import (block_grad_norm, decode_attention, flash_attention,
                           masked_adamw, ops)
from repro.models.layers.attention import _hmap

QWEN = get_config("qwen2.5-0.5b")
L = QWEN.num_layers
CAP = 5  # bank rows at k = 20% of qwen2.5-0.5b's 26 blocks

# stacked [L, ...] leaves of qwen2.5-0.5b: the MLP weight (row blocks with
# an overhanging tail), a norm scale (one short block) and a kv bias (one
# 128-lane row)
LEAVES = {
    "mlp_wg": (L, QWEN.d_model, QWEN.d_ff),
    "ln1_scale": (L, QWEN.d_model),
    "attn_bk": (L, QWEN.num_kv_heads, QWEN.head_dim),
}


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "the kernel did not lower to Mosaic"


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_block_grad_sq_norms_compiles(one_chip, leaf):
    def fn(g):
        return block_grad_norm.block_grad_sq_norms(ops._lanes(g),
                                                   interpret=False)
    _compile(fn, one_chip, (LEAVES[leaf], jnp.bfloat16))


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_masked_adamw_compiles(one_chip, leaf):
    shape = LEAVES[leaf]

    def fn(p, g, m, v, sel, cnt, lr):
        return masked_adamw.masked_adamw(
            ops._lanes(p), ops._lanes(g), ops._lanes(m), ops._lanes(v),
            sel, cnt, lr, 0.9, 0.999, 1e-8, 0.01, interpret=False)
    _compile(fn, one_chip, (shape, jnp.bfloat16), (shape, jnp.bfloat16),
             (shape, jnp.float32), (shape, jnp.float32), ((L,), jnp.float32),
             ((L,), jnp.float32), ((), jnp.float32))


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_banked_masked_adamw_compiles(one_chip, leaf):
    shape = LEAVES[leaf]
    bank = (CAP,) + shape[1:]

    def fn(p, g, m, v, slots, sel, cnt, lr):
        return masked_adamw.banked_masked_adamw(
            ops._lanes(p), ops._lanes(g), ops._lanes(m), ops._lanes(v),
            slots, sel, cnt, lr, 0.9, 0.999, 1e-8, 0.01, interpret=False)
    _compile(fn, one_chip, (shape, jnp.bfloat16), (shape, jnp.bfloat16),
             (bank, jnp.float32), (bank, jnp.float32), ((CAP,), jnp.int32),
             ((CAP,), jnp.float32), ((CAP,), jnp.float32), ((), jnp.float32))


def test_paged_decode_attention_compiles(one_chip):
    slots, page_size, max_len = 4, 16, 512
    maxp = max_len // page_size
    num_pages = slots * maxp
    h, kvh, d = QWEN.padded_heads, QWEN.num_kv_heads, QWEN.head_dim
    hmap = np.asarray(_hmap(QWEN), np.int32)

    def fn(q, k_pool, v_pool, tables, valid_len):
        return decode_attention.paged_decode_attention(
            q, k_pool, v_pool, tables, valid_len, hmap, interpret=False)
    pool = ((num_pages, kvh, page_size, d), jnp.bfloat16)
    _compile(fn, one_chip, ((slots, h, d), jnp.bfloat16), pool, pool,
             ((slots, maxp), jnp.int32), ((slots,), jnp.int32))


def test_flash_attention_segmented_fwd_bwd_compiles(one_chip):
    """Training attention of the qwen2.5-0.5b cells: 8 packed rows of 1024,
    16 (padded) heads of 64, int32 segment ids; forward and backward."""
    b, s, h, d = 8, 1024, QWEN.padded_heads, QWEN.head_dim
    bq, bk = flash_attention.block_sizes(s, d)

    def fn(q, k, v, seg):
        def loss(q, k, v):
            o = flash_attention.flash_attention(q, k, v, seg, h, bq, bk,
                                                True, False)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    qkv = ((b * h, s, d), jnp.bfloat16)
    _compile(fn, one_chip, qkv, qkv, qkv, ((b, s), jnp.int32))


# (S, D, segmented): every head width of a registered config that reaches
# the kernel (64, 112, 128, 256), both block sizes (256 where they tile S,
# else 128), and the plain causal kernel
FLASH_SHAPES = [(1024, 128, True), (1024, 112, True), (2048, 256, True),
                (384, 64, True), (1024, 128, False)]


@pytest.mark.parametrize("s,d,segmented", FLASH_SHAPES)
def test_flash_attention_widths_compile(one_chip, s, d, segmented):
    b, h = 2, 4
    assert flash_attention.fits(s, d, 2)
    bq, bk = flash_attention.block_sizes(s, d)

    def fn(q, k, v, seg):
        def loss(q, k, v):
            o = flash_attention.flash_attention(
                q, k, v, seg if segmented else None, h, bq, bk, True, False)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    qkv = ((b * h, s, d), jnp.bfloat16)
    _compile(fn, one_chip, qkv, qkv, qkv, ((b, s), jnp.int32))


def test_flash_attention_per_shard_compiles(topology, monkeypatch):
    """The layer's call on a 2 x 2 (data, model) mesh of described chips:
    each chip runs the kernel on its shard (batch over data, heads over
    model) inside ``shard_map``, forward and backward."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topology.devices[:4]).reshape(2, 2),
                ("data", "model"))
    b, s, h, d = 8, 1024, QWEN.padded_heads, QWEN.head_dim

    def fn(q, k, v, seg):
        def loss(q, k, v):
            o = ops.flash_attention(q, k, v, segment_ids=seg, mesh=mesh)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    act = NamedSharding(mesh, P("data", None, "model", None))
    qkv = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=act)
    seg = jax.ShapeDtypeStruct((b, s), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", None)))
    hlo = jax.jit(fn).lower(qkv, qkv, qkv, seg).compile().as_text()
    assert "tpu_custom_call" in hlo, "the kernel did not lower to Mosaic"

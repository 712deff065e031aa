"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs ref.py oracles
(interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


class TestBlockGradNorm:
    # (2, 70001): 547 lane rows -> a full 512-row block plus an overhanging
    # tail block whose out-of-range rows must not count
    @pytest.mark.parametrize("shape", [(3, 100), (2, 64, 65), (5, 7, 9, 11),
                                       (1, 2048), (4, 4096), (2, 70001)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, shape, dtype):
        g = (jax.random.normal(jax.random.PRNGKey(0), shape) * 2).astype(dtype)
        out = ops.block_grad_sq_norms(g)
        expect = ref.block_grad_sq_norms(g)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-3)

    def test_under_jit(self):
        g = jax.random.normal(jax.random.PRNGKey(1), (4, 333))
        out = jax.jit(ops.block_grad_sq_norms)(g)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.block_grad_sq_norms(g)),
                                   rtol=1e-5)


class TestMaskedAdamW:
    @pytest.mark.parametrize("shape", [(4, 100), (2, 32, 9), (3, 2048),
                                       (2, 70001)])
    @pytest.mark.parametrize("pdtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, shape, pdtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        nl = shape[0]
        p = jax.random.normal(ks[0], shape).astype(pdtype)
        g = (0.1 * jax.random.normal(ks[1], shape)).astype(pdtype)
        m = 0.01 * jax.random.normal(ks[2], shape)
        v = 0.001 * jnp.abs(jax.random.normal(ks[3], shape))
        sel = jnp.asarray(np.arange(nl) % 2, jnp.float32)
        cnt = jnp.arange(1, nl + 1, dtype=jnp.float32)
        args = (1e-2, 0.9, 0.999, 1e-8, 0.01)
        po, mo, vo = ops.masked_adamw(p, g, m, v, sel, cnt, *args)
        l2 = shape[0]
        flat = lambda t: t.reshape(l2, -1)  # noqa: E731
        pr, mr, vr = ref.masked_adamw(flat(p), flat(g), flat(m), flat(v),
                                      sel, cnt, *args)
        np.testing.assert_allclose(np.asarray(po, np.float32).reshape(l2, -1),
                                   np.asarray(pr, np.float32), **_tol(pdtype))
        np.testing.assert_allclose(np.asarray(mo).reshape(l2, -1),
                                   np.asarray(mr), rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vo).reshape(l2, -1),
                                   np.asarray(vr), rtol=1e-4, atol=1e-8)


class TestFlashAttention:
    @pytest.mark.parametrize("s", [128, 256, 384])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fwd_sweep(self, s, dtype):
        b, h, d = 2, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = (0.5 * jax.random.normal(ks[0], (b, s, h, d))).astype(dtype)
        k = (0.5 * jax.random.normal(ks[1], (b, s, h, d))).astype(dtype)
        v = (0.5 * jax.random.normal(ks[2], (b, s, h, d))).astype(dtype)
        o = ops.flash_attention(q, k, v)
        fold = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        expect = ref.flash_attention(fold(q), fold(k), fold(v)).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(expect, np.float32), **_tol(dtype))

    def test_grads_match_ref(self):
        b, s, h, d = 1, 256, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (0.5 * jax.random.normal(kk, (b, s, h, d)) for kk in ks)
        fold = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731

        def lk(q, k, v):
            return jnp.sum(ops.flash_attention(q, k, v) ** 2)

        def lr(q, k, v):
            return jnp.sum(ref.flash_attention(fold(q), fold(k), fold(v)) ** 2)

        gk = jax.grad(lk, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    def _packed_segments(self, b, s):
        """Variable-length packed layout incl. a pad (0) tail and segments
        crossing the 128-tile boundaries."""
        seg = np.zeros((b, s), np.int32)
        seg[0, :s // 3] = 1
        seg[0, s // 3:s - 40] = 2
        seg[0, s - 40:s - 16] = 3
        seg[1, :150] = 1
        seg[1, 150:] = 2
        return jnp.asarray(seg)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_segmented_fwd(self, dtype):
        b, s, h, d = 2, 256, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = (0.5 * jax.random.normal(ks[0], (b, s, h, d))).astype(dtype)
        k = (0.5 * jax.random.normal(ks[1], (b, s, h, d))).astype(dtype)
        v = (0.5 * jax.random.normal(ks[2], (b, s, h, d))).astype(dtype)
        seg = self._packed_segments(b, s)
        o = ops.flash_attention(q, k, v, segment_ids=seg)
        fold = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        expect = ref.flash_attention(fold(q), fold(k), fold(v),
                                     segment_ids=seg).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(expect, np.float32),
                                   **_tol(dtype))

    def test_segmented_grads_match_ref(self):
        b, s, h, d = 2, 256, 2, 32
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q, k, v = (0.5 * jax.random.normal(kk, (b, s, h, d)) for kk in ks)
        seg = self._packed_segments(b, s)
        fold = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731

        def lk(q, k, v):
            return jnp.sum(ops.flash_attention(q, k, v,
                                               segment_ids=seg) ** 2)

        def lr(q, k, v):
            return jnp.sum(ref.flash_attention(fold(q), fold(k), fold(v),
                                               segment_ids=seg) ** 2)

        gk = jax.grad(lk, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)


class TestDecodeAttention:
    @pytest.mark.parametrize("s,valid", [(512, 100), (1024, 1024), (2048, 7)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, s, valid, dtype):
        b, h, d = 2, 4, 64
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = (jax.random.normal(ks[0], (b, 1, h, d))).astype(dtype)
        k = (0.5 * jax.random.normal(ks[1], (b, s, h, d))).astype(dtype)
        v = (0.5 * jax.random.normal(ks[2], (b, s, h, d))).astype(dtype)
        o = ops.decode_attention(q, k, v, valid)
        fold = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        expect = ref.decode_attention(q.reshape(b, h, d), fold(k), fold(v), valid)
        np.testing.assert_allclose(np.asarray(o.reshape(b, h, d), np.float32),
                                   np.asarray(expect, np.float32), **_tol(dtype))

    @pytest.mark.parametrize("s,valids", [(512, (1, 100, 512)),
                                          (1024, (7, 1024, 333))])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_vector_valid_len(self, s, valids, dtype):
        """Per-row valid_len (continuous-batching slots at mixed progress)
        must match the oracle row for row."""
        b, h, d = len(valids), 4, 64
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = (jax.random.normal(ks[0], (b, 1, h, d))).astype(dtype)
        k = (0.5 * jax.random.normal(ks[1], (b, s, h, d))).astype(dtype)
        v = (0.5 * jax.random.normal(ks[2], (b, s, h, d))).astype(dtype)
        vl = jnp.asarray(valids, jnp.int32)
        o = ops.decode_attention(q, k, v, vl)
        fold = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
        expect = ref.decode_attention(q.reshape(b, h, d), fold(k), fold(v), vl)
        np.testing.assert_allclose(np.asarray(o.reshape(b, h, d), np.float32),
                                   np.asarray(expect, np.float32), **_tol(dtype))
        # each row must equal the scalar-valid_len result for its own length
        for i, v_i in enumerate(valids):
            solo = ops.decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                        v_i)
            np.testing.assert_allclose(np.asarray(o[i], np.float32),
                                       np.asarray(solo[0], np.float32),
                                       rtol=1e-6, atol=1e-6)


class TestRMSNorm:
    @pytest.mark.parametrize("shape", [(8, 128), (2, 16, 256), (3, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, shape, dtype):
        x = jax.random.normal(jax.random.PRNGKey(3), shape).astype(dtype)
        sc = (1 + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                          (shape[-1],))).astype(dtype)
        o = ops.rmsnorm(x, sc)
        expect = ref.rmsnorm(x.reshape(-1, shape[-1]), sc).reshape(shape)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(expect, np.float32), **_tol(dtype))


class TestPagedDecodeAttention:
    """Paged flash-decoding: pool + scalar-prefetched page tables must match
    both the pure-jnp oracle and the dense kernel on the gathered view."""

    def _pool(self, seed, num_pages, ps, kvh, d, dtype):
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        k = (0.5 * jax.random.normal(ks[0], (num_pages, kvh, ps, d))).astype(dtype)
        v = (0.5 * jax.random.normal(ks[1], (num_pages, kvh, ps, d))).astype(dtype)
        return k, v

    @pytest.mark.parametrize("ps", [4, 8, 16])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_page_size_sweep_vs_ref(self, ps, dtype):
        """Scrambled (non-contiguous) page tables with sentinel entries and
        per-row valid_len, GQA hmap — pinned against the jnp oracle."""
        b, h, kvh, d = 3, 4, 2, 64
        num_pages, maxp = 20, 5
        hmap = jnp.asarray([0, 0, 1, 1], jnp.int32)
        k_pool, v_pool = self._pool(7, num_pages, ps, kvh, d, dtype)
        rng = np.random.default_rng(11)
        perm = rng.permutation(num_pages)
        tbl = np.full((b, maxp), num_pages, np.int32)  # sentinel-filled
        vl = np.asarray([1, 2 * ps + 1, maxp * ps], np.int32)
        used = 0
        for i in range(b):
            n = -(-int(vl[i]) // ps)
            tbl[i, :n] = perm[used:used + n]
            used += n
        q = jax.random.normal(jax.random.PRNGKey(1), (b, 1, h, d)).astype(dtype)
        o = ops.paged_decode_attention(q, k_pool, v_pool, jnp.asarray(tbl),
                                       jnp.asarray(vl), hmap)
        expect = ref.paged_decode_attention(q.reshape(b, h, d), k_pool,
                                            v_pool, jnp.asarray(tbl),
                                            jnp.asarray(vl), hmap)
        np.testing.assert_allclose(np.asarray(o.reshape(b, h, d), np.float32),
                                   np.asarray(expect, np.float32),
                                   **_tol(dtype))

    @pytest.mark.parametrize("ps", [8, 16])
    def test_matches_dense_kernel_on_gathered_view(self, ps):
        """The paged kernel on (pool, table) must agree with the dense
        kernel run over the gathered head-expanded dense cache."""
        b, h, kvh, d = 2, 4, 2, 64
        maxp = 4
        num_pages = b * maxp
        hmap = jnp.asarray([0, 0, 1, 1], jnp.int32)
        k_pool, v_pool = self._pool(3, num_pages, ps, kvh, d, jnp.float32)
        rng = np.random.default_rng(5)
        tbl = rng.permutation(num_pages).reshape(b, maxp).astype(np.int32)
        vl = np.asarray([ps + 3, maxp * ps], np.int32)
        q = jax.random.normal(jax.random.PRNGKey(2), (b, 1, h, d))
        o = ops.paged_decode_attention(q, k_pool, v_pool, jnp.asarray(tbl),
                                       jnp.asarray(vl), hmap)
        # dense view: gather pages row-major, expand kv heads via hmap
        dense = lambda pool: pool[tbl].transpose(0, 1, 3, 2, 4).reshape(  # noqa: E731
            b, maxp * ps, kvh, d)[:, :, hmap, :]
        kd, vd = dense(k_pool), dense(v_pool)
        od = ops.decode_attention(q, kd, vd, jnp.asarray(vl))
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(od, np.float32),
                                   rtol=2e-5, atol=2e-5)

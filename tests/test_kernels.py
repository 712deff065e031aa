"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs ref.py oracles
(interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ModelConfig
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref
from repro.models.layers import attention
from repro.models.layers import attention_core as core


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


    @staticmethod
    def _skipping_segments():
        """[2, 512] packed rows whose later blocks hold no key of the first
        segment: one full row, one with a pad tail."""
        return np.array([[1] * 200 + [2] * 250 + [3] * 62,
                         [1] * 130 + [2] * 300 + [0] * 82], np.int32)

    def test_loop_ranges_skip_other_segments(self):
        """Per Q block, the K blocks the loops visit: causal, and from the
        first block whose ids meet the Q block's; per K block, the Q blocks
        that attend to it."""
        (first, end), (kfirst, kend) = fa._ranges(
            jnp.asarray(self._skipping_segments()), 512, 128, 128, True)
        # row 0: Q blocks 0..3 hold ids {1}, {1, 2}, {2}, {2, 3}
        assert first.tolist()[:4] == [0, 0, 1, 1]
        assert end.tolist()[:4] == [1, 2, 3, 4]
        # row 1: the pad tail (id 0) meets every block up to the diagonal
        assert first.tolist()[4:] == [0, 0, 1, 0]
        # K block 0 of row 0 (id 1) is attended by Q blocks 0 and 1 only
        assert (kfirst.tolist()[0], kend.tolist()[0]) == (0, 2)
        # K block 2 of row 1 (ids 2 and pad) is attended by Q blocks 2, 3
        assert (kfirst.tolist()[6], kend.tolist()[6]) == (2, 4)

    @pytest.mark.parametrize("segs", ["none", "packed", "repeated"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("d", [64, 128])
    def test_gqa_expanded_matches_ref(self, d, dtype, segs):
        """The layer's use: 4 query heads over 2 kv heads gather-expanded
        before the kernel, packed segments or none; output and gradients
        (through the gather, onto the kv heads) against the f32 oracle.
        ``repeated``: an id that comes back after another segment, so the
        blocks skipped between its runs must hold no pair of it."""
        b, s, h, kvh = 2, 512, 4, 2
        ks = jax.random.split(jax.random.PRNGKey(5), 4)
        q = (0.5 * jax.random.normal(ks[0], (b, s, h, d))).astype(dtype)
        k, v = ((0.5 * jax.random.normal(kk, (b, s, kvh, d))).astype(dtype)
                for kk in ks[1:3])
        ct = jax.random.normal(ks[3], (b, s, h, d))
        seg = None
        if segs == "packed":
            seg = jnp.asarray(self._skipping_segments(), jnp.int32)
        elif segs == "repeated":
            seg = jnp.asarray(np.repeat([[1, 2, 1, 3], [2, 1, 2, 2]],
                                        s // 4, axis=1), jnp.int32)
        hmap = core.head2group(h, kvh)
        fold = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731

        def lk(q, k, v):
            o = ops.flash_attention(q, core.expand_kv(k, hmap),
                                    core.expand_kv(v, hmap), segment_ids=seg)
            return jnp.sum(o.astype(jnp.float32) * ct), o

        def lr(q, k, v):
            o = ref.flash_attention(fold(q), fold(core.expand_kv(k, hmap)),
                                    fold(core.expand_kv(v, hmap)),
                                    segment_ids=seg).transpose(0, 2, 1, 3)
            return jnp.sum(o.astype(jnp.float32) * ct), o

        (_, ok), gk = jax.value_and_grad(lk, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
        (_, orf), gr = jax.value_and_grad(lr, argnums=(0, 1, 2),
                                          has_aux=True)(q, k, v)
        np.testing.assert_allclose(np.asarray(ok, np.float32),
                                   np.asarray(orf, np.float32), **_tol(dtype))
        bound = 1e-2 if dtype == jnp.bfloat16 else 1e-5
        for a, b_ in zip(gk, gr):
            a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
            assert np.linalg.norm(a - b_) <= bound * np.linalg.norm(b_)


def _counts():
    got = obs.metrics.snapshot().get("attention", {})
    return got.get("kernel_layers", 0), got.get("xla_layers", 0)


class TestAttentionKernelPath:
    """``attention.apply`` runs the flash kernel for plain causal training
    attention and ``chunked_attention`` for everything else."""

    CFG = ModelConfig(name="flash-layer", family="dense", num_layers=1,
                      d_model=128, num_heads=14, num_kv_heads=2, head_dim=64,
                      pad_heads_to=16, d_ff=256, vocab_size=32,
                      attn_bias=True, dtype="bfloat16")

    def _inputs(self, s=256):
        b = 2
        seg = np.zeros((b, s), np.int32)
        pos = np.zeros((b, s), np.int32)
        for row, cuts in enumerate(([0, 90, s - 56, s - 24], [0, 150, s])):
            for i, (lo, hi) in enumerate(zip(cuts[:-1], cuts[1:])):
                seg[row, lo:hi] = i + 1
                pos[row, lo:hi] = np.arange(hi - lo)
        params = attention.init(jax.random.PRNGKey(0), self.CFG)
        x = jax.random.normal(jax.random.PRNGKey(1), (b, s, 128),
                              jnp.bfloat16)
        return params, x, jnp.asarray(pos), jnp.asarray(seg)

    def test_kernel_path_matches_xla_path(self):
        params, x, pos, seg = self._inputs()
        ct = jax.random.normal(jax.random.PRNGKey(2), x.shape)
        runs = {}
        for mode in ("always", "never"):
            cfg = self.CFG.replace(use_pallas=mode)

            def loss(params, x):
                out = attention.apply(params, cfg, x, positions=pos,
                                      segment_ids=seg)
                return jnp.sum(out.astype(jnp.float32) * ct), out

            before = _counts()
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(params, x)
            after = _counts()
            runs[mode] = (out, grads, (after[0] - before[0],
                                       after[1] - before[1]))
        assert runs["always"][2] == (1, 0)
        assert runs["never"][2] == (0, 1)
        np.testing.assert_allclose(np.asarray(runs["always"][0], np.float32),
                                   np.asarray(runs["never"][0], np.float32),
                                   **_tol(jnp.bfloat16))
        for a, b_ in zip(jax.tree.leaves(runs["always"][1]),
                         jax.tree.leaves(runs["never"][1])):
            a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
            assert np.linalg.norm(a - b_) <= 2e-2 * np.linalg.norm(b_)
        # the padded heads (14 -> 16) stay zero-masked: no gradient
        gq = np.asarray(runs["always"][1][0]["wq"], np.float32)
        assert not gq[:, 14:].any()

    @pytest.mark.parametrize("case", ["prefix", "softcap", "untiled"])
    def test_other_masks_take_the_xla_path(self, case):
        params, x, pos, seg = self._inputs(s=200 if case == "untiled"
                                           else 256)
        cfg = self.CFG.replace(use_pallas="always")
        kw = {}
        if case == "prefix":
            kw = {"prefix_len": 16}
        elif case == "softcap":
            cfg = cfg.replace(attn_logit_softcap=30.0)
        before = _counts()
        out = attention.apply(params, cfg, x, **kw)
        after = _counts()
        assert (after[0] - before[0], after[1] - before[1]) == (0, 1)
        want = attention.apply(params, cfg.replace(use_pallas="never"), x,
                               **kw)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(want, np.float32))

    def test_encdec_decoder_takes_the_xla_path(self):
        """The encoder-decoder's decoder self-attention is plain causal, but
        keeps ``chunked_attention`` with the kernels on."""
        from repro.configs import get_smoke_config
        from repro.models import encdec
        cfg = get_smoke_config("seamless-m4t-medium").replace(
            use_pallas="always")
        p_l = encdec.dec_block_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, cfg.d_model),
                              jnp.dtype(cfg.dtype))
        enc_out = jax.random.normal(jax.random.PRNGKey(2),
                                    (2, 64, cfg.d_model), x.dtype)
        before = _counts()
        out, _ = encdec.dec_block_apply(p_l, cfg, x, enc_out)
        after = _counts()
        assert (after[0] - before[0], after[1] - before[1]) == (0, 1)
        want, _ = encdec.dec_block_apply(
            p_l, cfg.replace(use_pallas="never"), x, enc_out)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(want, np.float32))


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

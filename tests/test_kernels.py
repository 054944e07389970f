"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (histogram, lgc_compress_hist, maxabs, sparsify_ef,
                           thresholds_from_counts)
from repro.kernels import ref
from repro.kernels.swa_attention import swa_decode

SHAPES = [63, 128, 1000, 8192, 40_000]
DTYPES = [jnp.float32, jnp.bfloat16]


def _vec(n, dtype, seed=0, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), (n,)) * scale
            ).astype(dtype)


class TestMaxAbs:
    @pytest.mark.parametrize("n", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_jnp(self, n, dtype):
        x = _vec(n, dtype, seed=n)
        got = float(maxabs(x)[0, 0])
        want = float(jnp.max(jnp.abs(x.astype(jnp.float32))))
        assert got == pytest.approx(want, rel=1e-6)

    def test_all_zero(self):
        assert float(maxabs(jnp.zeros(256))[0, 0]) == 0.0

    @pytest.mark.parametrize("block_rows", [8, 64, 256])
    def test_block_sizes(self, block_rows):
        x = _vec(10_000, jnp.float32, seed=1)
        got = float(maxabs(x, block_rows=block_rows)[0, 0])
        assert got == pytest.approx(float(jnp.max(jnp.abs(x))), rel=1e-6)


class TestHistogram:
    @pytest.mark.parametrize("n", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, n, dtype):
        x = _vec(n, dtype, seed=n + 1)
        m = maxabs(x)
        got = histogram(x, m)
        want = ref.hist_counts(x.astype(jnp.float32), m.reshape(()))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_total_count_is_d(self):
        x = _vec(5000, jnp.float32, seed=2)
        c = histogram(x, maxabs(x))
        assert int(c.sum()) == 5000  # padding corrected

    def test_thresholds_monotone(self):
        x = _vec(4096, jnp.float32, seed=3)
        m = maxabs(x)
        thr = thresholds_from_counts(histogram(x, m), m,
                                     jnp.array([64, 256, 1024]))
        t = np.asarray(thr)
        assert t[0] >= t[1] >= t[2] >= 0


class TestSparsifyEF:
    @pytest.mark.parametrize("n", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, n, dtype):
        e = _vec(n, dtype, seed=n + 10, scale=0.1)
        d = _vec(n, dtype, seed=n + 11)
        u = e.astype(jnp.float32) + d.astype(jnp.float32)
        m = maxabs(u)
        cum_ks = jnp.array([max(1, n // 50), max(2, n // 10)], jnp.int32)
        thr = thresholds_from_counts(histogram(u, m), m, cum_ks)
        recv = jnp.array([1, 1], jnp.int32)
        g, en = sparsify_ef(e, d, thr, recv)
        g_r, en_r = ref.hist_layered_sparsify(u, thr, recv)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_r),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(en), np.asarray(en_r),
                                   rtol=1e-6, atol=1e-6)

    def test_channel_drop(self):
        n = 4096
        e, d = jnp.zeros(n), _vec(n, jnp.float32, seed=4)
        m = maxabs(d)
        cum_ks = jnp.array([100, 400], jnp.int32)
        thr = thresholds_from_counts(histogram(d, m), m, cum_ks)
        g_all, _ = sparsify_ef(e, d, thr, jnp.array([1, 1]))
        g_base, e_base = sparsify_ef(e, d, thr, jnp.array([1, 0]))
        assert int((g_base != 0).sum()) < int((g_all != 0).sum())
        # dropped mass conserved in memory: g + e' == u always
        np.testing.assert_allclose(np.asarray(g_base + e_base),
                                   np.asarray(d), rtol=1e-6)


class TestFusedPipeline:
    @pytest.mark.parametrize("n", [1000, 8192, 65_536])
    @pytest.mark.parametrize("c", [1, 2, 3, 4])
    def test_matches_ref_pipeline(self, n, c):
        e = _vec(n, jnp.float32, seed=n + c, scale=0.2)
        d = _vec(n, jnp.float32, seed=n + c + 1)
        ks = np.linspace(n // 100 + 1, n // 10 + 2, c).astype(np.int32)
        cum_ks = jnp.array(np.cumsum(ks), jnp.int32)
        recv = jnp.ones((c,), jnp.int32)
        g, en = lgc_compress_hist(e, d, cum_ks, recv)
        g_r, en_r = ref.hist_lgc_compress(e, d, cum_ks, recv)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_r), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(en), np.asarray(en_r), rtol=1e-6)

    def test_selection_near_k(self):
        """Histogram selection overshoot is bounded by one bin's mass."""
        n = 50_000
        d = _vec(n, jnp.float32, seed=9)
        cum_ks = jnp.array([2500], jnp.int32)
        g, _ = lgc_compress_hist(jnp.zeros(n), d, cum_ks, jnp.array([1]))
        nsel = int((g != 0).sum())
        assert nsel >= 2500
        assert nsel <= 2500 + n // 64  # loose bin-mass bound

    def test_covers_exact_topk(self):
        """Histogram selection is a superset of exact Top_K selection."""
        n = 20_000
        d = _vec(n, jnp.float32, seed=10)
        cum_ks = jnp.array([1000], jnp.int32)
        g, _ = lgc_compress_hist(jnp.zeros(n), d, cum_ks, jnp.array([1]))
        g_exact, _ = ref.exact_lgc_compress(jnp.zeros(n), d, cum_ks,
                                            jnp.array([1]))
        exact_support = np.asarray(g_exact != 0)
        got_support = np.asarray(g != 0)
        assert np.all(got_support[exact_support])


class TestPerLayerHistParity:
    """Per-layer candidate selection (repro.core.compressor) routes big
    segments through the Pallas kernels and small ones through ref.py; the
    routing threshold must be invisible -- kernels and oracles are
    bit-equal -- including at the 10^6-element scale the routing exists
    for."""

    def test_parity_at_1e6(self):
        from repro.core.compressor import (layer_budgets,
                                           per_layer_candidates_hist)
        n_big, n_small = 1_000_000, 30_000
        u = jnp.concatenate([_vec(n_big, jnp.float32, seed=20),
                             _vec(n_small, jnp.float32, seed=21)])
        slices = [("big", 0, n_big), ("small", n_big, n_big + n_small)]
        b = layer_budgets("size_prop", u, slices, jnp.int32(4096),
                          u.shape[0])
        via_pallas = per_layer_candidates_hist(u, slices, b)   # big->kernel
        via_ref = per_layer_candidates_hist(u, slices, b,
                                            pallas_min_elems=10 ** 9)
        np.testing.assert_array_equal(np.asarray(via_pallas),
                                      np.asarray(via_ref))
        # hist selection keeps >= budget per layer, overshoot one bin
        for i, (_, lo, hi) in enumerate(slices):
            nsel = int(np.asarray(via_pallas[lo:hi]).sum())
            assert nsel >= int(b[i])
            assert nsel <= int(b[i]) + (hi - lo) // 64

    def test_kernel_vs_ref_at_1e6(self):
        x = _vec(1_000_000, jnp.float32, seed=22)
        m = maxabs(x)
        np.testing.assert_array_equal(
            np.asarray(histogram(x, m)),
            np.asarray(ref.hist_counts(x, m.reshape(()))))
        assert float(m[0, 0]) == float(ref.hist_maxabs(x))


class TestSWADecode:
    @pytest.mark.parametrize("shape", [(2, 4, 512, 64), (1, 8, 1024, 128),
                                       (4, 2, 256, 32)])
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_ref(self, shape, dtype):
        b, h, w, dh = shape
        ks = jax.random.split(jax.random.PRNGKey(b * h), 4)
        q = jax.random.normal(ks[0], (b, h, dh), dtype)
        k = jax.random.normal(ks[1], (b, h, w, dh), dtype)
        v = jax.random.normal(ks[2], (b, h, w, dh), dtype)
        ln = jax.random.randint(ks[3], (b,), 1, w + 1)
        got = np.asarray(swa_decode(q, k, v, ln, chunk=128), np.float32)
        want = np.asarray(ref.swa_decode_ref(q, k, v, ln), np.float32)
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    def test_short_length_ignores_tail(self):
        """Garbage beyond `length` must not influence the output."""
        b, h, w, dh = 1, 2, 256, 64
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (b, h, dh))
        k = jax.random.normal(ks[1], (b, h, w, dh))
        v = jax.random.normal(ks[2], (b, h, w, dh))
        ln = jnp.array([100])
        out1 = swa_decode(q, k, v, ln, chunk=64)
        k2 = k.at[:, :, 100:].set(1e9)
        v2 = v.at[:, :, 100:].set(-1e9)
        out2 = swa_decode(q, k2, v2, ln, chunk=64)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-6)


class TestInterpretMode:
    """The platform, not a flag, decides how the kernels run."""

    @pytest.mark.parametrize("platform,mode", [("cpu", True), ("tpu", False)])
    def test_platform_picks_the_mode(self, monkeypatch, platform, mode):
        from repro.kernels.platform import resolve_interpret
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert resolve_interpret() is mode
        assert resolve_interpret(not mode) is (not mode)   # explicit wins

    def test_unknown_platform_raises(self, monkeypatch):
        from repro.kernels.platform import resolve_interpret
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(ValueError, match="'gpu'"):
            resolve_interpret()

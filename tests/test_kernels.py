"""Per-kernel correctness: sweep shapes/dtypes in interpret mode and assert
allclose against the ref.py pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(0)


def _lif_inputs(shape, dtype, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    u = jax.random.normal(k1, shape, dtype)
    s = (jax.random.uniform(k2, shape) < 0.3).astype(dtype)
    c = jax.random.normal(k3, shape, dtype)
    return u, s, c


class TestLIFKernel:
    @pytest.mark.parametrize("shape", [(8, 512), (1, 100), (3, 700), (16, 2048),
                                       (5, 1)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, shape, dtype):
        u, s, c = _lif_inputs(shape, dtype)
        got_u, got_s = ops.lif_step(u, s, c, beta=0.9, threshold=1.0)
        want_u, want_s = ref.lif_step_ref(u, s, c, beta=0.9, threshold=1.0)
        tol = 1e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got_u, np.float32),
                                   np.asarray(want_u, np.float32), atol=tol)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))

    @pytest.mark.parametrize("reset", ["subtract", "zero"])
    @pytest.mark.parametrize("beta,threshold", [(0.5, 1.0), (0.95, 0.5),
                                                (0.23, 2.0)])
    def test_parameter_sweep(self, reset, beta, threshold):
        u, s, c = _lif_inputs((4, 300), jnp.float32, seed=7)
        got = ops.lif_step(u, s, c, beta=beta, threshold=threshold,
                           reset_mechanism=reset)
        want = ref.lif_step_ref(u, s, c, beta=beta, threshold=threshold,
                                reset_mechanism=reset)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))

    def test_agrees_with_core_lif(self):
        """The kernel implements the same forward as repro.core.lif."""
        from repro.core.lif import LIFParams, lif_step as core_step
        u, s, c = _lif_inputs((2, 64), jnp.float32, seed=3)
        got_u, got_s = ops.lif_step(u, s, c, beta=0.9, threshold=1.0)
        want_u, want_s = core_step(u, s, c, LIFParams(beta=0.9, threshold=1.0))
        np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


class TestSpikeGemm:
    @pytest.mark.parametrize("shape", [(128, 128, 128), (256, 512, 128),
                                       (100, 333, 77), (8, 1024, 64),
                                       (1, 784, 500)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
    def test_matches_dense_ref(self, shape, dtype, density):
        M, K, N = shape
        k1, k2 = jax.random.split(jax.random.key(42))
        s = (jax.random.uniform(k1, (M, K)) < density).astype(dtype)
        w = (jax.random.normal(k2, (K, N)) * 0.1).astype(dtype)
        got = ops.spike_gemm(s, w)
        want = ref.spike_gemm_ref(s, w)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=tol, rtol=tol)

    @pytest.mark.parametrize("blocks", [(128, 128, 128), (8, 128, 256)])
    def test_block_shape_sweep(self, blocks):
        bm, bk, bn = blocks
        k1, k2 = jax.random.split(jax.random.key(1))
        s = (jax.random.uniform(k1, (64, 300)) < 0.1).astype(jnp.float32)
        w = jax.random.normal(k2, (300, 200), jnp.float32)
        got = ops.spike_gemm(s, w, block_m=bm, block_k=bk, block_n=bn)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.spike_gemm_ref(s, w)),
                                   atol=1e-4)

    def test_all_zero_input_skips_everything(self):
        s = jnp.zeros((128, 256), jnp.float32)
        w = jnp.ones((256, 128), jnp.float32)
        out = ops.spike_gemm(s, w)
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        assert ops.skip_fraction(s) == 1.0

    @pytest.mark.parametrize("seed", range(3))
    def test_flags_complete_and_sound(self, seed):
        """Property: a flag is 0 iff its tile holds no spikes."""
        rng = np.random.default_rng(seed)
        s = (rng.random((256, 512)) < 0.01).astype(np.float32)
        flags = np.asarray(ref.block_flags_ref(jnp.asarray(s), 128, 128))
        tiles = s.reshape(2, 128, 4, 128).sum((1, 3))
        np.testing.assert_array_equal(flags, (tiles > 0).astype(np.int32))

    def test_uniform_sparsity_rarely_skips(self):
        """Documenting the tile-granularity gap: uniformly-spread 1% firing
        leaves essentially no 8x128 tile empty (see ops.py commentary)."""
        rng = np.random.default_rng(0)
        s = (rng.random((8, 4096)) < 0.01).astype(np.float32)
        frac = ops.skip_fraction(jnp.asarray(s), block_m=8, block_k=128)
        assert frac < 0.05

    def test_profiled_permutation_unlocks_skips(self):
        """Heavy-tailed firing + profile-guided permutation -> real skips,
        with bit-exact results."""
        rng = np.random.default_rng(0)
        K = 4096
        rates = np.where(rng.random(K) < 0.85, 0.001, 0.15)  # heavy tail
        s = (rng.random((32, K)) < rates).astype(np.float32)
        w = rng.normal(size=(K, 256)).astype(np.float32) * 0.1
        base_skip = ops.skip_fraction(jnp.asarray(s), 8, 128)
        perm = ops.firing_rate_permutation(jnp.asarray(s.mean(0)))
        sp, wp = ops.apply_permutation(jnp.asarray(s), jnp.asarray(w), perm)
        perm_skip = ops.skip_fraction(sp, 8, 128)
        assert perm_skip > base_skip + 0.3, (base_skip, perm_skip)
        out = ops.spike_gemm_profiled(jnp.asarray(s), jnp.asarray(w), perm,
                                      block_m=8)
        np.testing.assert_allclose(np.asarray(out), s @ w, atol=1e-3)

    def test_gradient_path_via_ref(self):
        """The oracle's implicit gradient (what the custom_vjp backward
        reproduces — see tests/test_kernel_grads.py for the kernel side)."""
        s = (jax.random.uniform(jax.random.key(0), (16, 32)) < 0.3
             ).astype(jnp.float32)
        w = jax.random.normal(jax.random.key(1), (32, 8))
        g = jax.grad(lambda w: ref.spike_gemm_ref(s, w).sum())(w)
        np.testing.assert_allclose(np.asarray(g),
                                   np.asarray(jnp.broadcast_to(s.sum(0)[:, None],
                                                               (32, 8))))


class TestSpikeGemmBwdKernels:
    """Block-skip backward kernels (spike_gemm_bwd.py) vs the dense
    oracles: dW = Sᵀ·g on the forward's flags, dS = g·Wᵀ on any-nonzero
    cotangent occupancy."""

    @pytest.mark.parametrize("shape", [(128, 128, 128), (100, 333, 77),
                                       (8, 1024, 64), (1, 784, 500)])
    @pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
    def test_dw_matches_dense_ref(self, shape, density):
        M, K, N = shape
        k1, k2 = jax.random.split(jax.random.key(13))
        s = (jax.random.uniform(k1, (M, K)) < density).astype(jnp.float32)
        g = jax.random.normal(k2, (M, N), jnp.float32)
        got = ops.spike_gemm_bwd_dw(s, g, block_m=8)
        _, want = ref.spike_gemm_bwd_ref(s, jnp.zeros((K, N)), g)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("shape", [(128, 128, 128), (100, 333, 77),
                                       (8, 1024, 64)])
    def test_ds_matches_dense_ref(self, shape):
        M, K, N = shape
        k1, k2 = jax.random.split(jax.random.key(14))
        g = jax.random.normal(k1, (M, N), jnp.float32)
        w = jax.random.normal(k2, (K, N), jnp.float32) * 0.1
        got = ops.spike_gemm_bwd_ds(g, w, block_m=8)
        want, _ = ref.spike_gemm_bwd_ref(jnp.zeros((M, K)), w, g)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    def test_dw_skip_vs_dense_flags_bitident(self):
        """Property behind the sparse backward: running the dW kernel with
        the real (skipping) flags is bit-identical to running it with every
        flag forced on — a skipped tile contributes exactly zero."""
        k1, k2 = jax.random.split(jax.random.key(15))
        s = (jax.random.uniform(k1, (40, 700)) < 0.2).astype(jnp.float32)
        s = s.at[8:24, :].set(0.0).at[:, 256:512].set(0.0)
        g = jax.random.normal(k2, (40, 60), jnp.float32)
        flags = ops.block_flags(s, block_m=8, block_k=128)
        assert float(flags.mean()) < 1.0          # something is skipped
        a = ops.spike_gemm_bwd_dw(s, g, flags=flags, block_m=8)
        b = ops.spike_gemm_bwd_dw(s, g, flags=jnp.ones_like(flags),
                                  block_m=8)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_ds_skip_vs_dense_flags_bitident(self):
        """Same property on the dS side, gated on cotangent occupancy:
        zero out whole (8, 128) tiles of g and the gated kernel matches the
        all-flags-on kernel bit-for-bit."""
        k1, k2 = jax.random.split(jax.random.key(16))
        g = jax.random.normal(k1, (24, 256), jnp.float32)
        g = g.at[8:16, :].set(0.0).at[:, 128:].set(0.0)
        w = jax.random.normal(k2, (300, 256), jnp.float32) * 0.1
        gflags = ops.cotangent_block_flags(g, block_m=8, block_n=128)
        assert float(gflags.mean()) < 1.0
        a = ops.spike_gemm_bwd_ds(g, w, gflags=gflags, block_m=8)
        b = ops.spike_gemm_bwd_ds(g, w, gflags=jnp.ones_like(gflags),
                                  block_m=8)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_cancelling_cotangent_tile_not_skipped(self):
        """A signed tile whose entries sum to zero still holds work: the
        sum>0 spike-flag reduction would wrongly skip it, the any-nonzero
        cotangent reduction must not (and dS must stay exact)."""
        g = jnp.zeros((8, 256), jnp.float32)
        g = g.at[0, 0].set(1.0).at[1, 1].set(-1.0)   # tile sums to zero
        w = jnp.asarray(np.random.default_rng(0).normal(size=(64, 256)),
                        dtype=jnp.float32)
        spike_style = np.asarray(ref.block_flags_ref(g, 8, 128))
        any_style = np.asarray(ref.block_flags_any_ref(g, 8, 128))
        assert spike_style[0, 0] == 0 and any_style[0, 0] == 1
        got = ops.spike_gemm_bwd_ds(g, w, block_m=8)
        want, _ = ref.spike_gemm_bwd_ref(jnp.zeros((8, 64)), w, g)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_all_zero_cotangent_skips_everything(self):
        g = jnp.zeros((16, 128), jnp.float32)
        w = jnp.ones((256, 128), jnp.float32)
        out = ops.spike_gemm_bwd_ds(g, w, block_m=8)
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        assert float(ops.cotangent_block_flags(g, block_m=8,
                                               block_n=128).sum()) == 0.0


class TestFusedGemmLifKernel:
    """spike_gemm_fused.py forward vs the composed oracle."""

    @pytest.mark.parametrize("shape", [(8, 128, 128), (16, 300, 50),
                                       (5, 100, 33), (1, 784, 500)])
    @pytest.mark.parametrize("reset", ["subtract", "zero"])
    def test_matches_composed_ref(self, shape, reset):
        M, K, N = shape
        keys = jax.random.split(jax.random.key(21), 5)
        s = (jax.random.uniform(keys[0], (M, K)) < 0.2).astype(jnp.float32)
        w = jax.random.normal(keys[1], (K, N)) * 0.1
        b = jax.random.normal(keys[2], (N,)) * 0.1
        u0 = jax.random.normal(keys[3], (M, N))
        s0 = (jax.random.uniform(keys[4], (M, N)) < 0.3).astype(jnp.float32)
        got_u, got_s = ops.spike_gemm_lif_step(
            s, w, b, u0, s0, beta=0.9, threshold=1.0,
            reset_mechanism=reset, block_m=8)
        want_u, want_s = ref.spike_gemm_lif_ref(
            s, w, b, u0, s0, beta=0.9, threshold=1.0,
            reset_mechanism=reset)
        np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))

    def test_all_zero_train_is_pure_lif(self):
        """Every spike tile skipped: the accumulate contributes nothing and
        the epilogue reduces to the bare LIF update on the bias current."""
        M, K, N = 8, 256, 64
        s = jnp.zeros((M, K), jnp.float32)
        w = jax.random.normal(jax.random.key(0), (K, N))
        b = jnp.full((N,), 0.3, jnp.float32)
        u0 = jax.random.normal(jax.random.key(1), (M, N))
        s0 = jnp.zeros((M, N), jnp.float32)
        got_u, got_s = ops.spike_gemm_lif_step(s, w, b, u0, s0,
                                               beta=0.9, threshold=1.0,
                                               block_m=8)
        want_u, want_s = ref.lif_step_ref(
            u0, s0, jnp.broadcast_to(b, (M, N)), beta=0.9, threshold=1.0)
        np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


class TestKernelPlumbing:
    """Property/edge tests for the wrapper layer the training path rides:
    padding, occupancy flags, skip_fraction caching, PENC edges, and the
    profiled permutation's exact-equality invariance."""

    @pytest.mark.parametrize("shape,mults", [((8, 128), (8, 128)),
                                             ((64, 512), (8, 128)),
                                             ((128, 256), (128, 128))])
    def test_pad_to_noop_on_aligned_shapes(self, shape, mults):
        x = jnp.ones(shape)
        assert ops._pad_to(x, mults) is x       # no copy, not even identity

    def test_pad_to_pads_with_zeros(self):
        x = jnp.ones((5, 100))
        padded = ops._pad_to(x, (8, 128))
        assert padded.shape == (8, 128)
        np.testing.assert_array_equal(np.asarray(padded[:5, :100]), 1.0)
        assert float(padded.sum()) == 500.0     # padding contributed nothing

    @pytest.mark.parametrize("seed", range(3))
    def test_skip_fraction_consistent_with_flags(self, seed):
        """skip_fraction (jitted) == 1 - mean(block_flags_ref) on the padded
        matrix, for ragged shapes."""
        rng = np.random.default_rng(seed)
        s = jnp.asarray((rng.random((37, 300)) < 0.02).astype(np.float32))
        flags = ref.block_flags_ref(ops._pad_to(s, (8, 128)), 8, 128)
        want = float(1.0 - np.asarray(flags, np.float32).mean())
        assert ops.skip_fraction(s, 8, 128) == pytest.approx(want, abs=1e-7)

    def test_spike_gemm_reuses_caller_flags(self):
        """Precomputed block_flags short-circuit the in-call reduction and
        give bit-identical output."""
        k1, k2 = jax.random.split(jax.random.key(5))
        s = (jax.random.uniform(k1, (40, 300)) < 0.05).astype(jnp.float32)
        w = jax.random.normal(k2, (300, 150), jnp.float32)
        flags = ops.block_flags(s, block_m=8, block_k=128)
        got = ops.spike_gemm(s, w, flags=flags, block_m=8)
        want = ops.spike_gemm(s, w, block_m=8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_spike_gemm_rejects_mismatched_flags(self):
        s = jnp.ones((16, 256), jnp.float32)
        w = jnp.ones((256, 128), jnp.float32)
        bad = jnp.ones((1, 1), jnp.int32)
        with pytest.raises(ValueError, match="tile grid"):
            ops.spike_gemm(s, w, flags=bad, block_m=8)

    def test_penc_empty_rows(self):
        """Rows with no spikes compact to all -1 addresses and count 0."""
        s = jnp.zeros((4, 96), jnp.float32)
        idx, cnt = ops.penc_compact(s, capacity=32)
        np.testing.assert_array_equal(np.asarray(idx), -1)
        np.testing.assert_array_equal(np.asarray(cnt), 0)

    def test_penc_mixed_overflow_and_empty(self):
        """Capacity overflow (dense row) and empty row side by side: the
        dense row keeps its first ``capacity`` addresses but reports the
        true spike count; the empty row stays untouched."""
        s = jnp.stack([jnp.ones(64, jnp.float32), jnp.zeros(64, jnp.float32)])
        idx, cnt = ops.penc_compact(s, capacity=8)
        np.testing.assert_array_equal(np.asarray(idx[0]), np.arange(8))
        assert int(cnt[0]) == 64
        np.testing.assert_array_equal(np.asarray(idx[1]), -1)
        assert int(cnt[1]) == 0

    @pytest.mark.parametrize("seed", range(3))
    def test_profiled_permutation_exact_equality(self, seed):
        """Permutation invariance holds EXACTLY, not just to tolerance:
        with weights on a 1/256 grid every accumulate is an exact fp32 sum,
        so reordering the heavy-tailed pre-synaptic axis cannot change a
        single bit of the output."""
        rng = np.random.default_rng(seed)
        K = 1024
        rates = np.where(rng.random(K) < 0.8, 0.002, 0.2)
        s = jnp.asarray((rng.random((24, K)) < rates).astype(np.float32))
        w = jnp.asarray(rng.integers(-64, 64, size=(K, 96)) / 256.0,
                        dtype=jnp.float32)
        perm = ops.firing_rate_permutation(s.mean(0))
        got = ops.spike_gemm_profiled(s, w, perm, block_m=8)
        want = ops.spike_gemm(s, w, block_m=8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # and the permutation is a real permutation of the axis
        assert sorted(np.asarray(perm).tolist()) == list(range(K))


class TestPackedFlags:
    """The tile flags reach SMEM bit-packed, 32 tiles per int32 word."""

    @pytest.mark.parametrize("shape", [(1, 1), (3, 7), (8192, 1), (33, 2),
                                       (4, 8)])
    def test_every_tile_reads_back(self, shape):
        from repro.kernels.spike_gemm import FLAG_BITS, pack_flags
        flags = jax.random.bernoulli(KEY, 0.5, shape).astype(jnp.int32)
        flags = flags.at[-1, -1].set(1)             # a set bit 31 included
        words = np.asarray(pack_flags(flags)).astype(np.uint32)
        assert words.shape == (-(-flags.size // FLAG_BITS),)
        t = np.arange(flags.size)
        bits = (words[t // FLAG_BITS] >> (t % FLAG_BITS)) & 1
        np.testing.assert_array_equal(bits.reshape(shape), np.asarray(flags))

    def test_over_the_smem_budget_raises(self):
        from repro.kernels.spike_gemm import FLAG_SMEM_BYTES, pack_flags
        tiles = 8 * FLAG_SMEM_BYTES + 1              # one word too many
        with pytest.raises(ValueError, match="SMEM budget"):
            pack_flags(jnp.zeros((tiles, 1), jnp.int32))


class TestSpikeConvKernel:
    """Patch-tiled block-skip convolution (spike_conv.py) vs the dense
    ``lax.conv`` oracle — bit-for-bit on 1/256-grid weights, because with
    grid operands every fp32 accumulate is exact and tile order (or
    skipping) cannot change a single bit."""

    @staticmethod
    def _inputs(shape, kernel, cout, density, seed=0):
        rng = np.random.default_rng(seed)
        B, H, W, C = shape
        s = jnp.asarray((rng.random((B, H, W, C)) < density)
                        .astype(np.float32))
        w = jnp.asarray(rng.integers(-64, 64, (kernel, kernel, C, cout))
                        / 256.0, dtype=jnp.float32)
        return s, w

    @pytest.mark.parametrize("shape", [(2, 9, 9, 3), (1, 12, 10, 2),
                                       (3, 8, 8, 1), (2, 7, 11, 2)])
    @pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
    @pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                                (1, "VALID"), (2, "VALID")])
    def test_matches_dense_conv_oracle(self, shape, density, stride, padding):
        """Non-tile-multiple spatial shapes (M = B·OH·OW and K = KH·KW·C both
        ragged against the 8x128 grid): exact equality with XLA's conv."""
        s, w = self._inputs(shape, 3, 5, density)
        got = ops.spike_conv(s, w, stride=stride, padding=padding,
                             block_m=8)
        want = ref.spike_conv_ref(s, w, stride=stride, padding=padding)
        assert got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_skip_vs_all_ones_flags_bitident(self):
        """Running the conv kernel with the real (skipping) patch flags is
        bit-identical to forcing every flag on: an empty patch tile holds
        receptive fields that saw no spikes and contributes exactly zero."""
        rng = np.random.default_rng(3)
        s = (rng.random((4, 16, 16, 2)) < 0.2).astype(np.float32)
        s[:2] = 0.0                      # whole samples silent -> empty tiles
        s = jnp.asarray(s)
        w = jnp.asarray(rng.integers(-64, 64, (3, 3, 2, 6)) / 256.0,
                        dtype=jnp.float32)
        patches = ops.conv_patches(s, 3, 3, 1, "SAME")
        flags = ops.block_flags(patches, block_m=8, block_k=128)
        assert float(flags.mean()) < 1.0          # something is skipped
        a = ops.spike_conv(s, w, flags=flags, block_m=8)
        b = ops.spike_conv(s, w, flags=jnp.ones_like(flags), block_m=8)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_all_zero_train_is_pure_bias(self):
        """Every patch tile skipped: the layer current reduces to the bias
        broadcast — checked on the routed snn path, not just the raw op."""
        from repro.core import snn
        s = jnp.zeros((3, 10, 10, 2), jnp.float32)
        w = jax.random.normal(jax.random.key(0), (3, 3, 2, 4))
        out = ops.spike_conv(s, w, block_m=8)
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        patches = ops.conv_patches(s, 3, 3, 1, "SAME")
        assert ops.skip_fraction(patches, 8, 128) == 1.0
        spec = snn.Conv(4, 3)
        p = {"w": w, "b": jnp.full((4,), 0.25, jnp.float32)}
        cur = snn._layer_current(spec, p, s, matmul_backend="spike_gemm")
        np.testing.assert_array_equal(np.asarray(cur), 0.25)

    def test_rejects_mismatched_flags(self):
        s = jnp.ones((2, 8, 8, 2), jnp.float32)
        w = jnp.ones((3, 3, 2, 4), jnp.float32)
        bad = jnp.ones((1, 1), jnp.int32)
        with pytest.raises(ValueError, match="tile grid"):
            ops.spike_conv(s, w, flags=bad, block_m=8)

    @pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                                (1, "VALID"), (2, "VALID")])
    def test_patch_matrix_is_binary_and_flags_exact(self, stride, padding):
        """The im2col view of a {0,1} spike tensor is itself {0,1}, so the
        sum>0 occupancy gate stays exact on the patch matrix (DESIGN.md §13):
        a flag is 0 iff its tile holds no spikes."""
        rng = np.random.default_rng(11)
        s = jnp.asarray((rng.random((2, 11, 9, 3)) < 0.1).astype(np.float32))
        patches = np.asarray(ops.conv_patches(s, 3, 3, stride, padding))
        assert set(np.unique(patches)) <= {0.0, 1.0}
        padded = np.asarray(ops._pad_to(jnp.asarray(patches), (8, 128)))
        flags = np.asarray(ops.block_flags(jnp.asarray(patches),
                                           block_m=8, block_k=128))
        fm, fk = flags.shape
        tiles = padded.reshape(fm, 8, fk, 128).sum((1, 3))
        np.testing.assert_array_equal(flags, (tiles > 0).astype(np.int32))


class TestPENCCompact:
    """PENC address-extraction kernel vs oracle vs the serial validator."""

    @pytest.mark.parametrize("shape", [(8, 128), (3, 100), (16, 777)])
    @pytest.mark.parametrize("density", [0.0, 0.1, 0.9])
    def test_matches_ref(self, shape, density):
        B, N = shape
        s = (jax.random.uniform(jax.random.key(7), (B, N)) < density
             ).astype(jnp.float32)
        cap = min(N, 128)
        got_idx, got_cnt = ops.penc_compact(s, capacity=cap)
        want_idx, want_cnt = ref.penc_compact_ref(s, cap)
        np.testing.assert_array_equal(np.asarray(got_idx),
                                      np.asarray(want_idx))
        np.testing.assert_array_equal(np.asarray(got_cnt),
                                      np.asarray(want_cnt))

    def test_matches_serial_penc(self):
        """Same semantics as the hardware validator's chunked priority
        encoder when capacity covers the row."""
        from repro.core import validate
        rng = np.random.default_rng(3)
        bits = (rng.random((4, 250)) < 0.2).astype(np.float32)
        idx, cnt = ops.penc_compact(jnp.asarray(bits), capacity=250)
        for b in range(4):
            serial = validate.penc_compress(bits[b].astype(np.int64))
            got = [int(i) for i in np.asarray(idx[b]) if i >= 0]
            assert got == serial
            assert int(cnt[b]) == len(serial)

    def test_capacity_drops_overflow(self):
        s = jnp.ones((1, 64), jnp.float32)
        idx, cnt = ops.penc_compact(s, capacity=16)
        np.testing.assert_array_equal(np.asarray(idx[0]), np.arange(16))
        assert int(cnt[0]) == 64    # count reports true spikes

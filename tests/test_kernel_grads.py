"""Gradient-parity harness for the spike_gemm training path.

The kernel routes (``ops.spike_gemm_train``: block-skip Pallas forward AND
backward via custom_vjp; ``ops.spike_gemm_lif_step``: the fused GEMM+LIF
scan-step kernel) must be drop-in replacements for the pure-jnp matmul on
the BPTT hot path: same forward values, same cotangents, through surrogate
gradients and ``lax.scan``.  These tests lock that contract down at four
levels — the custom_vjps themselves (``jax.test_util.check_grads``),
bit-for-bit skip-exactness of the sparse backward on grid-quantized
operands, single-gemm loss gradients across non-tile-multiple shapes and
degenerate spike trains, and full SNN loss gradients under both LIF reset
mechanisms for every backend in ``snn.MATMUL_BACKENDS``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from repro.core import snn, train_snn
from repro.core.lif import LIFParams
from repro.kernels import ops, ref


def _spikes(shape, density, seed=0, dtype=jnp.float32):
    if density == 0.0:
        return jnp.zeros(shape, dtype)
    if density == 1.0:
        return jnp.ones(shape, dtype)
    return (jax.random.uniform(jax.random.key(seed), shape) < density
            ).astype(dtype)


def _assert_tree_allclose(a, b, atol=1e-5, rtol=1e-5):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), atol=atol, rtol=rtol), a, b)


class TestCustomVJP:
    """The custom_vjp contract on the gemm itself."""

    def test_check_grads_rev(self):
        """jax.test_util.check_grads on the custom_vjp (rev mode; the dense
        50% train keeps every occupancy flag stable under the numeric
        perturbations, so the block-skip forward stays the linear map)."""
        s = _spikes((16, 40), 0.5, seed=3)
        w = jax.random.normal(jax.random.key(4), (40, 12)) * 0.1
        check_grads(ops.spike_gemm_train, (s, w), order=1, modes=["rev"],
                    atol=1e-2, rtol=1e-2)

    @pytest.mark.parametrize("shape", [(32, 100, 10), (8, 784, 128),
                                       (5, 64, 3)])
    @pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
    def test_gemm_grads_match_jnp(self, shape, density):
        """value_and_grad of a scalar loss through the kernel path equals
        the jnp path, including non-tile-multiple K/N and all-zero /
        all-one spike trains."""
        M, K, N = shape
        s = _spikes((M, K), density, seed=M)
        w = jax.random.normal(jax.random.key(K), (K, N)) * 0.1

        def loss(fn):
            return lambda s, w: jnp.sum(jnp.tanh(fn(s, w)))

        (va, ga) = jax.value_and_grad(loss(ops.spike_gemm_train),
                                      argnums=(0, 1))(s, w)
        (vb, gb) = jax.value_and_grad(loss(lambda s, w: s @ w),
                                      argnums=(0, 1))(s, w)
        np.testing.assert_allclose(float(va), float(vb), rtol=1e-6)
        # forward tile-order rounding shifts the tanh' factor slightly at
        # saturation; the cotangent math itself is the exact dense reference
        _assert_tree_allclose(ga, gb, atol=1e-4, rtol=1e-4)

    def test_zero_train_zero_weight_grad(self):
        """An all-zero train skips every dW tile, yet the backward still
        produces the exact dense cotangents (dW = S^T g = 0, dS = g W^T)."""
        s = jnp.zeros((16, 256), jnp.float32)
        w = jax.random.normal(jax.random.key(0), (256, 64))
        ds, dw = jax.grad(lambda s, w: ops.spike_gemm_train(s, w).sum(),
                          argnums=(0, 1))(s, w)
        np.testing.assert_array_equal(np.asarray(dw), 0.0)
        np.testing.assert_allclose(np.asarray(ds),
                                   np.broadcast_to(np.asarray(w.sum(1)),
                                                   (16, 256)),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_through_permutation(self):
        """The profiled permutation is applied outside the custom_vjp; the
        chain rule through the gathers must reproduce unpermuted grads."""
        s = _spikes((8, 200), 0.2, seed=9)
        w = jax.random.normal(jax.random.key(10), (200, 16)) * 0.1
        perm = ops.firing_rate_permutation(s.mean(0))

        def loss_perm(w):
            return ops.spike_gemm_train(s[:, perm], w[perm, :]).sum()

        g_perm = jax.grad(loss_perm)(w)
        g_ref = jax.grad(lambda w: (s @ w).sum())(w)
        np.testing.assert_allclose(np.asarray(g_perm), np.asarray(g_ref),
                                   atol=1e-6)


class TestSparseBackwardExactness:
    """Skipping is EXACT, not approximate: a skipped tile is all-zero and
    contributes exactly zero to the cotangent accumulate (DESIGN.md §12)."""

    @pytest.mark.parametrize("shape", [(32, 100, 10), (8, 784, 128),
                                       (5, 64, 3), (24, 333, 96)])
    @pytest.mark.parametrize("density", [0.0, 0.15, 1.0])
    def test_bwd_bitexact_vs_dense_on_grid(self, shape, density):
        """Block-skip dW/dS equal the dense jnp cotangents BIT-FOR-BIT
        across non-tile-multiple shapes.  Operands on a 1/256 grid make
        every accumulate an exact fp32 sum (the idiom of
        test_kernels.test_profiled_permutation_exact_equality), so
        summation order is irrelevant and any deviation could only come
        from a wrongly-skipped tile."""
        M, K, N = shape
        rng = np.random.default_rng(M + N)
        s = _spikes((M, K), density, seed=M)
        w = jnp.asarray(rng.integers(-64, 64, (K, N)) / 256.0,
                        dtype=jnp.float32)
        g = jnp.asarray(rng.integers(-64, 64, (M, N)) / 256.0,
                        dtype=jnp.float32)
        _, vjp = jax.vjp(
            lambda s, w: ops.spike_gemm_train(s, w, block_m=8), s, w)
        ds, dw = vjp(g)
        np.testing.assert_array_equal(np.asarray(dw),
                                      np.asarray(jnp.dot(s.T, g)))
        np.testing.assert_array_equal(np.asarray(ds),
                                      np.asarray(jnp.dot(g, w.T)))

    def test_flags_ride_the_residuals(self):
        """The forward's occupancy reduction happens once: the flags saved
        by the VJP forward are exactly ``ops.block_flags`` of the spike
        matrix, and the backward consumes them as-is."""
        s = _spikes((16, 300), 0.05, seed=2)
        w = jax.random.normal(jax.random.key(3), (300, 40)) * 0.1
        _, res = ops._spike_gemm_train_fwd((8, 128, 128), s, w)
        saved_s, saved_w, saved_flags = res
        np.testing.assert_array_equal(
            np.asarray(saved_flags),
            np.asarray(ops.block_flags(s, block_m=8, block_k=128)))


class TestConvVJP:
    """The conv custom_vjp (ops.spike_conv_train): patch-tiled block-skip
    forward, block-skip dW/dS backward on the forward's flags, col2im via
    the exact linear transpose of the im2col view."""

    @staticmethod
    def _inputs(shape=(2, 9, 9, 3), kernel=3, cout=5, density=0.5, seed=0):
        rng = np.random.default_rng(seed)
        s = _spikes(shape, density, seed=seed + 1)
        w = jnp.asarray(rng.integers(-64, 64,
                                     (kernel, kernel, shape[-1], cout))
                        / 256.0, dtype=jnp.float32)
        return s, w

    def test_check_grads_rev(self):
        """check_grads on the conv custom_vjp (rev mode; the dense 50% train
        keeps every patch-tile occupancy flag stable under the numeric
        perturbations, so the block-skip forward stays the linear map)."""
        s, w = self._inputs(density=0.5, seed=3)
        conv = lambda s, w: ops.spike_conv_train(s, w, block_m=8)
        check_grads(conv, (s, w), order=1, modes=["rev"],
                    atol=1e-2, rtol=1e-2)

    @pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                                (1, "VALID")])
    @pytest.mark.parametrize("density", [0.0, 0.15, 1.0])
    def test_bwd_bitexact_vs_dense_on_grid(self, stride, padding, density):
        """Block-skip conv dW/dS equal the dense ``lax.conv`` cotangents
        BIT-FOR-BIT on 1/256-grid operands (every accumulate is an exact
        fp32 sum, so any deviation could only come from a wrongly-skipped
        patch tile or a mis-scattered col2im overlap)."""
        rng = np.random.default_rng(17)
        s, w = self._inputs(shape=(2, 10, 9, 2), cout=4, density=density,
                            seed=5)
        out, vjp = jax.vjp(
            lambda s, w: ops.spike_conv_train(s, w, stride=stride,
                                              padding=padding, block_m=8),
            s, w)
        g = jnp.asarray(rng.integers(-64, 64, out.shape) / 256.0,
                        dtype=jnp.float32)
        ds, dw = vjp(g)
        _, vjp_dense = jax.vjp(
            lambda s, w: ref.spike_conv_ref(s, w, stride=stride,
                                            padding=padding), s, w)
        ds_ref, dw_ref = vjp_dense(g)
        np.testing.assert_array_equal(np.asarray(dw), np.asarray(dw_ref))
        np.testing.assert_array_equal(np.asarray(ds), np.asarray(ds_ref))

    def test_flags_ride_the_residuals(self):
        """The forward's patch-occupancy reduction happens once: the flags
        saved by the VJP forward are exactly ``ops.block_flags`` of the
        im2col patch matrix, and the backward consumes them as-is (never
        recomputed)."""
        s, w = self._inputs(shape=(2, 12, 12, 2), cout=4, density=0.05,
                            seed=2)
        static = (1, "SAME", 8, 128, 128)
        _, res = ops._spike_conv_train_fwd(static, s, w)
        saved_s, saved_w, saved_flags = res
        patches = ops.conv_patches(s, 3, 3, 1, "SAME")
        np.testing.assert_array_equal(
            np.asarray(saved_flags),
            np.asarray(ops.block_flags(patches, block_m=8, block_k=128)))
        # the residual holds the raw spike tensor, not the patch matrix
        assert saved_s.shape == s.shape
        # and the backward driven by those residuals is the dense cotangent
        g = jnp.ones((2, 12, 12, 4), jnp.float32)
        ds, dw = ops._spike_conv_train_bwd(static, res, g)
        _, vjp_dense = jax.vjp(lambda s, w: ref.spike_conv_ref(s, w), s, w)
        ds_ref, dw_ref = vjp_dense(g)
        np.testing.assert_array_equal(np.asarray(dw), np.asarray(dw_ref))
        np.testing.assert_array_equal(np.asarray(ds), np.asarray(ds_ref))

    def test_zero_train_zero_weight_grad(self):
        """An all-zero spike tensor skips every patch tile, yet the backward
        still produces the exact dense cotangents (dW = 0, dS = g * Wᵀ
        folded back through col2im)."""
        s = jnp.zeros((2, 8, 8, 2), jnp.float32)
        w = jax.random.normal(jax.random.key(0), (3, 3, 2, 4))
        ds, dw = jax.grad(
            lambda s, w: ops.spike_conv_train(s, w, block_m=8).sum(),
            argnums=(0, 1))(s, w)
        np.testing.assert_array_equal(np.asarray(dw), 0.0)
        ds_ref = jax.grad(
            lambda s: ref.spike_conv_ref(s, w).sum())(s)
        np.testing.assert_allclose(np.asarray(ds), np.asarray(ds_ref),
                                   atol=1e-6)


class TestFusedKernelGrads:
    """ops.spike_gemm_lif_step: the fused GEMM+LIF scan step must carry the
    exact gradient contract of the unfused composition
    (spike_gemm_train + bias + lif.lif_step)."""

    def _inputs(self, seed=0, M=16, K=40, N=12):
        keys = jax.random.split(jax.random.key(seed), 4)
        s = _spikes((M, K), 0.5, seed=seed + 1)
        w = jax.random.normal(keys[0], (K, N)) * 0.1
        b = jax.random.normal(keys[1], (N,)) * 0.1
        u0 = jax.random.normal(keys[2], (M, N)) * 0.5
        s0 = _spikes((M, N), 0.3, seed=seed + 2)
        return s, w, b, u0, s0

    def test_check_grads_membrane_path(self):
        """check_grads (rev) through the fused kernel's membrane output —
        u is linear in (w, b, u_prev), so the numeric check is exact-ish.
        The spike output is a Heaviside whose surrogate gradient is
        deliberately NOT the numerical derivative (that is the point of
        surrogate training); its path is locked by the parity tests."""
        s, w, b, u0, s0 = self._inputs()

        def membrane(w, b, u0):
            u, _ = ops.spike_gemm_lif_step(s, w, b, u0, s0,
                                           beta=0.9, threshold=1.0)
            return u

        check_grads(membrane, (w, b, u0), order=1, modes=["rev"],
                    atol=1e-2, rtol=1e-2)

    @pytest.mark.parametrize("reset", ["subtract", "zero"])
    def test_fused_vjp_matches_unfused(self, reset):
        """Full (gu, gs) cotangents through the fused custom_vjp equal the
        unfused composition's — including the fast-sigmoid surrogate on the
        spike output and the LIF chain rule on both reset mechanisms."""
        from repro.core.lif import LIFParams, lif_step as core_lif
        s, w, b, u0, s0 = self._inputs(seed=4)
        lif = LIFParams(beta=0.9, threshold=1.0, reset_mechanism=reset)
        kb = dict(block_m=8, block_n=128, block_k=128)

        def fused(w, b, u0, s0):
            return ops.spike_gemm_lif_step(
                s, w, b, u0, s0, beta=lif.beta, threshold=lif.threshold,
                slope=lif.slope, reset_mechanism=reset, **kb)

        def unfused(w, b, u0, s0):
            cur = ops.spike_gemm_train(s, w, **kb) + b
            return core_lif(u0, s0, cur, lif)

        gu = jax.random.normal(jax.random.key(10), u0.shape)
        gs = jax.random.normal(jax.random.key(11), u0.shape)
        outs_f, vjp_f = jax.vjp(fused, w, b, u0, s0)
        outs_u, vjp_u = jax.vjp(unfused, w, b, u0, s0)
        # identical spikes; membrane equal to fp rounding (the fused
        # epilogue and XLA's fused elementwise may associate differently)
        np.testing.assert_array_equal(np.asarray(outs_f[1]),
                                      np.asarray(outs_u[1]))
        np.testing.assert_allclose(np.asarray(outs_f[0]),
                                   np.asarray(outs_u[0]), atol=1e-6)
        _assert_tree_allclose(vjp_f((gu, gs)), vjp_u((gu, gs)),
                              atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("reset", ["subtract", "zero"])
    def test_fused_dw_bitexact_on_grid(self, reset):
        """The fused backward's dW is bit-for-bit the dense cotangent on
        grid-quantized operands, under both reset mechanisms — the skipped
        spike tiles contribute exactly zero through the fused path too."""
        rng = np.random.default_rng(7)
        M, K, N = 24, 300, 20
        s = _spikes((M, K), 0.1, seed=9)
        w = jnp.asarray(rng.integers(-64, 64, (K, N)) / 256.0,
                        dtype=jnp.float32)
        b = jnp.zeros((N,), jnp.float32)
        u0 = jnp.zeros((M, N), jnp.float32)
        s0 = jnp.zeros((M, N), jnp.float32)
        gu = jnp.asarray(rng.integers(-64, 64, (M, N)) / 256.0,
                         dtype=jnp.float32)

        def fused(w):
            return ops.spike_gemm_lif_step(
                s, w, b, u0, s0, beta=0.9, threshold=1.0,
                reset_mechanism=reset, block_m=8)

        _, vjp = jax.vjp(fused, w)
        # gs = 0 keeps the surrogate factor out so g stays on the grid
        (dw,) = vjp((gu, jnp.zeros_like(gu)))
        np.testing.assert_array_equal(np.asarray(dw),
                                      np.asarray(jnp.dot(s.T, gu)))


class TestLossGradParity:
    """Full surrogate-gradient BPTT through lax.scan, both backends."""

    def _cfg(self, reset="subtract", K=100, hidden=33, classes=10):
        lif = LIFParams(reset_mechanism=reset)
        side = int(np.sqrt(K))
        return snn.SNNConfig(
            name=f"g-{reset}", input_shape=(side, side),
            layers=(snn.Dense(hidden, lif=lif), snn.Dense(classes, lif=lif)),
            num_classes=classes, num_steps=5)

    @pytest.mark.parametrize("reset", ["subtract", "zero"])
    def test_loss_grads_match(self, reset):
        cfg = self._cfg(reset)
        params = snn.init_params(jax.random.key(0), cfg)
        x = jax.random.uniform(jax.random.key(1), (16, 100))
        y = jax.random.randint(jax.random.key(2), (16,), 0, cfg.num_classes)
        key = jax.random.key(3)
        grads = {}
        vals = {}
        for backend in snn.MATMUL_BACKENDS:
            vals[backend], grads[backend] = jax.value_and_grad(
                lambda p: train_snn.loss_fn(cfg, p, key, x, y,
                                            matmul_backend=backend))(params)
        for backend in snn.MATMUL_BACKENDS[1:]:
            np.testing.assert_allclose(float(vals["jnp"]),
                                       float(vals[backend]), rtol=1e-6)
            _assert_tree_allclose(grads["jnp"], grads[backend],
                                  atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("density", [0.0, 1.0])
    def test_degenerate_input_trains(self, density):
        """All-zero and all-one input spike trains through the full net."""
        cfg = self._cfg("subtract", K=64, hidden=24, classes=4)
        params = snn.init_params(jax.random.key(5), cfg)
        spikes_in = _spikes((cfg.num_steps, 8, 64), density)
        y = jnp.arange(8) % 4

        def loss(p, backend):
            out = snn.apply(cfg, p, spikes_in, matmul_backend=backend)
            from repro.core import encoding
            return encoding.rate_loss(out, y, cfg.num_classes)

        va, ga = jax.value_and_grad(loss)(params, "jnp")
        for backend in snn.MATMUL_BACKENDS[1:]:
            vb, gb = jax.value_and_grad(loss)(params, backend)
            np.testing.assert_allclose(float(va), float(vb), rtol=1e-6)
            _assert_tree_allclose(ga, gb, atol=1e-6, rtol=1e-6)

    def test_forward_values_match(self):
        """Spike-for-spike identical forward trains (binary outputs make
        exact equality the right assertion)."""
        cfg = self._cfg("zero")
        params = snn.init_params(jax.random.key(7), cfg)
        x = jax.random.uniform(jax.random.key(8), (4, 100))
        from repro.core import encoding
        spikes_in = encoding.rate_encode(jax.random.key(9), x, cfg.num_steps)
        out_j = snn.apply(cfg, params, spikes_in, matmul_backend="jnp",
                          return_all_layers=True)
        for backend in snn.MATMUL_BACKENDS[1:]:
            out_k = snn.apply(cfg, params, spikes_in,
                              matmul_backend=backend,
                              return_all_layers=True)
            for a, b in zip(out_j, out_k):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

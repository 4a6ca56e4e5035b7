"""Public jit'd wrappers around the Pallas kernels: padding, flag
computation and dtype handling.  Whether a kernel runs interpreted (CPU) or
compiled (TPU) is decided from the platform by ``repro.runtime`` alone."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.lif_step import LIF_BLOCKS, lif_step_pallas
from repro.kernels.spike_conv import (conv_out_size, conv_patches,
                                      spike_conv_pallas)
from repro.kernels.spike_gemm import spike_gemm_pallas
from repro.kernels.spike_gemm_bwd import (spike_gemm_ds_pallas,
                                          spike_gemm_dw_pallas)
from repro.kernels.spike_gemm_fused import spike_gemm_lif_pallas


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-s) % m) for s, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


@functools.partial(jax.jit, static_argnames=("beta", "threshold",
                                             "reset_mechanism", "block_b",
                                             "block_n"))
def lif_step(u_prev: jax.Array, s_prev: jax.Array, current: jax.Array, *,
             beta: float, threshold: float, reset_mechanism: str = "subtract",
             block_b: int = LIF_BLOCKS["block_b"],
             block_n: int = LIF_BLOCKS["block_n"]
             ) -> tuple[jax.Array, jax.Array]:
    """Fused LIF update on arbitrary (B, N); pads to tile multiples.

    Default tile is ``lif_step.LIF_BLOCKS`` (shared with the kernel module;
    see the constant's note on why it is wider than ``snn.KERNEL_BLOCKS``).
    """
    B, N = u_prev.shape
    args = [_pad_to(a, (block_b, block_n)) for a in (u_prev, s_prev, current)]
    u, s = lif_step_pallas(*args, beta=beta, threshold=threshold,
                           reset_mechanism=reset_mechanism,
                           block_b=block_b, block_n=block_n)
    return u[:B, :N], s[:B, :N]


@functools.partial(jax.jit, static_argnames=("block_m", "block_k"))
def block_flags(spikes: jax.Array, *, block_m: int = 128,
                block_k: int = 128) -> jax.Array:
    """Per-tile occupancy flags for ``spikes`` padded to block multiples —
    the array ``spike_gemm`` prefetches.  Computed once here, it can be fed
    back via ``spike_gemm(..., flags=...)`` so hot loops that already
    measured ``skip_fraction`` don't pay the reduction twice."""
    s = _pad_to(spikes, (block_m, block_k))
    return ref.block_flags_ref(s, block_m, block_k)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def spike_gemm(spikes: jax.Array, weights: jax.Array, *,
               flags: jax.Array = None,
               block_m: int = 128, block_n: int = 128,
               block_k: int = 128) -> jax.Array:
    """Sparsity-aware S @ W with block-level spike skipping.

    ``flags``: optional precomputed occupancy from ``block_flags`` (same
    block shape); when omitted the flags are computed here.
    """
    M, K = spikes.shape
    _, N = weights.shape
    s = _pad_to(spikes, (block_m, block_k))
    w = _pad_to(weights, (block_k, block_n))
    if flags is None:
        flags = ref.block_flags_ref(s, block_m, block_k)
    want = (s.shape[0] // block_m, s.shape[1] // block_k)
    if flags.shape != want:
        raise ValueError(
            f"flags shape {flags.shape} does not match the {want} tile grid "
            f"of spikes {spikes.shape} at block_m={block_m}, "
            f"block_k={block_k}; build them with ops.block_flags on the "
            f"same spike matrix and block sizes")
    out = spike_gemm_pallas(flags, s, w, block_m=block_m, block_n=block_n,
                            block_k=block_k)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# Block-skip backward kernels (the other two matmuls of BPTT)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def cotangent_block_flags(g: jax.Array, *, block_m: int = 128,
                          block_n: int = 128) -> jax.Array:
    """Any-nonzero per-tile occupancy of a SIGNED cotangent, padded to block
    multiples — the gate of the dS backward pass.  Distinct from
    ``block_flags``: a float tile whose entries cancel to a zero sum still
    holds work (``ref.block_flags_any_ref``)."""
    gp = _pad_to(g, (block_m, block_n))
    return ref.block_flags_any_ref(gp, block_m, block_n)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def spike_gemm_bwd_dw(spikes: jax.Array, g: jax.Array, *,
                      flags: jax.Array = None,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128) -> jax.Array:
    """dW[K,N] = Sᵀ·g with block-skip on the spike tiles.

    ``flags``: the FORWARD's occupancy array (``block_flags`` on the same
    spike matrix and block sizes) — a skipped (m, k) spike tile is all-zero
    and contributes exactly zero to dW rows k, so reusing the flags makes the
    sparse backward bit-identical to running the same kernel unskipped.
    """
    M, K = spikes.shape
    _, N = g.shape
    s = _pad_to(spikes, (block_m, block_k))
    gp = _pad_to(g, (block_m, block_n))
    if flags is None:
        flags = ref.block_flags_ref(s, block_m, block_k)
    want = (s.shape[0] // block_m, s.shape[1] // block_k)
    if flags.shape != want:
        raise ValueError(
            f"flags shape {flags.shape} does not match the {want} tile grid "
            f"of spikes {spikes.shape} at block_m={block_m}, "
            f"block_k={block_k}")
    dw = spike_gemm_dw_pallas(flags, s, gp, block_m=block_m, block_n=block_n,
                              block_k=block_k)
    return dw[:K, :N]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def spike_gemm_bwd_ds(g: jax.Array, weights: jax.Array, *,
                      gflags: jax.Array = None,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128) -> jax.Array:
    """dS[M,K] = g·Wᵀ with block-skip on the cotangent tiles.

    Surrogate-gradient cotangents vanish wherever ``|u - θ|`` is large, so
    whole (m, n) tiles of ``g`` are exactly zero late in training; ``gflags``
    (``cotangent_block_flags``) gates the accumulate the same way the
    forward's spike flags do.
    """
    M, N = g.shape
    K, _ = weights.shape
    gp = _pad_to(g, (block_m, block_n))
    w = _pad_to(weights, (block_k, block_n))
    if gflags is None:
        gflags = ref.block_flags_any_ref(gp, block_m, block_n)
    want = (gp.shape[0] // block_m, gp.shape[1] // block_n)
    if gflags.shape != want:
        raise ValueError(
            f"gflags shape {gflags.shape} does not match the {want} tile "
            f"grid of g {g.shape} at block_m={block_m}, block_n={block_n}")
    ds = spike_gemm_ds_pallas(gflags, gp, w, block_m=block_m, block_n=block_n,
                              block_k=block_k)
    return ds[:M, :K]


# ---------------------------------------------------------------------------
# Differentiable spike GEMM (the training hot path)
# ---------------------------------------------------------------------------
# BPTT needs gradients through the accumulate phase.  ``spike_gemm_train``
# wraps the Pallas kernels in a ``jax.custom_vjp``: block-skip forward AND
# block-skip backward — dW = Sᵀ·g reuses the forward's occupancy flags
# (saved in the VJP residuals so neither pass recomputes the reduction),
# dS = g·Wᵀ is gated on any-nonzero cotangent-tile occupancy.  Skipping is
# exact in both directions (an empty tile contributes exactly zero), so
# surrogate-gradient training through ``lax.scan`` stays numerically the
# dense reference up to fp32 tile-order rounding.  DESIGN.md §11–§12.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spike_gemm_train(blocks: tuple, spikes: jax.Array,
                      weights: jax.Array) -> jax.Array:
    block_m, block_n, block_k = blocks
    return spike_gemm(spikes, weights, block_m=block_m, block_n=block_n,
                      block_k=block_k)


def _spike_gemm_train_fwd(blocks, spikes, weights):
    block_m, block_n, block_k = blocks
    flags = block_flags(spikes, block_m=block_m, block_k=block_k)
    out = spike_gemm(spikes, weights, flags=flags, block_m=block_m,
                     block_n=block_n, block_k=block_k)
    return out, (spikes, weights, flags)


def _spike_gemm_train_bwd(blocks, res, g):
    block_m, block_n, block_k = blocks
    spikes, weights, flags = res
    g32 = g.astype(jnp.float32)
    d_spikes = spike_gemm_bwd_ds(
        g32, weights, block_m=block_m, block_n=block_n,
        block_k=block_k).astype(spikes.dtype)
    d_weights = spike_gemm_bwd_dw(
        spikes, g32, flags=flags, block_m=block_m, block_n=block_n,
        block_k=block_k).astype(weights.dtype)
    return d_spikes, d_weights


_spike_gemm_train.defvjp(_spike_gemm_train_fwd, _spike_gemm_train_bwd)


def spike_gemm_train(spikes: jax.Array, weights: jax.Array, *,
                     block_m: int = 128, block_n: int = 128,
                     block_k: int = 128) -> jax.Array:
    """Differentiable S @ W: block-skip Pallas forward and backward."""
    return _spike_gemm_train((block_m, block_n, block_k),
                             spikes, weights)


# ---------------------------------------------------------------------------
# Block-skip spike convolution (the conv datapath of the same engine)
# ---------------------------------------------------------------------------
# A Conv layer is the same sparsity-aware accumulate run over the im2col view
# of its spike input: patches of {0,1} spikes are still {0,1} spikes, so the
# sum>0 occupancy gate of ``block_flags`` stays exact on the patch matrix and
# both backward matmuls are ordinary GEMM cotangents of that matrix — the
# dW/dS kernels of spike_gemm_bwd.py are reused verbatim.  DESIGN.md §13.

@functools.partial(jax.jit, static_argnames=("stride", "padding", "block_m",
                                             "block_n", "block_k"))
def spike_conv(s_in: jax.Array, weights: jax.Array, *, stride: int = 1,
               padding: str = "SAME", flags: jax.Array = None,
               block_m: int = 128, block_n: int = 128,
               block_k: int = 128) -> jax.Array:
    """Sparsity-aware NHWC x HWIO convolution with patch-tile skipping.

    ``flags``: optional precomputed occupancy of the PATCH matrix
    (``block_flags(conv_patches(s_in, ...))`` with the same block sizes);
    when omitted the flags are computed here.  Output is (B, OH, OW, F),
    bit-identical to ``lax.conv_general_dilated`` up to fp32 tile-order
    rounding (exactly equal on grid operands — see tests/test_kernels.py).
    """
    B, H, W, C = s_in.shape
    kh, kw, cin, cout = weights.shape
    if cin != C:
        raise ValueError(f"weights expect {cin} input channels, spikes "
                         f"have {C}")
    oh, _, _ = conv_out_size(H, kh, stride, padding)
    ow, _, _ = conv_out_size(W, kw, stride, padding)
    patches = conv_patches(s_in, kh, kw, stride, padding)
    p = _pad_to(patches, (block_m, block_k))
    w = _pad_to(weights.reshape(kh * kw * cin, cout), (block_k, block_n))
    if flags is None:
        flags = ref.block_flags_ref(p, block_m, block_k)
    want = (p.shape[0] // block_m, p.shape[1] // block_k)
    if flags.shape != want:
        raise ValueError(
            f"flags shape {flags.shape} does not match the {want} tile grid "
            f"of the patch matrix {patches.shape} at block_m={block_m}, "
            f"block_k={block_k}; build them with ops.block_flags on "
            f"ops.conv_patches of the same spike tensor")
    out = spike_conv_pallas(flags, p, w, block_m=block_m, block_n=block_n,
                            block_k=block_k)
    return out[:B * oh * ow, :cout].reshape(B, oh, ow, cout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spike_conv_train(static: tuple, s_in: jax.Array,
                      weights: jax.Array) -> jax.Array:
    stride, padding, block_m, block_n, block_k = static
    return spike_conv(s_in, weights, stride=stride, padding=padding,
                      block_m=block_m, block_n=block_n, block_k=block_k)


def _spike_conv_train_fwd(static, s_in, weights):
    stride, padding, block_m, block_n, block_k = static
    kh, kw = weights.shape[:2]
    patches = conv_patches(s_in, kh, kw, stride, padding)
    flags = block_flags(patches, block_m=block_m, block_k=block_k)
    out = spike_conv(s_in, weights, stride=stride, padding=padding,
                     flags=flags, block_m=block_m, block_n=block_n,
                     block_k=block_k)
    # the flags ride the residuals (PR-6 contract): the backward reuses the
    # forward's occupancy reduction instead of recomputing it.  The patch
    # matrix itself is NOT saved — it is cheap deterministic slicing of
    # ``s_in`` and rebuilding it keeps residual memory at O(B·H·W·C) instead
    # of O(B·OH·OW·KH·KW·C).
    return out, (s_in, weights, flags)


def _spike_conv_train_bwd(static, res, g):
    stride, padding, block_m, block_n, block_k = static
    s_in, weights, flags = res
    kh, kw, cin, cout = weights.shape
    patch_fn = lambda x: conv_patches(x, kh, kw, stride, padding)
    patches, unpatch = jax.vjp(patch_fn, s_in)
    g2 = g.reshape(-1, cout).astype(jnp.float32)
    d_w = spike_gemm_bwd_dw(
        patches, g2, flags=flags, block_m=block_m, block_n=block_n,
        block_k=block_k)
    d_patches = spike_gemm_bwd_ds(
        g2, weights.reshape(kh * kw * cin, cout), block_m=block_m,
        block_n=block_n, block_k=block_k)
    # col2im: the exact linear transpose of conv_patches (pad + strided
    # slice + concat), derived by jax.vjp so overlap scatter-adds match the
    # dense conv's input cotangent bit for bit on grid operands.
    (d_s,) = unpatch(d_patches.astype(s_in.dtype))
    return d_s, d_w.reshape(kh, kw, cin, cout).astype(weights.dtype)


_spike_conv_train.defvjp(_spike_conv_train_fwd, _spike_conv_train_bwd)


def spike_conv_train(s_in: jax.Array, weights: jax.Array, *, stride: int = 1,
                     padding: str = "SAME", block_m: int = 128,
                     block_n: int = 128, block_k: int = 128) -> jax.Array:
    """Differentiable block-skip convolution: patch-tiled forward, block-skip
    dW/dS backward reusing the forward's flags from the VJP residuals."""
    return _spike_conv_train(
        (int(stride), str(padding), block_m, block_n, block_k),
        s_in, weights)


# ---------------------------------------------------------------------------
# Fused GEMM + LIF scan step (matmul_backend="spike_gemm_fused")
# ---------------------------------------------------------------------------
# One Dense training step is accumulate -> +bias -> leak/threshold/reset;
# ``spike_gemm_lif_step`` runs all of it in the fused Pallas kernel
# (spike_gemm_fused.py) so membrane state never round-trips through HBM
# between the matmul and the neuron update.  The custom_vjp backward applies
# the fast-sigmoid surrogate (exactly ``lif.spike_fn``'s rule), the LIF
# chain rule, and the two block-skip backward kernels above — the forward's
# flags again ride the residuals.  DESIGN.md §12.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spike_gemm_lif_train(static: tuple, spikes: jax.Array,
                          weights: jax.Array, bias: jax.Array,
                          u_prev: jax.Array, s_prev: jax.Array
                          ) -> tuple[jax.Array, jax.Array]:
    out, _ = _spike_gemm_lif_fwd_impl(static, spikes, weights, bias,
                                      u_prev, s_prev)
    return out


def _spike_gemm_lif_fwd_impl(static, spikes, weights, bias, u_prev, s_prev):
    (block_m, block_n, block_k,
     beta, threshold, slope, reset_mechanism) = static
    B, K = spikes.shape
    _, N = weights.shape
    s = _pad_to(spikes, (block_m, block_k))
    w = _pad_to(weights, (block_k, block_n))
    b = _pad_to(bias.reshape(1, -1), (1, block_n))
    u0 = _pad_to(u_prev, (block_m, block_n))
    s0 = _pad_to(s_prev, (block_m, block_n))
    flags = ref.block_flags_ref(s, block_m, block_k)
    u, sp = spike_gemm_lif_pallas(
        flags, s, w, b, u0, s0, beta=beta, threshold=threshold,
        reset_mechanism=reset_mechanism, block_m=block_m, block_n=block_n,
        block_k=block_k)
    return (u[:B, :N], sp[:B, :N]), flags


def _spike_gemm_lif_train_fwd(static, spikes, weights, bias, u_prev, s_prev):
    (u, sp), flags = _spike_gemm_lif_fwd_impl(static, spikes, weights, bias,
                                              u_prev, s_prev)
    return (u, sp), (spikes, weights, bias, u_prev, s_prev, u, flags)


def _spike_gemm_lif_train_bwd(static, res, cots):
    (block_m, block_n, block_k,
     beta, threshold, slope, reset_mechanism) = static
    spikes, weights, bias, u_prev, s_prev, u, flags = res
    gu, gs = cots
    # fast-sigmoid surrogate through s = H(u - theta), then the LIF chain
    # rule — term for term what autodiff derives on the unfused
    # lif.lif_step, so fused and unfused cotangents agree.
    v = u - threshold
    surr = 1.0 / jnp.square(1.0 + slope * jnp.abs(v))
    g = gu + gs * surr
    if reset_mechanism == "subtract":
        d_u_prev = beta * g
        d_s_prev = -threshold * g
    else:
        d_u_prev = beta * (1.0 - s_prev) * g
        d_s_prev = -(beta * u_prev) * g
    g32 = g.astype(jnp.float32)
    d_bias = g32.sum(0).astype(bias.dtype)
    d_spikes = spike_gemm_bwd_ds(
        g32, weights, block_m=block_m, block_n=block_n,
        block_k=block_k).astype(spikes.dtype)
    d_weights = spike_gemm_bwd_dw(
        spikes, g32, flags=flags, block_m=block_m, block_n=block_n,
        block_k=block_k).astype(weights.dtype)
    return d_spikes, d_weights, d_bias, d_u_prev.astype(u_prev.dtype), \
        d_s_prev.astype(s_prev.dtype)


_spike_gemm_lif_train.defvjp(_spike_gemm_lif_train_fwd,
                             _spike_gemm_lif_train_bwd)


def spike_gemm_lif_step(spikes: jax.Array, weights: jax.Array,
                        bias: jax.Array, u_prev: jax.Array,
                        s_prev: jax.Array, *, beta: float, threshold: float,
                        slope: float = 25.0,
                        reset_mechanism: str = "subtract",
                        block_m: int = 8, block_n: int = 128,
                        block_k: int = 128
                        ) -> tuple[jax.Array, jax.Array]:
    """Differentiable fused scan step: (u, s) = LIF(u, s, S @ W + b).

    Bit-identical forward to ``spike_gemm_train(S, W) + b`` composed with
    ``lif.lif_step`` (same accumulate order, same epilogue expression);
    surrogate-gradient backward through the block-skip kernels.
    """
    return _spike_gemm_lif_train(
        (block_m, block_n, block_k,
         float(beta), float(threshold), float(slope), reset_mechanism),
        spikes, weights, bias, u_prev, s_prev)


@functools.partial(jax.jit, static_argnames=("capacity", "block_b"))
def penc_compact(spikes: jax.Array, capacity: int, *, block_b: int = 8
                 ) -> tuple[jax.Array, jax.Array]:
    """Spike-address extraction (the ECU's PENC) on (B, N) spike rows."""
    from repro.kernels.penc_compact import penc_compact_pallas
    B, N = spikes.shape
    s = _pad_to(spikes, (block_b, 1))
    idx, cnt = penc_compact_pallas(s, capacity=capacity, block_b=block_b)
    return idx[:B], cnt[:B]


@functools.partial(jax.jit, static_argnames=("block_m", "block_k"))
def _skip_fraction(spikes: jax.Array, *, block_m: int, block_k: int):
    flags = block_flags(spikes, block_m=block_m, block_k=block_k)
    return 1.0 - flags.astype(jnp.float32).mean()


def skip_fraction(spikes: jax.Array, block_m: int = 128,
                  block_k: int = 128) -> float:
    """Fraction of (M,K) tiles the kernel skips — the measurable benefit of
    the sparsity-aware design on given traffic.

    Jitted (pad + tile-reduce fuse and the trace is cached per shape), so
    calling it on the benchmarks/bench_kernels.py hot loop costs one
    compiled reduction, not an eager re-pad per call; pair with
    ``block_flags`` + ``spike_gemm(..., flags=...)`` to reuse the same
    occupancy for the matmul itself."""
    # clamp: fp rounding of the mean can land a hair past 1.0
    return max(0.0, float(_skip_fraction(spikes, block_m=block_m,
                                         block_k=block_k)))


# ---------------------------------------------------------------------------
# Profile-guided neuron permutation (beyond-paper optimization)
# ---------------------------------------------------------------------------
# Uniformly-spread spikes almost never leave a 128-wide tile empty, even at
# 1-10% firing (the paper's Fig.-1 regime): P(empty) = (1-p)^(bm*bk).  But SNN
# firing is heavy-tailed — a minority of neurons produce most spikes.  Sorting
# the pre-synaptic axis by *profiled* firing rate (the very statistic the
# paper's DSE collects) clusters cold neurons into tiles that are empty on
# most steps.  The weight rows are permuted once, offline; runtime cost is
# zero.  This is the LHR-style "allocate by observed sparsity" insight applied
# to MXU tiles instead of hardware neurons.

def firing_rate_permutation(rates: jax.Array) -> jax.Array:
    """Permutation placing rarely-firing pre-synaptic neurons first.

    ``rates``: (K,) mean firing probability per neuron (from profiling).
    Apply to spike columns and weight rows: ``S[:, perm] @ W[perm, :]``.
    """
    return jnp.argsort(rates)


def apply_permutation(spikes: jax.Array, weights: jax.Array,
                      perm: jax.Array) -> tuple[jax.Array, jax.Array]:
    return spikes[:, perm], weights[perm, :]


def spike_gemm_profiled(spikes: jax.Array, weights: jax.Array,
                        perm: jax.Array, **kw) -> jax.Array:
    """spike_gemm with a profile-guided pre-synaptic permutation; exactly
    equal to the unpermuted product (permutation-invariance of matmul)."""
    s, w = apply_permutation(spikes, weights, perm)
    return spike_gemm(s, w, **kw)

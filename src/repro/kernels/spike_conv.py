"""Block-skip spike convolution — the sparsity-aware conv datapath.

PULSE (arXiv:2402.06210) extends the paper's sparsity-aware accumulate
engine to convolution: incoming spike events only cost work for the output
pixels whose receptive field they touch.  On a TPU the skip granularity is
again an MXU tile (DESIGN.md §2), so the conv is *patch-tiled*:

  1. the (B, H, W, C) spike tensor is lowered to its im2col view — a
     (B·OH·OW, KH·KW·C) patch matrix whose rows are receptive fields and
     whose entries are literal copies of spike bits (zero-padding adds
     zeros), so the patch matrix is itself a {0,1} spike matrix;
  2. per-tile occupancy flags are computed on the patch matrix with the
     *same* ``ops.block_flags`` reduction the Dense path uses — exact for
     {0,1} entries because a tile sums to zero iff it holds no spike;
  3. the kernel below runs the block-skip accumulate over
     ``patches @ W.reshape(KH·KW·C, F)``; an empty patch tile (a tile of
     receptive fields that saw no spikes) costs one SMEM read instead of a
     MAC block, exactly as in ``spike_gemm.py``.

The dW/dS backward matmuls of the conv are plain GEMM cotangents of the
patch matrix, so they reuse the block-skip backward kernels of
``spike_gemm_bwd.py`` verbatim (dW on the forward's flags, dS on any-nonzero
cotangent occupancy); the fold back from patch-space to the input spike
tensor is the exact linear transpose of ``conv_patches`` (DESIGN.md §13).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spike_gemm import pack_flags, tile_flag
from repro.runtime import kernel_interpret


def conv_out_size(size: int, kernel: int, stride: int,
                  padding: str) -> tuple[int, int, int]:
    """(output size, pad_lo, pad_hi) for one spatial dim — XLA's convention
    (``lax.padtype_to_pads``), so the patch view matches ``lax.conv`` SAME
    semantics exactly."""
    if padding == "SAME":
        out = -(-size // stride)
        pad = max((out - 1) * stride + kernel - size, 0)
        return out, pad // 2, pad - pad // 2
    if padding == "VALID":
        return (size - kernel) // stride + 1, 0, 0
    raise ValueError(f"unknown padding {padding!r}; pick SAME or VALID")


def conv_patches(s_in: jax.Array, kh: int, kw: int, stride: int,
                 padding: str) -> jax.Array:
    """im2col: (B, H, W, C) -> (B·OH·OW, KH·KW·C) patch matrix.

    Row ``b·OH·OW + oh·OW + ow`` is output pixel (b, oh, ow)'s receptive
    field; features are ordered (dy, dx, c) so the matching weight matrix is
    simply ``w.reshape(KH·KW·C, F)`` of the HWIO layout.  Pure pad + strided
    slice + concatenate — linear, so its ``jax.vjp`` is the exact col2im
    scatter-add the backward needs.
    """
    B, H, W, C = s_in.shape
    oh, ph_lo, ph_hi = conv_out_size(H, kh, stride, padding)
    ow, pw_lo, pw_hi = conv_out_size(W, kw, stride, padding)
    xp = jnp.pad(s_in, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    cols = []
    for dy in range(kh):
        for dx in range(kw):
            cols.append(xp[:, dy:dy + (oh - 1) * stride + 1:stride,
                           dx:dx + (ow - 1) * stride + 1:stride, :])
    patches = jnp.concatenate(cols, axis=-1)          # (B, OH, OW, KH·KW·C)
    return patches.reshape(B * oh * ow, kh * kw * C)


def _spike_conv_kernel(flags_ref, p_ref, w_ref, o_ref, acc_ref):
    """Block-skip accumulate over the patch matrix (mirrors
    ``spike_gemm.py``: reduction innermost, VMEM f32 accumulator, ``pl.when``
    gating the dot on the scalar-prefetched patch-tile flag)."""
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tile_flag(flags_ref, i, k, pl.num_programs(2)) != 0)
    def _accumulate():
        acc_ref[...] += jnp.dot(p_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def spike_conv_pallas(flags: jax.Array, patches: jax.Array,
                      weights: jax.Array, *, block_m: int = 128,
                      block_n: int = 128, block_k: int = 128,
                      out_dtype=jnp.float32) -> jax.Array:
    """out[M,N] = patches[M,K] @ weights[K,N], skipping empty patch tiles.

    ``patches``: the im2col view (M = B·OH·OW receptive-field rows,
    K = KH·KW·C); ``weights``: the HWIO filter reshaped to (K, F).
    ``flags``: (M//block_m, K//block_k) occupancy of the patch matrix
    (``ref.block_flags_ref`` — exact for {0,1} spikes).  Shapes must be
    pre-padded to block multiples (the ops.py wrapper pads).
    """
    M, K = patches.shape
    K2, N = weights.shape
    assert K == K2 and M % block_m == 0 and K % block_k == 0 and N % block_n == 0
    grid = (M // block_m, N // block_n, K // block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k, flags: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k, flags: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k, flags: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        _spike_conv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=kernel_interpret(),
    )(pack_flags(flags), patches, weights)

"""Block-skip Pallas backward kernels for the spike GEMM training path.

BPTT through ``out = S @ W`` needs two cotangent matmuls per layer per scan
step, and both inherit the forward's sparsity (DESIGN.md §12):

* ``dW = Sᵀ · g`` — the contraction runs over the batch/row axis of the
  *same* spike matrix the forward consumed.  A spike tile ``S[m, k]`` that
  the forward skipped is all-zero, so its transposed tile contributes
  exactly zero to the ``dW`` rows ``k``: the forward's ``block_flags``
  array, read transposed (reduction index first), gates the accumulate and
  neither pass recomputes the occupancy reduction.
* ``dS = g · Wᵀ`` — here the sparse operand is the *cotangent*: surrogate
  gradients vanish wherever ``|u - θ|`` is large, so late in training whole
  (m, n) tiles of ``g`` are exactly zero.  Occupancy of ``g`` must be
  computed with an any-nonzero reduction (``ref.block_flags_any_ref``) —
  the forward's sum>0 test is only exact for nonnegative spikes, and a
  float tile whose entries cancel must NOT be skipped.

Both kernels mirror ``spike_gemm.py``: reduction as the innermost grid
dimension, a VMEM f32 accumulator initialised at step 0 and flushed at the
last step, and ``pl.when`` guarding the dot on a scalar-prefetched flag so a
skipped tile costs one SMEM read instead of a MAC block.  Tiles are
transposed in-register (``.T`` on the VMEM block) rather than materialising
Sᵀ/Wᵀ in HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spike_gemm import pack_flags, tile_flag
from repro.runtime import kernel_interpret


def _dw_kernel(flags_ref, s_ref, g_ref, dw_ref, acc_ref):
    ki, m = pl.program_id(0), pl.program_id(2)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # forward flags are (m, k)-indexed; the reduction index comes first here
    @pl.when(tile_flag(flags_ref, m, ki, pl.num_programs(0)) != 0)
    def _accumulate():
        acc_ref[...] += jnp.dot(s_ref[...].T, g_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(m == pl.num_programs(2) - 1)
    def _flush():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def spike_gemm_dw_pallas(flags: jax.Array, spikes: jax.Array, g: jax.Array,
                         *, block_m: int = 128, block_n: int = 128,
                         block_k: int = 128, out_dtype=jnp.float32
                         ) -> jax.Array:
    """dW[K,N] = spikes[M,K]ᵀ @ g[M,N], skipping empty spike tiles.

    ``flags``: the FORWARD's (M//block_m, K//block_k) occupancy array —
    reused verbatim, indexed transposed.  Shapes must be pre-padded to block
    multiples (the ops.py wrapper pads).
    """
    M, K = spikes.shape
    M2, N = g.shape
    assert M == M2 and M % block_m == 0 and K % block_k == 0 and N % block_n == 0
    grid = (K // block_k, N // block_n, M // block_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda ki, j, m, flags: (m, ki)),
            pl.BlockSpec((block_m, block_n), lambda ki, j, m, flags: (m, j)),
        ],
        out_specs=pl.BlockSpec((block_k, block_n),
                               lambda ki, j, m, flags: (ki, j)),
        scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        _dw_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, N), out_dtype),
        interpret=kernel_interpret(),
    )(pack_flags(flags), spikes, g)


def _ds_kernel(gflags_ref, g_ref, w_ref, ds_ref, acc_ref):
    i, n = pl.program_id(0), pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tile_flag(gflags_ref, i, n, pl.num_programs(2)) != 0)
    def _accumulate():
        acc_ref[...] += jnp.dot(g_ref[...], w_ref[...].T,
                                preferred_element_type=jnp.float32)

    @pl.when(n == pl.num_programs(2) - 1)
    def _flush():
        ds_ref[...] = acc_ref[...].astype(ds_ref.dtype)


def spike_gemm_ds_pallas(gflags: jax.Array, g: jax.Array, weights: jax.Array,
                         *, block_m: int = 128, block_n: int = 128,
                         block_k: int = 128, out_dtype=jnp.float32
                         ) -> jax.Array:
    """dS[M,K] = g[M,N] @ weights[K,N]ᵀ, skipping empty cotangent tiles.

    ``gflags``: (M//block_m, N//block_n) any-nonzero occupancy of ``g``
    (``ref.block_flags_any_ref``).  Shapes pre-padded to block multiples.
    """
    M, N = g.shape
    K, N2 = weights.shape
    assert N == N2 and M % block_m == 0 and K % block_k == 0 and N % block_n == 0
    grid = (M // block_m, K // block_k, N // block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, ki, n, gflags: (i, n)),
            pl.BlockSpec((block_k, block_n), lambda i, ki, n, gflags: (ki, n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_k),
                               lambda i, ki, n, gflags: (i, ki)),
        scratch_shapes=[pltpu.VMEM((block_m, block_k), jnp.float32)],
    )
    return pl.pallas_call(
        _ds_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, K), out_dtype),
        interpret=kernel_interpret(),
    )(pack_flags(gflags), g, weights)

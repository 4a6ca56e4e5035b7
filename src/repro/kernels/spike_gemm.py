"""Sparsity-aware spike GEMM — the paper's PENC idea re-grained for TPU.

The FPGA design compresses the incoming binary spike train with a priority
encoder so that only firing neurons cost work.  A TPU cannot skip individual
bits — the MXU consumes 128x128 tiles and VMEM moves whole blocks — so the
skip granularity becomes a (block_m x block_k) tile of the spike matrix:

  1. per-tile occupancy flags are computed with a cheap jnp reduction
     (ops.py), the analogue of the ECU's compression pass;
  2. the flags ride in scalar-prefetch memory (SMEM) so the kernel knows,
     *before* the MXU touches a tile, whether it may skip the dot AND the
     VMEM->MXU traffic for that tile.  They travel bit-packed, 32 tiles to
     an int32 word (``pack_flags``): a 2-D int32 array would be padded to
     128 lanes per row in SMEM, and a conv patch matrix has thousands of
     8-row tiles (DESIGN.md §13 states the bound);
  3. ``pl.when`` guards the accumulate — an all-zero spike tile costs one
     SMEM read instead of a 128x128x128 MAC block.

With the layerwise firing ratios the paper reports (3-30% of neurons,
Fig. 1), most K-tiles of a deep layer are empty and the skip rate is large;
benchmarks/bench_kernels.py reports measured skip fractions on trained-model
traffic.  DESIGN.md §2 records this hardware adaptation; the backward-pass
kernels that reuse these flags live in spike_gemm_bwd.py (DESIGN.md §12).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import kernel_interpret

#: occupancy flags per packed SMEM word
FLAG_BITS = 32
#: SMEM one kernel call may spend on packed flags: a quarter of a v5e
#: core's 1 MiB, the rest left to the compiler's own scalars (DESIGN §13)
FLAG_SMEM_BYTES = 256 * 1024


def pack_flags(flags: jax.Array) -> jax.Array:
    """(rows, cols) 0/1 tile flags -> 1-D int32, bit ``t % 32`` of word
    ``t // 32`` holding tile ``t = row * cols + col``.  The bits are
    disjoint, so the sum is their OR and cannot carry."""
    nbytes = 4 * -(-flags.size // FLAG_BITS)
    if nbytes > FLAG_SMEM_BYTES:
        raise ValueError(
            f"{flags.size} tiles pack to {nbytes} bytes of flags, over the "
            f"{FLAG_SMEM_BYTES}-byte SMEM budget of one kernel call; use "
            f"larger tiles or split the call")
    flat = flags.reshape(-1).astype(jnp.int32)
    flat = jnp.pad(flat, (0, (-flat.size) % FLAG_BITS))
    words = flat.reshape(-1, FLAG_BITS)
    return jnp.left_shift(words, jnp.arange(FLAG_BITS, dtype=jnp.int32)
                          ).sum(axis=1, dtype=jnp.int32)


def tile_flag(flags_ref, row, col, cols: int):
    """Occupancy of tile (row, col) from ``pack_flags`` words in SMEM."""
    t = row * cols + col
    word = flags_ref[t // FLAG_BITS]
    return lax.shift_right_logical(word, t % FLAG_BITS) & 1


def _spike_gemm_kernel(flags_ref, s_ref, w_ref, o_ref, acc_ref):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tile_flag(flags_ref, i, k, pl.num_programs(2)) != 0)
    def _accumulate():
        acc_ref[...] += jnp.dot(s_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def spike_gemm_pallas(flags: jax.Array, spikes: jax.Array, weights: jax.Array,
                      *, block_m: int = 128, block_n: int = 128,
                      block_k: int = 128, out_dtype=jnp.float32
                      ) -> jax.Array:
    """out[M,N] = spikes[M,K] @ weights[K,N], skipping empty spike tiles.

    ``flags``: (M//block_m, K//block_k) int32 occupancy (see ref.block_flags_ref).
    Shapes must be pre-padded to block multiples (ops.py wrapper pads).
    """
    M, K = spikes.shape
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
        _spike_gemm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=kernel_interpret(),
    )(pack_flags(flags), spikes, weights)

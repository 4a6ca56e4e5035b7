"""Fused spike-GEMM + LIF scan-step Pallas kernel.

One training scan step per Dense layer is ``current = S @ W + b`` followed
by the LIF membrane update — two kernels with the (B, N) current, membrane
and spike tensors round-tripping through HBM between them.  The hardware
analogue (PULSE, arXiv:2402.06210) is a single sparsity-aware unit that
folds the neuron update into the accumulate datapath; this kernel does the
same on the MXU: the block-skip accumulate of ``spike_gemm.py`` runs
unchanged, and the *epilogue* of the K-reduction (the grid step that would
merely flush the accumulator) instead applies bias add, leak, threshold
compare and reset while the accumulator tile is still VMEM-resident
(DESIGN.md §12).

The epilogue evaluates the exact expression ``repro.core.lif.lif_step``
evaluates, in the same operation order, so the fused forward is bit-identical
to the unfused spike_gemm + LIF composition — the property that keeps DSE
cells backend-invariant across all three matmul backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spike_gemm import pack_flags, tile_flag
from repro.runtime import kernel_interpret


def _fused_kernel(flags_ref, s_ref, w_ref, b_ref, u_ref, sp_ref,
                  u_out_ref, s_out_ref, acc_ref, *,
                  beta: float, threshold: float, reset_mechanism: str):
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tile_flag(flags_ref, i, k, pl.num_programs(2)) != 0)
    def _accumulate():
        acc_ref[...] += jnp.dot(s_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        dt = u_ref.dtype
        cur = acc_ref[...].astype(dt) + b_ref[...]
        u_prev = u_ref[...]
        s_prev = sp_ref[...]
        beta_ = jnp.asarray(beta, dt)
        thr = jnp.asarray(threshold, dt)
        if reset_mechanism == "subtract":
            u = beta_ * u_prev + cur - thr * s_prev
        else:
            u = beta_ * u_prev * (jnp.asarray(1.0, dt) - s_prev) + cur
        u_out_ref[...] = u
        s_out_ref[...] = (u > thr).astype(dt)


def spike_gemm_lif_pallas(flags: jax.Array, spikes: jax.Array,
                          weights: jax.Array, bias: jax.Array,
                          u_prev: jax.Array, s_prev: jax.Array, *,
                          beta: float, threshold: float,
                          reset_mechanism: str = "subtract",
                          block_m: int = 8, block_n: int = 128,
                          block_k: int = 128
                          ) -> tuple[jax.Array, jax.Array]:
    """(u, s) = LIF(u_prev, s_prev, spikes @ weights + bias) in one pass.

    ``flags``: (M//block_m, K//block_k) occupancy of ``spikes``; ``bias`` is
    (1, N).  Shapes must be pre-padded to block multiples (ops.py wrapper
    pads) — padded neurons see zero current/state and, for any positive
    threshold, stay silent until sliced away.
    """
    M, K = spikes.shape
    K2, N = weights.shape
    assert K == K2 and u_prev.shape == (M, N) and s_prev.shape == (M, N)
    assert bias.shape == (1, N)
    assert M % block_m == 0 and K % block_k == 0 and N % block_n == 0
    grid = (M // block_m, N // block_n, K // block_k)
    state_spec = pl.BlockSpec((block_m, block_n),
                              lambda i, j, k, flags: (i, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k, flags: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k, flags: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k, flags: (0, j)),
            state_spec,
            state_spec,
        ],
        out_specs=(state_spec, state_spec),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    kernel = functools.partial(_fused_kernel, beta=beta, threshold=threshold,
                               reset_mechanism=reset_mechanism)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((M, N), u_prev.dtype),
                   jax.ShapeDtypeStruct((M, N), u_prev.dtype)),
        interpret=kernel_interpret(),
    )(pack_flags(flags), spikes, weights, bias, u_prev, s_prev)

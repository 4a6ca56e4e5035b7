"""Fused LIF membrane-update Pallas TPU kernel.

The accelerator's activation phase (leak multiply + synaptic add + bias +
threshold compare + reset) fused into one VMEM-resident elementwise pass —
one HBM round trip for the whole update instead of five.  Tiles are
(block_b, block_n) with block_n a multiple of 128 (VPU lane width) and
block_b a multiple of 8 (sublane), per the TPU tiling rules.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import kernel_interpret

#: Default standalone-LIF tile, shared by this module and the ops.py wrapper
#: (one constant, not two hardcodings).  ``block_b`` equals the training
#: GEMM tile's ``block_m`` (``snn.KERNEL_BLOCKS`` derives from it): both are
#: the f32 sublane minimum of 8.  ``block_n`` is deliberately 4x the GEMM's
#: 128-lane ``block_n``: a pure elementwise VPU pass has no MXU accumulator
#: tile to stay aligned with, so wider tiles amortise grid overhead.  The
#: *fused* GEMM+LIF kernel (spike_gemm_fused.py) instead inherits the GEMM's
#: 128-lane block_n because its epilogue operates on the accumulator tile.
LIF_BLOCKS = {"block_b": 8, "block_n": 512}


def _lif_kernel(u_ref, s_ref, c_ref, u_out_ref, s_out_ref, *,
                beta: float, threshold: float, reset_mechanism: str):
    dt = u_ref.dtype
    u_prev = u_ref[...]
    s_prev = s_ref[...]
    cur = c_ref[...]
    beta_ = jnp.asarray(beta, dt)
    thr = jnp.asarray(threshold, dt)
    if reset_mechanism == "subtract":
        u = beta_ * u_prev + cur - thr * s_prev
    else:
        u = beta_ * u_prev * (jnp.asarray(1.0, dt) - s_prev) + cur
    u_out_ref[...] = u
    s_out_ref[...] = (u > thr).astype(dt)


def lif_step_pallas(u_prev: jax.Array, s_prev: jax.Array, current: jax.Array,
                    *, beta: float, threshold: float,
                    reset_mechanism: str = "subtract",
                    block_b: int = LIF_BLOCKS["block_b"],
                    block_n: int = LIF_BLOCKS["block_n"]
                    ) -> tuple[jax.Array, jax.Array]:
    """(B, N) fused LIF update.  Inputs must be pre-padded to block multiples
    (the ops.py wrapper handles padding/unpadding)."""
    B, N = u_prev.shape
    assert B % block_b == 0 and N % block_n == 0, (B, N, block_b, block_n)
    grid = (B // block_b, N // block_n)
    spec = pl.BlockSpec((block_b, block_n), lambda i, j: (i, j))
    kernel = functools.partial(_lif_kernel, beta=beta, threshold=threshold,
                               reset_mechanism=reset_mechanism)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((B, N), u_prev.dtype),
                   jax.ShapeDtypeStruct((B, N), u_prev.dtype)),
        interpret=kernel_interpret(),
    )(u_prev, s_prev, current)

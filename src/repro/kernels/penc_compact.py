"""PENC spike-address compaction as a Pallas TPU kernel.

The paper's Event Control Unit priority-encodes an n-bit spike train into a
shift register of spike ADDRESSES (one per cycle).  The TPU-idiomatic
equivalent extracts, per row, the indices of firing neurons packed to the
front of a fixed-capacity buffer — implemented as a *one-hot matmul*
compaction so the scatter runs on the MXU instead of serial address logic:

    pos[n]  = cumsum(spike)[n] - 1              (running address slot)
    sel     = onehot(pos) * spike               (N x K selection matrix)
    out[k]  = sum_n n * sel[n, k]               (a matmul)

Rows are tiled over VMEM; capacity K bounds per-tile traffic exactly like
the paper's 100-bit PENC chunk bounds FPGA routing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import kernel_interpret


def _penc_kernel(s_ref, idx_ref, cnt_ref, *, capacity: int):
    s = s_ref[...]                                   # (block_b, N) {0,1}
    n = s.shape[-1]
    pos = jnp.cumsum(s, axis=-1) - s                 # slot per spike
    slots = jnp.arange(capacity, dtype=s.dtype)
    # selection tensor (b, n, k): spike n writes slot k
    sel = (pos[..., None] == slots[None, None, :]) * s[..., None]
    iota = jnp.arange(n, dtype=jnp.float32)
    idx = jnp.einsum("n,bnk->bk", iota, sel.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    count = jnp.sum(s, axis=-1).astype(jnp.int32)    # (b,)
    valid = slots[None, :] < count[:, None].astype(s.dtype)
    idx_ref[...] = jnp.where(valid, idx, -1.0).astype(jnp.int32)
    cnt_ref[...] = count


def penc_compact_pallas(spikes: jax.Array, *, capacity: int,
                        block_b: int = 8) -> tuple[jax.Array, jax.Array]:
    """spikes: (B, N) in {0,1} -> (indices (B, capacity) int32 with -1 pad,
    counts (B,) int32).  Spikes beyond ``capacity`` per row are dropped
    (the ECU's chunk bound); B must be a multiple of block_b (ops pads)."""
    B, N = spikes.shape
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)
    kernel = functools.partial(_penc_kernel, capacity=capacity)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, N), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((block_b, capacity), lambda i: (i, 0)),
                   pl.BlockSpec((block_b,), lambda i: (i,))),
        out_shape=(jax.ShapeDtypeStruct((B, capacity), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32)),
        interpret=kernel_interpret(),
    )(spikes)

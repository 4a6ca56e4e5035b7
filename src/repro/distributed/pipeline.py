"""SPMD pipeline parallelism (GPipe schedule over a mesh axis).

Layers are partitioned into S stages; stage s's parameters live on the
devices of mesh axis ``stage`` index s (leading-dim sharding).  Microbatches
stream through: at step t, stage s processes microbatch t-s while
``ppermute`` rotates activations to the next stage — the classic GPipe
pipeline with S-1 bubble steps, expressed as a single SPMD program
(no per-stage processes).

Intended for depth-dominated models at node counts where a 2D (data, model)
mesh runs out of useful tensor-parallel width; at the assignment's 16x16
mesh none of the ten archs needs it, so it ships as a first-class optional
feature with its own correctness tests (tests/test_pipeline.py).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

PyTree = Any


def pipeline_apply(stage_fn: Callable, stage_params: PyTree,
                   micro_inputs: jax.Array, mesh,
                   axis: str = "stage") -> jax.Array:
    """Run ``stage_fn`` as an S-stage pipeline.

    stage_fn(params_slice, x) -> y with x.shape == y.shape (the activation
    that flows between stages).
    stage_params: pytree whose leaves lead with dim S (one slice per stage).
    micro_inputs: (n_micro, ...) microbatched inputs.
    Returns (n_micro, ...) outputs of the final stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = micro_inputs.shape[0]
    steps = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params, micro):
        # params leaves: (1, ...) local stage slice; micro: (n_micro, ...)
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        carry = jnp.zeros_like(micro[0])
        outputs = jnp.zeros_like(micro)
        for t in range(steps):
            mb_in = micro[min(t, n_micro - 1)]
            x = jnp.where(stage == 0, mb_in, carry)
            y = stage_fn(params, x)
            out_idx = t - (n_stages - 1)
            if out_idx >= 0:
                # only the LAST stage's value is meaningful here; other
                # stages write garbage that their shard of `outputs` keeps
                # locally and is discarded by the out_spec (last stage owns
                # the gather below)
                outputs = outputs.at[out_idx].set(
                    jnp.where(stage == n_stages - 1, y, outputs[out_idx]))
            carry = jax.lax.ppermute(y, axis, perm)
        # broadcast the last stage's outputs to every shard so the
        # replicated out_spec is consistent
        last = jax.lax.ppermute(
            outputs, axis, [((n_stages - 1 + i) % n_stages, i)
                            for i in range(n_stages)])
        return last

    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, micro_inputs)


def stack_stages(layer_params: PyTree, n_stages: int) -> PyTree:
    """Regroup per-layer stacked params (L, ...) into (S, L/S, ...)."""
    def regroup(leaf):
        L = leaf.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])

    return jax.tree.map(regroup, layer_params)

"""SNN training driver (the paper's "Training Phase").

Surrogate-gradient descent (fast-sigmoid) + BPTT through the ``lax.scan``
time loop, rate-coded inputs, population-coded outputs, rate cross-entropy.
After training, ``dump_traces`` extracts the spike traffic + weights that the
Configuration Phase feeds to the accelerator model — the JAX equivalent of
the paper's snntorch dump.

Every entry point threads ``matmul_backend`` (``"jnp"`` | ``"spike_gemm"``
| ``"spike_gemm_fused"``, DESIGN.md §11–§12) down to ``snn.apply``; the
kernel backends run both the forward accumulate AND the BPTT cotangent
matmuls block-skip, and the fused backend folds the LIF update into the
accumulate epilogue.  All three are training-equivalent — same loss
trajectory, bit-identical traces — so cached DSE cells stay backend-free.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core import encoding, snn
from repro.core.accelerator import cycle_model
from repro.data import synthetic
from repro.kernels import ops as kernel_ops

PyTree = Any


@dataclasses.dataclass
class TrainResult:
    params: PyTree
    train_loss: list[float]
    test_accuracy: float
    cfg: snn.SNNConfig


def _encode_input(key: jax.Array, x: jax.Array, num_steps: int) -> jax.Array:
    if x.ndim == 5:        # pre-encoded event data (B, T, H, W, C), in
        # the type it is held in: ``snn.apply`` casts one step at a time
        return x.transpose(1, 0, 2, 3, 4)
    return encoding.rate_encode(key, x, num_steps)


def loss_fn(cfg: snn.SNNConfig, params: PyTree, key: jax.Array,
            x: jax.Array, y: jax.Array,
            matmul_backend: Optional[str] = None) -> jax.Array:
    spikes_in = _encode_input(key, x, cfg.num_steps)
    out_train = snn.apply(cfg, params, spikes_in,
                          matmul_backend=matmul_backend)
    return encoding.rate_loss(out_train, y, cfg.num_classes)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _predict(cfg: snn.SNNConfig, matmul_backend: Optional[str],
             params: PyTree, key: jax.Array, x: jax.Array):
    spikes_in = _encode_input(key, x, cfg.num_steps)
    out_train = snn.apply(cfg, params, spikes_in,
                          matmul_backend=matmul_backend)
    return encoding.population_decode(out_train, cfg.num_classes)


def evaluate(cfg: snn.SNNConfig, params: PyTree, x: np.ndarray, y: np.ndarray,
             batch_size: int = 256, seed: int = 1234,
             matmul_backend: Optional[str] = None) -> float:
    backend = snn.resolve_matmul_backend(matmul_backend)
    correct, total = 0, 0
    key = jax.random.key(seed)
    for i in range(0, len(x), batch_size):
        key, sub = jax.random.split(key)
        xb = jnp.asarray(x[i:i + batch_size])
        pred = _predict(cfg, backend, params, sub, xb)
        correct += int((np.asarray(pred) == y[i:i + batch_size]).sum())
        total += len(y[i:i + batch_size])
    return correct / max(total, 1)


def make_train_step(cfg: snn.SNNConfig, tx,
                    matmul_backend: Optional[str] = None):
    """One SGD step of the training loop as a pure ``(params, opt_state,
    key, x, y) -> (params, opt_state, loss)`` function — unjitted, so
    callers can wrap it in ``jax.jit`` (the solo loop below, through the
    per-signature cache ``_jitted_train_step``) or ``jax.vmap`` it over a
    leading cell axis first (``distributed.cellstack`` trains whole
    same-signature cell stacks through this exact function, which is what
    keeps stacked and solo training bit-identical).  The cache holds one
    executable per signature, for the last ``SIGNATURES_KEPT`` signatures."""
    backend = snn.resolve_matmul_backend(matmul_backend)

    def train_step(params, opt_state, key, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, key, x, y,
                              matmul_backend=backend))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


# Signatures whose jitted train step and trace dump stay compiled, well
# above the few dozen cells of a Fig-8 sweep.  Past it the least recently
# used signature's programs are dropped, and JAX frees its executables.
SIGNATURES_KEPT = 64


@functools.lru_cache(maxsize=SIGNATURES_KEPT)
def _jitted_train_step(make, cfg: snn.SNNConfig, lr: float, backend: str):
    """``jax.jit(make(cfg, optim.adam(lr), backend))``, built once per
    ``(make, cfg, lr, backend)`` and reused by every later cell of that
    signature, so a cell traces and lowers no train step of its own; jit's
    own cache still keys the shapes (batch, T).  ``make`` is part of the key
    so a replaced ``make_train_step`` gets a program of its own."""
    return jax.jit(make(cfg, optim.adam(lr), backend))


def init_cell(cfg: snn.SNNConfig, tx, seed: int):
    """The exact (params, opt_state, key) chain ``train`` starts from.

    Kept host-side and per-cell on purpose: ``jax.random.normal`` under
    ``vmap`` draws *different* bits than the solo call, so stacked trainers
    must initialize each cell through this function and stack the results
    rather than vmap the initializer (DESIGN.md §14)."""
    key = jax.random.key(seed)
    key, pkey = jax.random.split(key)
    params = snn.init_params(pkey, cfg)
    return params, tx.init(params), key


def train(cfg: snn.SNNConfig, data: synthetic.Dataset, *,
          steps: int = 300, batch_size: int = 64, lr: float = 2e-3,
          seed: int = 0, log_every: int = 50, verbose: bool = False,
          matmul_backend: Optional[str] = None) -> TrainResult:
    backend = snn.resolve_matmul_backend(matmul_backend)
    tx = optim.adam(lr)
    params, opt_state, key = init_cell(cfg, tx, seed)
    train_step = _jitted_train_step(make_train_step, cfg, lr, backend)

    losses = []
    it = synthetic.batches(data.x_train, data.y_train, batch_size,
                           seed=seed, epochs=10_000)
    for step_i in range(steps):
        xb, yb = next(it)
        key, sub = jax.random.split(key)
        params, opt_state, loss = train_step(
            params, opt_state, sub, jnp.asarray(xb), jnp.asarray(yb))
        losses.append(float(loss))
        if verbose and step_i % log_every == 0:
            print(f"step {step_i:4d}  loss {float(loss):.4f}")

    acc = evaluate(cfg, params, data.x_test, data.y_test,
                   matmul_backend=backend)
    return TrainResult(params=params, train_loss=losses, test_accuracy=acc, cfg=cfg)


@functools.lru_cache(maxsize=SIGNATURES_KEPT)
def _jitted_dump(cfg: snn.SNNConfig, backend: str):
    """The jitted ``snn.spike_counts_per_layer`` of one ``(cfg, backend)``,
    kept like ``_jitted_train_step``."""
    def spike_counts(params: PyTree, spikes_in: jax.Array) -> list[jax.Array]:
        return snn.spike_counts_per_layer(cfg, params, spikes_in,
                                          matmul_backend=backend)
    return jax.jit(spike_counts)


def dump_traces(cfg: snn.SNNConfig, params: PyTree, x: np.ndarray,
                seed: int = 7, max_samples: int = 64,
                matmul_backend: Optional[str] = None) -> dict:
    """Extract spike-traffic statistics for the accelerator model.

    Returns per-layer input spike counts with shape (T, N) (N = samples) —
    the Configuration-Phase artifact the cycle model consumes.  The counts
    are backend-invariant (tests/test_train_backend.py), so cached DSE cells
    never depend on which matmul path trained them.
    """
    key = jax.random.key(seed)
    xb = jnp.asarray(x[:max_samples])
    spikes_in = _encode_input(key, xb, cfg.num_steps)
    dump = _jitted_dump(cfg, snn.resolve_matmul_backend(matmul_backend))
    counts = dump(params, spikes_in)
    return {
        "layer_input_spike_counts": [np.asarray(c) for c in counts],
        "layer_sizes": cfg.layer_sizes(),
        "num_steps": cfg.num_steps,
    }


def trace_counts(cfg: snn.SNNConfig, params: PyTree, x: np.ndarray,
                 seed: int = 7, max_samples: int = 64,
                 matmul_backend: Optional[str] = None) -> list[np.ndarray]:
    """``dump_traces`` reduced to the per-layer (T,) mean traffic the cycle
    model consumes — the Configuration-Phase artifact most callers want."""
    traces = dump_traces(cfg, params, x, seed=seed, max_samples=max_samples,
                         matmul_backend=matmul_backend)
    return cycle_model.counts_from_traces(traces["layer_input_spike_counts"])


def train_firing_permutation(train: jax.Array) -> jax.Array:
    """THE profiling statistic of the kernel path: per-input-neuron mean
    firing rate of a (T, B, ...) spike train, sorted cold-first
    (``ops.firing_rate_permutation``).  Single definition so the benchmark's
    ``skip_fraction_profiled`` measures exactly the permutation training
    would apply."""
    flat = train.reshape(-1, int(np.prod(train.shape[2:])))
    return kernel_ops.firing_rate_permutation(flat.mean(0))


def profiled_permutations(cfg: snn.SNNConfig, params: PyTree, x: np.ndarray,
                          seed: int = 7, max_samples: int = 64) -> list:
    """Per-layer pre-synaptic permutations from profiled firing rates.

    Runs a profiling pass over ``x`` and sorts each Dense layer's input axis
    by observed firing rate (``train_firing_permutation``) so cold neurons
    cluster into skippable MXU tiles.  Returns a list aligned with
    ``cfg.layers`` (``None`` for Conv/MaxPool), ready for
    ``snn.apply(..., matmul_backend="spike_gemm", layer_perms=...)``.
    """
    key = jax.random.key(seed)
    xb = jnp.asarray(x[:max_samples])
    spikes_in = _encode_input(key, xb, cfg.num_steps)
    trains = iter(snn.layer_input_trains(cfg, params, spikes_in))
    perms: list = []
    for spec in cfg.layers:
        perm = None
        if isinstance(spec, (snn.Dense, snn.Conv)):
            train = next(trains)
            if isinstance(spec, snn.Dense):
                perm = train_firing_permutation(train)
        perms.append(perm)
    return perms

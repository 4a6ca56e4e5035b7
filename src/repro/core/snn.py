"""Spiking network definition (the paper's model substrate, in JAX).

Networks are declared as a sequence of layer specs (``Dense``, ``Conv``,
``MaxPool``) mirroring the topologies in the paper's Table I (net-1..net-5).
The temporal dimension is driven by ``lax.scan`` (BPTT unrolls through it);
every spiking layer's output train is returned so that

* ``repro.core.sparsity`` can reproduce the Fig.-1 firing-ratio analysis, and
* ``repro.core.accelerator.cycle_model`` can be driven by the *actual* spike
  traffic of the trained model — the paper's "dump spikes from snntorch"
  step.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core.lif import LIFParams, lif_step, spike_fn
from repro.kernels import ops as kernel_ops

PyTree = Any

# ---------------------------------------------------------------------------
# Matmul backends (DESIGN.md §11–§12)
# ---------------------------------------------------------------------------
# The accumulate phase of every Dense layer can run on the pure-jnp
# reference matmul, the block-skip Pallas kernel (``repro.kernels``, wrapped
# in a custom_vjp whose backward is also block-skip), or the fused
# GEMM+LIF scan-step kernel (``spike_gemm_fused``: the LIF update runs in
# the accumulate epilogue so membrane state never round-trips through HBM).
# Conv layers run the same block-skip accumulate over their im2col patch
# matrix (``kernels/spike_conv.py``) on both kernel backends.  ``None``
# resolves through the environment so DSE cell training can opt whole
# processes in without threading a flag.

MATMUL_BACKENDS = ("jnp", "spike_gemm", "spike_gemm_fused")
MATMUL_BACKEND_ENV = "REPRO_MATMUL_BACKEND"

#: kernel tile shape on the training path: batch rows are few (``block_m``
#: shares the f32 sublane minimum with the standalone LIF kernel's
#: ``block_b`` — one constant, see kernels/lif_step.py) while K rides full
#: 128-lane tiles — the skip granule benchmarks/bench_kernels.py measures.
KERNEL_BLOCKS = {"block_m": kernel_ops.LIF_BLOCKS["block_b"],
                 "block_n": 128, "block_k": 128}


def resolve_matmul_backend(backend: Optional[str] = None) -> str:
    """Resolve an explicit backend choice, falling back to the
    ``REPRO_MATMUL_BACKEND`` environment variable, then ``"jnp"``."""
    backend = backend or os.environ.get(MATMUL_BACKEND_ENV) or "jnp"
    if backend not in MATMUL_BACKENDS:
        raise ValueError(f"unknown matmul backend {backend!r}; "
                         f"pick from {MATMUL_BACKENDS}")
    return backend


# ---------------------------------------------------------------------------
# Layer specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Dense:
    features: int
    lif: LIFParams = LIFParams()


@dataclasses.dataclass(frozen=True)
class Conv:
    features: int
    kernel: int = 3
    stride: int = 1
    padding: str = "SAME"
    lif: LIFParams = LIFParams()


@dataclasses.dataclass(frozen=True)
class MaxPool:
    """Spike OR-pooling, non-overlapping (paper Sec. V-C: 2x2 OR gate)."""
    window: int = 2


LayerSpec = Union[Dense, Conv, MaxPool]


@dataclasses.dataclass(frozen=True)
class SNNConfig:
    """A full spiking model: topology + coding hyper-parameters."""
    name: str
    input_shape: tuple[int, ...]          # (H, W, C) for conv nets, (D,) for MLPs
    layers: tuple[LayerSpec, ...]
    num_classes: int
    pcr: int = 1                          # population-coding ratio (neurons/class)
    num_steps: int = 25                   # spike-train length T

    @property
    def output_features(self) -> int:
        return self.num_classes * self.pcr

    def layer_sizes(self) -> list[int]:
        """Logical neuron count of every *spiking* layer (used for LHR sizing)."""
        sizes = []
        shape = self.input_shape
        for spec in self.layers:
            shape = _out_shape(spec, shape)
            if isinstance(spec, (Dense, Conv)):
                sizes.append(int(math.prod(shape)))
        return sizes

    def spiking_layers(self) -> list[LayerSpec]:
        return [l for l in self.layers if isinstance(l, (Dense, Conv))]


def _out_shape(spec: LayerSpec, in_shape: tuple[int, ...]) -> tuple[int, ...]:
    if isinstance(spec, Dense):
        return (spec.features,)
    if isinstance(spec, Conv):
        h, w, _ = in_shape
        if spec.padding == "SAME":
            oh, ow = -(-h // spec.stride), -(-w // spec.stride)
        else:
            oh = (h - spec.kernel) // spec.stride + 1
            ow = (w - spec.kernel) // spec.stride + 1
        return (oh, ow, spec.features)
    if isinstance(spec, MaxPool):
        h, w, c = in_shape
        return (h // spec.window, w // spec.window, c)
    raise TypeError(spec)


def output_shapes(cfg: SNNConfig) -> list[tuple[int, ...]]:
    shapes, shape = [], cfg.input_shape
    for spec in cfg.layers:
        shape = _out_shape(spec, shape)
        shapes.append(shape)
    return shapes


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: SNNConfig, dtype=jnp.float32) -> PyTree:
    params = []
    shape = cfg.input_shape
    for spec in cfg.layers:
        if isinstance(spec, Dense):
            fan_in = int(math.prod(shape))
            key, sub = jax.random.split(key)
            w = jax.random.normal(sub, (fan_in, spec.features), dtype) / math.sqrt(fan_in)
            params.append({"w": w, "b": jnp.zeros((spec.features,), dtype)})
        elif isinstance(spec, Conv):
            cin = shape[-1]
            fan_in = spec.kernel * spec.kernel * cin
            key, sub = jax.random.split(key)
            w = jax.random.normal(
                sub, (spec.kernel, spec.kernel, cin, spec.features), dtype
            ) / math.sqrt(fan_in)
            params.append({"w": w, "b": jnp.zeros((spec.features,), dtype)})
        else:
            params.append({})
        shape = _out_shape(spec, shape)
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _layer_current(spec: LayerSpec, p: PyTree, s_in: jax.Array,
                   matmul_backend: str = "jnp",
                   perm: Optional[jax.Array] = None) -> jax.Array:
    """Synaptic current for one layer given the pre-synaptic spike tensor.

    The binary matmul here is the accelerator's accumulate phase.  With
    ``matmul_backend="spike_gemm"`` Dense layers route through
    ``repro.kernels`` (block-skip Pallas forward and backward via
    custom_vjp); the jnp path is the reference semantics.  The
    ``"spike_gemm_fused"`` backend bypasses this function entirely for Dense
    layers — ``step`` calls the fused GEMM+LIF kernel instead, so only jnp
    and spike_gemm (and every Conv layer) land here.  Conv layers route
    through the patch-tiled block-skip kernel (``ops.spike_conv_train``) on
    BOTH kernel backends; there is no fused conv epilogue, so the fused
    backend shares the spike_gemm conv path.  ``perm`` is an optional
    profiled pre-synaptic permutation (``ops.firing_rate_permutation``)
    that clusters cold neurons into skippable tiles — applied as
    ``S[:, perm] @ W[perm, :]``, which leaves the product invariant
    (Dense-only; conv layers take no permutation).
    """
    if isinstance(spec, Dense):
        flat = s_in.reshape(s_in.shape[0], -1)
        if matmul_backend == "spike_gemm":
            w = p["w"]
            if perm is not None:
                flat, w = kernel_ops.apply_permutation(flat, w, perm)
            return kernel_ops.spike_gemm_train(flat, w,
                                               **KERNEL_BLOCKS) + p["b"]
        return flat @ p["w"] + p["b"]
    if isinstance(spec, Conv):
        if matmul_backend in ("spike_gemm", "spike_gemm_fused"):
            return kernel_ops.spike_conv_train(
                s_in, p["w"], stride=spec.stride, padding=spec.padding,
                **KERNEL_BLOCKS) + p["b"]
        out = jax.lax.conv_general_dilated(
            s_in, p["w"],
            window_strides=(spec.stride, spec.stride),
            padding=spec.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return out + p["b"]
    raise TypeError(spec)


def _fused_dense_step(spec: Dense, p: PyTree, s_in: jax.Array,
                      state: tuple[jax.Array, jax.Array],
                      perm: Optional[jax.Array]
                      ) -> tuple[jax.Array, jax.Array]:
    """Accumulate + bias + LIF update in one Pallas pass
    (``matmul_backend="spike_gemm_fused"``): the kernel's epilogue applies
    the membrane update while the accumulator tile is VMEM-resident, so the
    (B, N) current never round-trips through HBM (DESIGN.md §12)."""
    flat = s_in.reshape(s_in.shape[0], -1)
    w = p["w"]
    if perm is not None:
        flat, w = kernel_ops.apply_permutation(flat, w, perm)
    u_prev, s_prev = state
    lif = spec.lif
    return kernel_ops.spike_gemm_lif_step(
        flat, w, p["b"], u_prev, s_prev,
        beta=lif.beta, threshold=lif.threshold, slope=lif.slope,
        reset_mechanism=lif.reset_mechanism, **KERNEL_BLOCKS)


def _or_pool(s: jax.Array, window: int) -> jax.Array:
    return jax.lax.reduce_window(
        s, -jnp.inf, jax.lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, window, window, 1),
        padding="VALID",
    )


def init_states(cfg: SNNConfig, batch: int, dtype=jnp.float32) -> list:
    states, shape = [], cfg.input_shape
    for spec in cfg.layers:
        shape = _out_shape(spec, shape)
        if isinstance(spec, (Dense, Conv)):
            z = jnp.zeros((batch,) + shape, dtype)
            states.append((z, z))
        else:
            states.append(None)
    return states


def step(cfg: SNNConfig, params: PyTree, states: list, s_in: jax.Array,
         matmul_backend: str = "jnp",
         layer_perms: Optional[Sequence] = None
         ) -> tuple[list, list[jax.Array]]:
    """One time step through all layers.

    Returns (new_states, per-spiking-layer output spikes).  Note the hardware
    is layer-pipelined so different layers process different time steps
    concurrently; functionally (spike-to-spike) the result is identical to
    this sequential sweep, which is what the validation checks.

    ``layer_perms``: optional per-layer pre-synaptic permutations aligned
    with ``cfg.layers`` (``None`` entries for unpermuted layers; see
    ``train_snn.profiled_permutations``).
    """
    if layer_perms is not None and len(layer_perms) != len(cfg.layers):
        raise ValueError(f"layer_perms has {len(layer_perms)} entries for "
                         f"{len(cfg.layers)} layers")
    perms = layer_perms or (None,) * len(cfg.layers)
    new_states, spikes = [], []
    x = s_in
    for spec, p, st, perm in zip(cfg.layers, params, states, perms):
        if isinstance(spec, Dense) and matmul_backend == "spike_gemm_fused":
            u, s = _fused_dense_step(spec, p, x, st, perm)
            new_states.append((u, s))
            spikes.append(s)
            x = s
        elif isinstance(spec, (Dense, Conv)):
            cur = _layer_current(spec, p, x, matmul_backend, perm)
            u_prev, s_prev = st
            u, s = lif_step(u_prev, s_prev, cur, spec.lif)
            new_states.append((u, s))
            spikes.append(s)
            x = s
        elif isinstance(spec, MaxPool):
            x = _or_pool(x, spec.window)
            new_states.append(None)
        else:
            raise TypeError(spec)
    return new_states, spikes


# ---------------------------------------------------------------------------
# The time loop and its BPTT memory
# ---------------------------------------------------------------------------

#: Bytes of carries (each spiking layer's float32 membrane and spikes) that
#: one BPTT scan may keep for all its time steps.  Past it the backward
#: pass is rematerialised (``remat_chunk``).  At batch 64, net-3 (T 51)
#: keeps about 61 MB and ``dvs-conv`` (T 16) about 0.1 GB, under it; net-5
#: (128x128x2 events, T 124) would keep 41.7 GB.
REMAT_BUDGET_BYTES = 2 ** 30


def remat_chunk(cfg: SNNConfig, batch: int, num_steps: int) -> int:
    """Time steps in each rematerialised chunk of the BPTT scan over
    ``num_steps`` steps at ``batch``: ``num_steps`` itself (one scan that
    keeps every step's residuals) where all steps' carries fit
    ``REMAT_BUDGET_BYTES``, else about sqrt(T), so the backward pass holds
    the carry entering each chunk and one chunk's carries at a time."""
    carry_bytes = 2 * 4 * sum(cfg.layer_sizes())
    if num_steps * batch * carry_bytes <= REMAT_BUDGET_BYTES:
        return num_steps
    return max(1, round(math.sqrt(num_steps)))


def _entering(cfg: SNNConfig, s_in: jax.Array,
              spikes: list[jax.Array]) -> list[jax.Array]:
    """The spikes entering each spiking layer at one time step: the input,
    then every spiking layer's output but the last, OR-pooled by the pools
    that follow it (the hardware's ECU sees the pooled train)."""
    trains, outputs = [s_in], iter(spikes)
    for spec in cfg.layers:
        if isinstance(spec, MaxPool):
            if len(trains) > 1:
                trains[-1] = _or_pool(trains[-1], spec.window)
        else:
            trains.append(next(outputs))
    return trains[:-1]


def _run(cfg: SNNConfig, params: PyTree, spike_input: jax.Array, emit,
         matmul_backend: Optional[str], layer_perms: Optional[Sequence]):
    """``emit(s_in, spikes)`` of every time step of the net over a (T, B,
    ...) input train, stacked over time.  Each step's input is cast to
    float32 on the device inside the scan, so an input train held in bytes
    is never widened whole.  Where ``remat_chunk`` picks chunks shorter
    than T, the backward pass is rematerialised (``_remat_scan``); the
    result is the same either way."""
    backend = resolve_matmul_backend(matmul_backend)
    num_steps, batch = spike_input.shape[:2]

    def body(params, perms, states, s_in):
        s_in = s_in.astype(jnp.float32)
        new_states, spikes = step(cfg, params, states, s_in,
                                  matmul_backend=backend, layer_perms=perms)
        return new_states, emit(s_in, spikes)

    states0 = init_states(cfg, batch)
    chunk = remat_chunk(cfg, batch, num_steps)
    if chunk >= num_steps:
        return jax.lax.scan(functools.partial(body, params, layer_perms),
                            states0, spike_input)[1]

    # a kept carry is each layer's membrane, flat: its spikes are the
    # membrane over threshold, as the LIF update made them
    def pack(states):
        return [None if st is None else st[0].reshape(-1) for st in states]

    def unpack(flat):
        states = []
        for spec, u, zero in zip(cfg.layers, flat, states0):
            if u is not None:
                u = u.reshape(zero[0].shape)
                u = (u, spike_fn(u - spec.lif.threshold, spec.lif.slope))
            states.append(u)
        return states

    return _remat_scan(body, chunk, pack, unpack, params, layer_perms,
                       states0, spike_input)


def _chunks(a: jax.Array, chunk: int) -> jax.Array:
    """A time-major array padded with zeros at its end to whole chunks, as
    (chunks, chunk, ...)."""
    pad = jnp.zeros((-a.shape[0] % chunk,) + a.shape[1:], a.dtype)
    a = jnp.concatenate([a, pad])
    return a.reshape((-1, chunk) + a.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _remat_scan(body, chunk, pack, unpack, params, consts, carry, xs):
    """The stacked outputs of ``body(params, consts, carry, x) -> (carry,
    y)`` over ``xs``'s leading axis, with a rematerialised backward pass:
    the forward keeps the carry entering each chunk of ``chunk`` steps, as
    ``pack(carry)``, and the backward recomputes one chunk's carries at a
    time from it (``unpack`` rebuilds the carry bit for bit).

    It is written out, not left to ``jax.checkpoint`` of each chunk's scan:
    autodiff of a scan of scans sums the parameters' gradient chunk by
    chunk and then over chunks, which rounds otherwise than one scan.  Here
    it is one running sum from step T back to step 1, in one scan's order,
    so the gradient is one scan's, bit for bit.  Where ``chunk`` does not
    divide T, the time axis is padded at its end with zero-input steps
    whose outputs are dropped: their cotangents are zero, so they add exact
    zeros to that sum.  ``consts`` (the layer permutations) and ``xs`` (the
    input train, data) get no cotangent."""
    return jax.lax.scan(functools.partial(body, params, consts), carry,
                        xs)[1]


def _remat_scan_fwd(body, chunk, pack, unpack, params, consts, carry, xs):
    def part(c, x):
        end, ys = jax.lax.scan(functools.partial(body, params, consts), c, x)
        return end, (pack(c), ys)

    _, (starts, ys) = jax.lax.scan(part, carry, _chunks(xs, chunk))
    num_steps = xs.shape[0]
    ys = jax.tree.map(lambda y: y.reshape((-1,) + y.shape[2:])[:num_steps],
                      ys)
    return ys, (params, consts, xs, starts)


def _remat_scan_bwd(body, chunk, pack, unpack, res, ct_ys):
    params, consts, xs, starts = res

    def back_chunk(cts, part):
        start, x, ct_y = part
        with jax.named_scope("bptt_recompute"):
            _, entering = jax.lax.scan(
                lambda c, x: (body(params, consts, c, x)[0], pack(c)),
                unpack(start), x)

        def back_step(cts, inputs):
            acc, ct_carry = cts
            c, x, ct_y = inputs
            with jax.named_scope("bptt_recompute"):
                _, vjp = jax.vjp(lambda p, c: body(p, consts, c, x), params,
                                 unpack(c))
            g_params, ct_carry = vjp((ct_carry, ct_y))
            return (jax.tree.map(jnp.add, acc, g_params), ct_carry), None

        return jax.lax.scan(back_step, cts, (entering, x, ct_y),
                            reverse=True)[0], None

    carry = unpack(jax.tree.map(lambda s: s[0], starts))
    cts = (jax.tree.map(jnp.zeros_like, params),
           jax.tree.map(jnp.zeros_like, carry))
    (g_params, ct_carry), _ = jax.lax.scan(
        back_chunk, cts,
        (starts, _chunks(xs, chunk),
         jax.tree.map(lambda a: _chunks(a, chunk), ct_ys)), reverse=True)
    return g_params, None, ct_carry, None


_remat_scan.defvjp(_remat_scan_fwd, _remat_scan_bwd)


def apply(cfg: SNNConfig, params: PyTree, spike_input: jax.Array,
          return_all_layers: bool = False,
          matmul_backend: Optional[str] = None,
          layer_perms: Optional[Sequence] = None):
    """Run the net over a (T, B, ...) input spike train.

    Returns the output layer's (T, B, n_out) spike train; with
    ``return_all_layers`` also every hidden layer's train (instrumentation).
    ``matmul_backend``/``layer_perms`` select the accumulate-phase execution
    path (see ``_layer_current``); results are backend-invariant.
    """
    def emit(s_in, spikes):
        return spikes if return_all_layers else spikes[-1]
    return _run(cfg, params, spike_input, emit, matmul_backend, layer_perms)


def layer_input_trains(cfg: SNNConfig, params: PyTree,
                       spike_input: jax.Array,
                       matmul_backend: Optional[str] = None
                       ) -> list[jax.Array]:
    """The (T, B, ...) spike train **entering** each spiking layer.

    Entry ``l`` is spiking layer ``l``'s input traffic (entry 0 is the
    encoded input train, in float32); pooling between layers is applied
    first, because the hardware's ECU sees the pooled train.  This is the
    statistic behind both the cycle model (``spike_counts_per_layer``) and
    the profile-guided tile permutation
    (``train_snn.profiled_permutations``).
    """
    return _run(cfg, params, spike_input,
                lambda s_in, spikes: _entering(cfg, s_in, spikes),
                matmul_backend, None)


def spike_counts_per_layer(cfg: SNNConfig, params: PyTree,
                           spike_input: jax.Array,
                           matmul_backend: Optional[str] = None
                           ) -> list[jax.Array]:
    """Per-layer **input** spike counts, shape (T, B) each — the traffic
    statistic that drives the accelerator cycle model.

    Entry ``l`` counts spikes entering spiking layer ``l`` (so entry 0 counts
    the encoded input train); see ``layer_input_trains``.  The counts are
    summed inside the time loop, step by step, so no layer's whole train is
    held.
    """
    def emit(s_in, spikes):
        return [t.reshape(t.shape[0], -1).sum(-1)
                for t in _entering(cfg, s_in, spikes)]
    return _run(cfg, params, spike_input, emit, matmul_backend, None)

"""Kernel benchmarks: micro (block-skip rates + fused-LIF timings) and an
end-to-end BPTT benchmark of the training hot path.

The micro section reports skip fractions of the sparsity-aware spike GEMM on
real trained-SNN traffic (the TPU-granular analogue of the paper's PENC
savings).  The BPTT section times the forward (``loss_fn``) and one full
forward+backward training step (``jax.value_and_grad`` of the rate loss
through ``lax.scan``) for all three matmul backends — pure jnp, the
block-skip Pallas kernel (now with block-skip *backward* kernels behind its
custom_vjp), and the fused GEMM+LIF scan-step kernel — across the built-in
workloads' T x population grid, emitting one JSON line per cell in the
``BENCH_*.json`` schema (``*_fwd_seconds`` / ``*_bwd_seconds`` /
``*_step_seconds`` per backend, ``skip_fraction`` / ``bwd_skip_fraction``)
so ``tools/bench_diff.py`` tracks the training hot path across runs.  Conv
workloads (dvs-conv) are first-class cells: their Conv layers route through
the patch-tiled block-skip kernel and their skip fractions are measured on
the im2col patch matrices the kernel actually tiles.

The kernels run the way ``repro.runtime`` decides from the platform: under
``JAX_PLATFORMS=cpu`` they are interpreted, so wall-clock measures the
Pallas interpreter and only the SKIP FRACTION carries over; on a TPU they
compile to Mosaic and the timings are the chip's.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, emit_json, timed, timed_compile
from repro import runtime
from repro.core import encoding, snn, train_snn
from repro.core.workloads import registry
from repro.data import synthetic
from repro.kernels import ops, ref


def _micro(quick: bool) -> None:
    # trained-model traffic
    data = synthetic.make_images(seed=0, n_train=512, n_test=128)
    cfg = snn.SNNConfig(name="k", input_shape=(28, 28),
                        layers=(snn.Dense(256), snn.Dense(256),
                                snn.Dense(10 * 5)),
                        num_classes=10, pcr=5, num_steps=15)
    res = train_snn.train(cfg, data, steps=60 if quick else 150,
                          batch_size=64)
    x = jnp.asarray(data.x_test[:32])
    spikes_in = encoding.rate_encode(jax.random.key(0), x, cfg.num_steps)
    all_spikes = snn.apply(cfg, res.params, spikes_in,
                           return_all_layers=True)
    layer_w = [res.params[0]["w"], res.params[1]["w"], res.params[2]["w"]]
    trains = [spikes_in.reshape(-1, 784)] + [
        s.reshape(-1, s.shape[-1]) for s in all_spikes[:-1]]
    for l, (train, w) in enumerate(zip(trains, layer_w)):
        density = float(train.mean())
        base = ops.skip_fraction(train, block_m=8, block_k=128)
        perm = ops.firing_rate_permutation(train.mean(axis=0))
        sp, wp = ops.apply_permutation(train, w, perm)
        perm_skip = ops.skip_fraction(sp, block_m=8, block_k=128)
        out, us = timed(lambda: ops.spike_gemm(
            sp[:64], wp, block_m=8).block_until_ready(), repeats=1)
        want = ref.spike_gemm_ref(sp[:64], wp)
        ok = bool(jnp.allclose(out, want, atol=1e-3))
        emit(f"kernels/spike_gemm/layer{l}", us,
             f"density={density:.3f} skip={base:.2f} "
             f"skip_profiled={perm_skip:.2f} allclose={ok}")

    # fused LIF shape sweep
    for shape in [(8, 512), (64, 4096)]:
        u = jnp.zeros(shape)
        s = jnp.zeros(shape)
        c = jnp.ones(shape) * 0.5
        (out, us) = timed(lambda: ops.lif_step(
            u, s, c, beta=0.9, threshold=1.0)[0].block_until_ready(),
            repeats=1)
        emit(f"kernels/lif_step/{shape[0]}x{shape[1]}", us,
             "interpret-mode" if runtime.kernel_interpret() else "compiled")


def _layer_skip_fractions(cfg: snn.SNNConfig, params, spikes_in
                          ) -> tuple[float, float]:
    """Mean (base, profile-permuted) tile-skip fraction over every spiking
    layer's input traffic — the tiles the kernel path actually skips.
    Dense layers measure the flattened train; Conv layers measure the
    im2col PATCH matrix their block-skip kernel tiles (spike_conv.py).
    The profiled permutation is Dense-only, so conv layers contribute their
    base skip to the profiled mean.  ``layer_input_trains`` yields exactly
    one train per spiking layer."""
    trains = snn.layer_input_trains(cfg, params, spikes_in)
    bm, bk = snn.KERNEL_BLOCKS["block_m"], snn.KERNEL_BLOCKS["block_k"]
    base, perm = [], []
    for spec, train in zip(cfg.spiking_layers(), trains):
        if isinstance(spec, snn.Dense):
            flat = train.reshape(-1, int(np.prod(train.shape[2:])))
            base.append(ops.skip_fraction(flat, bm, bk))
            p = train_snn.train_firing_permutation(train)
            perm.append(ops.skip_fraction(flat[:, p], bm, bk))
        elif isinstance(spec, snn.Conv):
            t, b = train.shape[:2]
            patches = ops.conv_patches(
                train.reshape((t * b,) + train.shape[2:]),
                spec.kernel, spec.kernel, spec.stride, spec.padding)
            frac = ops.skip_fraction(patches, bm, bk)
            base.append(frac)
            perm.append(frac)
    return float(np.mean(base)), float(np.mean(perm))


def _bptt_cell(wl: registry.Workload, T: int, pop: float) -> None:
    cfg = wl.build(T, pop)
    data = wl.make_data(T)
    res = train_snn.train(cfg, data, steps=wl.train_steps,
                          batch_size=wl.batch_size, lr=wl.lr, seed=0)
    xb = jnp.asarray(data.x_train[:wl.batch_size])
    yb = jnp.asarray(data.y_train[:wl.batch_size])
    key = jax.random.key(0)

    fields = {}
    step_seconds = {}
    for backend in snn.MATMUL_BACKENDS:
        fwd = jax.jit(
            lambda p, b=backend: train_snn.loss_fn(cfg, p, key, xb, yb,
                                                   matmul_backend=b))
        vg = jax.jit(jax.value_and_grad(
            lambda p, b=backend: train_snn.loss_fn(cfg, p, key, xb, yb,
                                                   matmul_backend=b)))
        # repeats=3: these fields are regression-tracked by bench_diff, so
        # average away single-sample scheduler noise on shared CI runners.
        # The warmup call is the explicit compile pass — its wall-clock is
        # reported separately as *_compile_seconds, never folded into the
        # steady-state per-call figures.
        _, us_fwd, c_fwd = timed_compile(
            lambda: jax.block_until_ready(fwd(res.params)), repeats=3)
        _, us, c_vg = timed_compile(
            lambda: jax.block_until_ready(vg(res.params)), repeats=3)
        step_seconds[backend] = us / 1e6
        fields[f"{backend}_fwd_seconds"] = round(us_fwd / 1e6, 6)
        # the backward's cost is the fwd+bwd step minus the fwd-only pass
        # (both jitted end to end; clamp against scheduler noise)
        fields[f"{backend}_bwd_seconds"] = round(
            max((us - us_fwd) / 1e6, 0.0), 6)
        fields[f"{backend}_step_seconds"] = round(us / 1e6, 6)
        # total jit-compile cost of this backend's cell (fwd + fwd/bwd):
        # what every fresh cellfarm worker pays once per cell, and what
        # stacked training amortizes over the whole cell stack
        fields[f"{backend}_compile_seconds"] = round(c_fwd + c_vg, 6)

    spikes_in = train_snn._encode_input(
        jax.random.key(1), jnp.asarray(data.x_test[:32]), T)
    skip, skip_profiled = _layer_skip_fractions(cfg, res.params, spikes_in)
    emit_json(f"kernels/bptt/{wl.name}/T{T}/p{pop:g}",
              speedup=round(step_seconds["jnp"]
                            / max(step_seconds["spike_gemm"], 1e-12), 4),
              fused_speedup=round(
                  step_seconds["jnp"]
                  / max(step_seconds["spike_gemm_fused"], 1e-12), 4),
              skip_fraction=round(skip, 4),
              skip_fraction_profiled=round(skip_profiled, 4),
              # dW = S^T.g reuses the forward's occupancy flags verbatim, so
              # the backward's spike-side pass skips exactly the tiles the
              # forward skips; the dS pass adds cotangent-occupancy skips on
              # top (zero early in training, grows as surrogates saturate)
              bwd_skip_fraction=round(skip, 4),
              accuracy=round(res.test_accuracy, 4),
              **fields)


def _bptt(quick: bool) -> None:
    # conv cells ride the same grid now that Conv routes through the
    # patch-tiled kernel; quick mode keeps one shrunk dvs-conv cell in CI
    names = ["mnist-mlp", "dvs-conv"] if quick else registry.names()
    for name in names:
        wl = dataclasses.replace(
            registry.get(name),
            n_train=256, n_test=64, train_steps=20 if quick else 60)
        if any(isinstance(l, snn.Conv) for l in wl.layers):
            # interpret-mode Pallas executes the B·OH·OW patch grid
            # serially — shrink the retina/batch so conv cells stay
            # benchmarkable on CPU (skip fractions are size-honest either
            # way; on the CPU wall-clock measures the interpreter)
            wl = dataclasses.replace(
                wl, input_shape=(8, 8, 2), batch_size=16, n_train=128,
                num_steps_choices=(2,) if quick else (4, 8),
                population_choices=(1.0,) if quick else (1.0, 2.0))
        Ts = wl.num_steps_choices[:2] if quick else wl.num_steps_choices
        pops = wl.population_choices[:2] if quick else wl.population_choices
        for T in Ts:
            for pop in pops:
                _bptt_cell(wl, int(T), float(pop))


def run(quick: bool = False):
    _micro(quick)
    _bptt(quick)


if __name__ == "__main__":
    run()

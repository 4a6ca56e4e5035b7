"""The solo trainer's per-signature programs: every cell of one signature
(configuration, learning rate, backend, shapes) reuses one jitted train step
and one jitted trace dump, so only a signature's first cell traces and
lowers them, and a cell resolved after another is bit for bit the cell
resolved alone."""
import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

from repro.core import snn, train_snn, workloads

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
STEP, DUMP = "jit(train_step)", "jit(spike_counts)"
ASSIGN = {"num_steps": 3, "population": 1.0}


def _mlp(backend="jnp", **kw):
    base = dict(name="tiny-trainer-cache", dataset="mnist",
                input_shape=(12, 12), layers=(snn.Dense(16), snn.Dense(10)),
                num_classes=10, pcr=1, n_train=64, n_test=16, train_steps=3,
                batch_size=16, trace_samples=8, matmul_backend=backend)
    base.update(kw)
    return workloads.Workload(**base)


def _clear():
    train_snn._jitted_train_step.cache_clear()
    train_snn._jitted_dump.cache_clear()
    jax.clear_caches()


@pytest.fixture
def lowered():
    """Names of the programs lowered to MLIR while the test runs, from a
    fresh start: no program of an earlier test is reused."""
    _clear()
    names = []

    def listen(event, seconds, **kw):
        if event == LOWERED:
            names.append(kw.get("fun_name"))
    monitoring.register_event_duration_secs_listener(listen)
    yield names
    monitoring.unregister_event_duration_listener(listen)


def _resolve(tmp_path, wl, seed, assignment=ASSIGN):
    cache = workloads.TraceCache(root=str(tmp_path / f"{wl.name}-{seed}"))
    art = cache.resolve(wl, assignment, seed=seed, quant_bits=())
    assert not art.cache_hit
    return art


@pytest.mark.parametrize("backend", ["jnp", "spike_gemm_fused"])
def test_one_lowering_per_signature(tmp_path, lowered, backend):
    wl = _mlp(backend)
    _resolve(tmp_path, wl, seed=1)
    first = list(lowered)
    assert first.count(STEP) == 1 and first.count(DUMP) == 1, first
    _resolve(tmp_path, wl, seed=2)
    assert lowered[len(first):] == []
    assert train_snn._jitted_train_step.cache_info().currsize == 1
    assert train_snn._jitted_dump.cache_info().currsize == 1


@pytest.mark.parametrize("change", [
    lambda wl: (wl, {**ASSIGN, "num_steps": 4}),
    lambda wl: (dataclasses.replace(wl, lr=1e-3), ASSIGN),
    lambda wl: (dataclasses.replace(wl, matmul_backend="spike_gemm"),
                ASSIGN)], ids=["T", "lr", "backend"])
def test_other_signature_lowers_anew(tmp_path, lowered, change):
    wl = _mlp()
    _resolve(tmp_path, wl, seed=1)
    n = len(lowered)
    other, assignment = change(wl)
    _resolve(tmp_path, dataclasses.replace(other, name="other"), seed=1,
             assignment=assignment)
    assert lowered[n:].count(STEP) == 1, lowered[n:]
    assert train_snn._jitted_train_step.cache_info().currsize == 2


@pytest.mark.parametrize("backend", ["jnp", "spike_gemm_fused"])
def test_cell_after_another_is_bit_identical(tmp_path, backend):
    wl = _mlp(backend)
    _clear()
    _resolve(tmp_path / "warm", wl, seed=1)
    after = _resolve(tmp_path / "warm", wl, seed=2)
    _clear()
    alone = _resolve(tmp_path / "cold", wl, seed=2)
    for a, b in zip(jax.tree.leaves(after.params),
                    jax.tree.leaves(alone.params), strict=True):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(after.counts, alone.counts, strict=True):
        np.testing.assert_array_equal(a, b)
    assert after.accuracy == alone.accuracy


MLP = snn.SNNConfig(name="dump-mlp", input_shape=(12, 12),
                    layers=(snn.Dense(16), snn.Dense(10)), num_classes=10,
                    num_steps=3)
CONV = snn.SNNConfig(name="dump-conv", input_shape=(8, 8, 2),
                     layers=(snn.Conv(3, 3), snn.MaxPool(2), snn.Dense(10)),
                     num_classes=10, num_steps=3)


@pytest.mark.parametrize("cfg, x_shape", [
    (MLP, (8, 12, 12)),
    (CONV, (8, 3, 8, 8, 2))], ids=["mlp", "conv"])     # conv: events
def test_jitted_dump_equals_eager_counts(cfg, x_shape):
    """The dump's jit boundary moved; its counts did not."""
    params = snn.init_params(jax.random.key(0), cfg)
    x = np.asarray(jax.random.bernoulli(jax.random.key(1), 0.3, x_shape),
                   np.float32)
    dumped = train_snn.dump_traces(cfg, params, x, seed=7, max_samples=8)
    spikes_in = train_snn._encode_input(jax.random.key(7), x, cfg.num_steps)
    eager = snn.spike_counts_per_layer(cfg, params, spikes_in)
    for a, b in zip(dumped["layer_input_spike_counts"], eager, strict=True):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_cache_frees_the_programs_of_dropped_signatures():
    """Past ``SIGNATURES_KEPT`` signatures the least recently used one's
    train step is dropped and its executable freed: a long-lived service
    keeps a bounded number of programs."""
    _clear()
    client = jax.devices()[0].client
    x = jnp.zeros(2)

    def make(cfg, tx, backend):
        def train_step(x):
            return x + 1
        return train_step

    gc.collect()
    before = len(client.live_executables())
    kept = train_snn.SIGNATURES_KEPT
    for lr in range(kept + 3):
        train_snn._jitted_train_step(make, MLP, float(lr), "jnp")(x)
    gc.collect()
    assert len(client.live_executables()) - before == kept
    assert train_snn._jitted_train_step.cache_info().currsize == kept
    _clear()


def test_dump_cache_is_bounded():
    _clear()
    dumps = [train_snn._jitted_dump(dataclasses.replace(MLP, num_steps=t),
                                    "jnp")
             for t in range(1, train_snn.SIGNATURES_KEPT + 2)]
    assert train_snn._jitted_dump.cache_info().currsize == \
        train_snn.SIGNATURES_KEPT
    assert train_snn._jitted_dump(dataclasses.replace(MLP, num_steps=2),
                                  "jnp") is dumps[1]
    assert train_snn._jitted_dump(dataclasses.replace(MLP, num_steps=1),
                                  "jnp") is not dumps[0]
    _clear()

"""The rematerialised BPTT scan, the trace counts summed inside the scan
and the event data held as bytes.

With the remat forced (the budget set to 0), training gives one scan's
losses, gradients, parameters and counts bit for bit, on every matmul
backend and under ``vmap``; the budget picks no remat for net-3's and
``dvs-conv``'s shapes and chunks of 11 for net-5's."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import snn, train_snn, workloads
from repro.data import synthetic

T = 13                     # not a multiple of its chunk, 4: a partial chunk
CONV = snn.SNNConfig(
    name="remat-conv", input_shape=(8, 8, 2),
    layers=(snn.Conv(4, 3), snn.MaxPool(2), snn.Conv(4, 3), snn.Dense(8),
            snn.Dense(6)),
    num_classes=3, pcr=2, num_steps=T)


@pytest.fixture(scope="module")
def events():
    return synthetic.make_events(name="remat", seed=3, num_classes=3,
                                 n_train=24, n_test=8, t=T, h=8, w=8)


def _train(cfg, data, backend, steps=3, batch=8):
    """Loss and gradient at the seeded init, the losses of ``steps`` Adam
    steps, the parameters after them and the trace counts."""
    tx = optim.adam(2e-3)
    params, opt_state, key = train_snn.init_cell(cfg, tx, 2 ** 33 + 5)
    x, y = jnp.asarray(data.x_train), jnp.asarray(data.y_train)
    first = jax.jit(jax.grad(lambda p: train_snn.loss_fn(
        cfg, p, key, x[:batch], y[:batch], matmul_backend=backend)))(params)
    step = jax.jit(train_snn.make_train_step(cfg, tx, backend))
    losses = []
    for i in range(steps):
        key, sub = jax.random.split(key)
        rows = slice(i * batch, (i + 1) * batch)
        params, opt_state, loss = step(params, opt_state, sub, x[rows],
                                       y[rows])
        losses.append(loss)
    counts = train_snn.dump_traces(cfg, params, data.x_test,
                                   matmul_backend=backend)
    return {"first_grad": first, "losses": losses, "params": params,
            "counts": counts["layer_input_spike_counts"]}


def _assert_bitwise_equal(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _remat_taken(cfg, data, backend) -> bool:
    """Whether the training gradient holds the rematerialised backward,
    which recomputes under the scope ``bptt_recompute``."""
    x = jnp.asarray(data.x_train[:8])
    grad = jax.jit(jax.grad(lambda p: train_snn.loss_fn(
        cfg, p, jax.random.key(0), x, jnp.zeros(8, jnp.int32),
        matmul_backend=backend)))
    text = grad.lower(snn.init_params(jax.random.key(0), cfg)).as_text(
        debug_info=True)
    return "bptt_recompute" in text


@pytest.mark.parametrize("backend", snn.MATMUL_BACKENDS)
def test_remat_equals_one_scan_bit_for_bit(backend, events, monkeypatch):
    """Chunks of 4 over T 13, the last one partial: the first gradient,
    the losses and parameters of 3 steps and the counts are one scan's."""
    whole = _train(CONV, events, backend)
    assert not _remat_taken(CONV, events, backend)
    monkeypatch.setattr(snn, "REMAT_BUDGET_BYTES", 0)
    assert snn.remat_chunk(CONV, 8, T) == 4
    assert _remat_taken(CONV, events, backend)
    chunked = _train(CONV, events, backend)
    for key in whole:
        _assert_bitwise_equal(chunked[key], whole[key])


def test_remat_under_vmap_equals_one_scan(events, monkeypatch):
    """The stacked trainer's ``vmap`` of the step: loss and gradient of two
    cells at once, rematerialised and not, agree bit for bit."""
    cfg = CONV
    params = jax.vmap(lambda k: snn.init_params(k, cfg))(
        jax.random.split(jax.random.key(4), 2))
    x = jnp.asarray(events.x_train[:16]).reshape(
        (2, 8) + events.x_train.shape[1:])
    y = jnp.asarray(events.y_train[:16].reshape(2, 8))

    def stacked():
        return jax.jit(jax.vmap(jax.value_and_grad(
            lambda p, x, y: train_snn.loss_fn(cfg, p, jax.random.key(1), x,
                                              y))))(params, x, y)

    whole = stacked()
    monkeypatch.setattr(snn, "REMAT_BUDGET_BYTES", 0)
    _assert_bitwise_equal(stacked(), whole)


NET3 = workloads.Workload(
    name="net-3", dataset="fmnist", input_shape=(28, 28),
    layers=(snn.Dense(1024), snn.Dense(1024)), num_classes=10, pcr=30)
NET5 = workloads.Workload(
    name="net-5", dataset="dvs", input_shape=(128, 128, 2),
    layers=(snn.Conv(32, 3), snn.MaxPool(2), snn.Conv(32, 3), snn.MaxPool(2),
            snn.Dense(512)),
    num_classes=11, pcr=23, encoding="event")


@pytest.mark.parametrize("wl,num_steps,population,chunk", [
    (NET3, 51, 1.0, 51),
    (workloads.get("dvs-conv"), 16, 1.0, 16),
    (workloads.get("dvs-conv"), 16, 2.0, 16),
    (NET5, 124, 1.0, 11)], ids=["net-3", "dvs-conv", "dvs-conv-p2", "net-5"])
def test_remat_is_chosen_by_carry_bytes(wl, num_steps, population, chunk):
    """One scan where T x batch x carry bytes fit the budget (net-3 about
    61 MB, dvs-conv about 0.1 GB at batch 64); chunks of about sqrt(T)
    past it (net-5, 41.7 GB)."""
    cfg = wl.build(num_steps, population)
    assert snn.remat_chunk(cfg, 64, num_steps) == chunk


@pytest.mark.parametrize("cfg,x_shape", [
    (snn.SNNConfig(name="mlp", input_shape=(6, 6),
                   layers=(snn.Dense(12), snn.Dense(8)), num_classes=4,
                   num_steps=5), (5, 3, 6, 6)),
    (dataclasses.replace(CONV, num_steps=5), (5, 3, 8, 8, 2)),
    (snn.SNNConfig(name="pool-first", input_shape=(8, 8, 2),
                   layers=(snn.MaxPool(2), snn.Conv(3, 3), snn.MaxPool(2),
                           snn.MaxPool(2), snn.Dense(4)),
                   num_classes=4, num_steps=5), (5, 3, 8, 8, 2))],
    ids=["mlp", "conv", "pools"])
def test_counts_in_the_scan_equal_counts_of_whole_trains(cfg, x_shape):
    """The counts summed step by step inside the scan equal the whole
    output trains of ``apply(return_all_layers=True)``, OR-pooled by the
    pools that follow each layer and summed afterwards; so do the trains
    ``layer_input_trains`` gives."""
    params = snn.init_params(jax.random.key(0), cfg)
    x = jax.random.bernoulli(jax.random.key(1), 0.4, x_shape).astype(
        jnp.uint8)
    outputs = iter(snn.apply(cfg, params, x, return_all_layers=True))
    want = [x.astype(jnp.float32)]
    for spec in cfg.layers:
        if not isinstance(spec, snn.MaxPool):
            want.append(next(outputs))
        elif len(want) > 1:
            want[-1] = jax.vmap(
                lambda s, w=spec.window: snn._or_pool(s, w))(want[-1])
    want = want[:-1]
    counts = snn.spike_counts_per_layer(cfg, params, x)
    trains = snn.layer_input_trains(cfg, params, x)
    assert len(counts) == len(trains) == len(want) == len(
        cfg.spiking_layers())
    for c, train, w in zip(counts, trains, want, strict=True):
        np.testing.assert_array_equal(train, w)
        assert c.dtype == jnp.float32
        np.testing.assert_array_equal(
            c, w.reshape(w.shape[:2] + (-1,)).sum(-1))


def _loop_events(seed, num_classes, n_train, n_test, t, h, w):
    """The event generator as a per-sample, per-step loop in float32: the
    reference of the vectorised ``synthetic.make_events``."""
    rng = np.random.default_rng(seed)
    angles = np.linspace(0, 2 * np.pi, num_classes, endpoint=False)
    speeds = 1.0 + 0.5 * (np.arange(num_classes) % 2)

    def make(n):
        y = rng.integers(0, num_classes, size=n)
        x = np.zeros((n, t, h, w, 2), np.float32)
        for i in range(n):
            ang, spd = angles[y[i]], speeds[y[i]]
            cy, cx = rng.uniform(h * 0.3, h * 0.7), rng.uniform(w * 0.3,
                                                                w * 0.7)
            dy, dx = spd * np.sin(ang), spd * np.cos(ang)
            prev = None
            for ts in range(t):
                py, px = int(cy + dy * ts) % h, int(cx + dx * ts) % w
                mask = np.zeros((h, w), bool)
                mask[max(py - 2, 0):min(py + 3, h),
                     max(px - 2, 0):min(px + 3, w)] = True
                if prev is not None:
                    x[i, ts, :, :, 0][mask & ~prev] = 1.0
                    x[i, ts, :, :, 1][prev & ~mask] = 1.0
                prev = mask
            x[i] = np.maximum(x[i], rng.random((t, h, w, 2)) < 0.01)
        return x, y.astype(np.int32)

    return (*make(n_train), *make(n_test))


@pytest.mark.parametrize("seed,num_classes,t,h,w", [
    (0, 8, 16, 32, 32), (11, 3, 9, 12, 10), (5, 11, 7, 16, 16)])
def test_events_are_the_loops_values_as_bytes(seed, num_classes, t, h, w):
    data = synthetic.make_events(seed=seed, num_classes=num_classes,
                                 n_train=6, n_test=4, t=t, h=h, w=w)
    want = _loop_events(seed, num_classes, 6, 4, t, h, w)
    got = (data.x_train, data.y_train, data.x_test, data.y_test)
    assert data.x_train.dtype == data.x_test.dtype == np.uint8
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_event_data_is_made_once_and_read_only():
    wl = dataclasses.replace(workloads.get("dvs-conv"), name="dvs-once",
                             n_train=4, n_test=2)
    data = wl.make_data(5)
    assert wl.make_data(5) is data
    assert not data.x_train.flags.writeable
    assert not data.x_test.flags.writeable
    with pytest.raises(ValueError):
        data.x_train[0, 0, 0, 0, 0] = 1
    assert wl.make_data(6).x_train.shape[1] == 6

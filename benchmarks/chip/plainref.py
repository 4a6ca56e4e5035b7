"""Plain reference for one model cell: data, init, BPTT training, accuracy
and spike traces of a leaky integrate-and-fire network, in straightforward
``jax.numpy``.

It imports nothing of the program under test.  It follows the semantics of
the published description that the program implements (arXiv:2310.16745,
Sec. V-C: LIF with reset by subtraction, fast-sigmoid surrogate gradient,
rate-coded inputs, population-coded outputs and rate cross-entropy) and the
cell recipe the configuration file states: the synthetic data generators,
the seeded init and batch order, Adam, and the seeded key chains of
training, evaluation and trace dump.  So one cell seed gives the same
inputs, init and batches here as in the program, and only the arithmetic
of training differs.

``dtype="float32"`` holds everything in float32, with matmuls at the
precision the configuration states (``matmul_precision``; JAX's
``default``, which on a TPU is one bfloat16 pass with float32 sums and on a
CPU full float32): the reference.  ``dtype="bfloat16"`` holds parameters,
optimizer state, membranes and spikes in bfloat16: the control, the next
precision below the configuration's float32.  ``precision="highest"``
runs the matmuls at full float32 on any platform.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from work import topology

EVAL_BATCH = 256
EVAL_SEED = 1234
TRACE_SEED = 7
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
LIF_BETA, LIF_THRESHOLD, LIF_SLOPE = 0.95, 1.0, 25.0


# ---------------------------------------------------------------------------
# data: the synthetic stand-ins, generated from the configuration's seed
# ---------------------------------------------------------------------------

def _prototypes(rng, num_classes, h, w, blobs=4):
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    protos = np.zeros((num_classes, h, w), np.float32)
    for c in range(num_classes):
        for _ in range(blobs):
            cy, cx = rng.uniform(4, h - 4), rng.uniform(4, w - 4)
            sig = rng.uniform(1.5, 4.0)
            amp = rng.uniform(0.5, 1.0)
            protos[c] += amp * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2)
                                      / (2 * sig ** 2))
        protos[c] /= protos[c].max() + 1e-9
    return protos


def make_images(seed, num_classes, n_train, n_test, h, w, noise):
    """Intensity images in [0, 1]: per-class blob prototypes, pixel noise
    and a per-sample gain.  Returns (x_train, y_train, x_test, y_test)."""
    rng = np.random.default_rng(seed)
    protos = _prototypes(rng, num_classes, h, w)

    def draw(n):
        y = rng.integers(0, num_classes, size=n)
        x = protos[y] + noise * rng.standard_normal((n, h, w)).astype(
            np.float32)
        x *= rng.uniform(0.7, 1.0, size=(n, 1, 1)).astype(np.float32)
        return np.clip(x, 0.0, 1.0), y.astype(np.int32)

    return (*draw(n_train), *draw(n_test))


def make_events(seed, num_classes, n_train, n_test, t, h, w):
    """Two-polarity event streams (N, T, H, W, 2) in {0, 1}: a 5x5 blob
    moving along a class-specific direction, plus 1% sensor noise."""
    rng = np.random.default_rng(seed)
    angles = np.linspace(0, 2 * np.pi, num_classes, endpoint=False)
    speeds = 1.0 + 0.5 * (np.arange(num_classes) % 2)

    def draw(n):
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
            noise = rng.random((t, h, w, 2)) < 0.01
            x[i] = np.maximum(x[i], noise.astype(np.float32))
        return x, y.astype(np.int32)

    return (*draw(n_train), *draw(n_test))


def make_data(wl: dict, num_steps: int):
    if wl["dataset"] == "dvs":
        h, w, _ = wl["input_shape"]
        return make_events(wl["data_seed"], wl["num_classes"], wl["n_train"],
                           wl["n_test"], num_steps, h, w)
    h, w = wl["input_shape"]
    return make_images(wl["data_seed"], wl["num_classes"], wl["n_train"],
                       wl["n_test"], h, w, wl["noise"])


def batch_indices(n: int, batch: int, seed: int, steps: int) -> np.ndarray:
    """(steps, batch) sample indices: one seeded permutation per epoch,
    whole batches only."""
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < steps:
        perm = rng.permutation(n)
        for i in range(0, n - batch + 1, batch):
            out.append(perm[i:i + batch])
    return np.stack(out[:steps])


# ---------------------------------------------------------------------------
# the network
# ---------------------------------------------------------------------------

def _out_shape(layer, shape):
    if layer[0] == "dense":
        return (layer[1],)
    h, w, c = shape
    if layer[0] == "conv":
        _, f, k, stride, padding = layer
        if padding == "SAME":
            return (-(-h // stride), -(-w // stride), f)
        return ((h - k) // stride + 1, (w - k) // stride + 1, f)
    return (h // layer[1], w // layer[1], c)


def init_params(seed: int, layers, input_shape):
    """Seeded init: N(0, 1/fan_in) weights, zero biases, one key split per
    weighted layer off the cell key's first split."""
    key = jax.random.key(seed)
    key, pkey = jax.random.split(key)
    params, shape = [], tuple(input_shape)
    for layer in layers:
        if layer[0] == "dense":
            fan_in = math.prod(shape)
            pkey, sub = jax.random.split(pkey)
            params.append({
                "w": jax.random.normal(sub, (fan_in, layer[1]), jnp.float32)
                / math.sqrt(fan_in),
                "b": jnp.zeros((layer[1],), jnp.float32)})
        elif layer[0] == "conv":
            k, cin = layer[2], shape[-1]
            pkey, sub = jax.random.split(pkey)
            params.append({
                "w": jax.random.normal(sub, (k, k, cin, layer[1]),
                                       jnp.float32) / math.sqrt(k * k * cin),
                "b": jnp.zeros((layer[1],), jnp.float32)})
        else:
            params.append({})
        shape = _out_shape(layer, shape)
    return params, key


@jax.custom_vjp
def _spike(v):
    return (v > 0).astype(v.dtype)


def _spike_fwd(v):
    return _spike(v), v


def _spike_bwd(v, g):
    return (g * (1.0 / jnp.square(1.0 + LIF_SLOPE * jnp.abs(v))),)


_spike.defvjp(_spike_fwd, _spike_bwd)


def _conv(s, w, stride, padding):
    """NHWC x HWIO convolution as one product: the im2col patch matrix,
    patches ordered (kh, kw, cin) as HWIO flattens, times the weights."""
    k, _, _, cout = w.shape
    _, h, wd, _ = s.shape
    if padding == "SAME":
        oh, ow = -(-h // stride), -(-wd // stride)
        ph = max((oh - 1) * stride + k - h, 0)
        pw = max((ow - 1) * stride + k - wd, 0)
        s = jnp.pad(s, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)))
    else:
        oh, ow = (h - k) // stride + 1, (wd - k) // stride + 1
    patches = jnp.concatenate(
        [s[:, i:i + (oh - 1) * stride + 1:stride,
           j:j + (ow - 1) * stride + 1:stride, :]
         for i in range(k) for j in range(k)], axis=-1)
    return patches @ w.reshape(-1, cout)


def _pool(s, window):
    return jax.lax.reduce_window(s, -jnp.inf, jax.lax.max,
                                 (1, window, window, 1),
                                 (1, window, window, 1), "VALID")


def run(layers, params, spikes_in, dtype, all_layers=False):
    """(T, B, ...) input spikes -> output spikes (T, B, n_out), or every
    spiking layer's output train with ``all_layers``."""
    batch = spikes_in.shape[1]
    shape, states = tuple(spikes_in.shape[2:]), []
    for layer in layers:
        shape = _out_shape(layer, shape)
        states.append(jnp.zeros((batch,) + shape, dtype)
                      if layer[0] != "pool" else None)

    def step(states, s):
        new, outs = [], []
        for layer, p, st in zip(layers, params, states):
            if layer[0] == "pool":
                s = _pool(s, layer[1])
                new.append(None)
                continue
            if layer[0] == "dense":
                cur = s.reshape(batch, -1) @ p["w"] + p["b"]
            else:
                cur = _conv(s, p["w"], layer[3], layer[4]) + p["b"]
            u = LIF_BETA * st[0] + cur - LIF_THRESHOLD * st[1]
            s = _spike(u - LIF_THRESHOLD)
            new.append((u, s))
            outs.append(s)
        return new, (outs if all_layers else outs[-1])

    states = [None if st is None else (st, st) for st in states]
    _, out = jax.lax.scan(step, states, spikes_in)
    return out


def encode(key, x, num_steps, dtype):
    """Rate code for images (Bernoulli per step, drawn in float32), or the
    time-major transpose of pre-encoded events."""
    if x.ndim == 5:
        return x.transpose(1, 0, 2, 3, 4).astype(dtype)
    probs = jnp.broadcast_to(x, (num_steps,) + x.shape)
    return jax.random.bernoulli(key, probs).astype(dtype)


def _pooled_counts(out, num_classes):
    counts = out.sum(0)
    return counts.reshape(counts.shape[0], num_classes, -1).sum(-1)


def loss(layers, params, spikes_in, y, num_classes):
    logits = _pooled_counts(run(layers, params, spikes_in, spikes_in.dtype),
                            num_classes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


# ---------------------------------------------------------------------------
# one cell, end to end
# ---------------------------------------------------------------------------
# ``spec`` = (layers, num_steps, num_classes, dtype name, lr): hashable, so
# one process compiles each program once for all the cells it checks.

def _grad(spec, params, key, xb, yb):
    layers, num_steps, classes, dtype, _ = spec
    return jax.value_and_grad(
        lambda p: loss(layers, p, encode(key, xb, num_steps, jnp.dtype(dtype)),
                       yb, classes))(params)


@functools.partial(jax.jit, static_argnums=0)
def _train(spec, params, key, x_tr, y_tr, idx):
    dt, lr = jnp.dtype(spec[3]), spec[4]
    zeros = jax.tree.map(jnp.zeros_like, params)
    first = _grad(spec, params, jax.random.split(key)[1], x_tr[idx[0]],
                  y_tr[idx[0]])[1]

    def one(carry, i):
        params, mu, nu, key, count = carry
        key, sub = jax.random.split(key)
        value, g = _grad(spec, params, sub, x_tr[i], y_tr[i])
        count = count + 1
        mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, g)
        nu = jax.tree.map(lambda v, g: ADAM_B2 * v
                          + (1 - ADAM_B2) * jnp.square(g), nu, g)
        c1 = (1 - ADAM_B1 ** count.astype(jnp.float32)).astype(dt)
        c2 = (1 - ADAM_B2 ** count.astype(jnp.float32)).astype(dt)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2)
                                                  + ADAM_EPS)),
            params, mu, nu)
        return (params, mu, nu, key, count), value

    carry = (params, zeros, zeros, key, jnp.zeros([], jnp.int32))
    (params, *_), losses = jax.lax.scan(one, carry, idx)
    return params, first, losses


@functools.partial(jax.jit, static_argnums=0)
def _predict(spec, params, key, xb):
    layers, num_steps, classes, dtype, _ = spec
    dt = jnp.dtype(dtype)
    out = run(layers, params, encode(key, xb, num_steps, dt), dt)
    return jnp.argmax(_pooled_counts(out, classes), axis=-1)


@functools.partial(jax.jit, static_argnums=0)
def _counts(spec, params, xb):
    """Spike counts entering each spiking layer, (T, B) each: the input
    train, then every spiking layer's output but the last, pooled where a
    pool follows it."""
    layers, num_steps, _, dtype, _ = spec
    dt = jnp.dtype(dtype)
    s_in = encode(jax.random.key(TRACE_SEED), xb, num_steps, dt)
    outs = iter(run(layers, params, s_in, dt, all_layers=True))
    trains = [s_in]
    for i, layer in enumerate(layers):
        if layer[0] == "pool":
            continue
        train = next(outs)
        for nxt in layers[i + 1:]:
            if nxt[0] != "pool":
                break
            train = jax.vmap(lambda s, w=nxt[1]: _pool(s, w))(train)
        trains.append(train)
    return [t.astype(jnp.float32).reshape(t.shape[0], t.shape[1], -1).sum(-1)
            for t in trains[:-1]]


def train_cell(wl: dict, num_steps: int, population: float, seed: int,
               dtype: str = "float32", keep_rows: int = None,
               precision: str = "default") -> dict:
    """Train one cell from its seed and return what the program produces
    for it: params (numpy), the loss of each training step, test accuracy
    and the per-layer input spike counts, plus the init and the first
    step's gradient of each leaf.

    ``keep_rows`` plants a fault for calibration: each step trains on the
    first ``keep_rows`` rows of its batch only, the mean taken over them."""
    dt = jnp.dtype(dtype)
    layers = tuple(topology(wl, population))
    spec = (layers, int(num_steps), wl["num_classes"], dt.name, wl["lr"])
    x_tr, y_tr, x_te, y_te = make_data(wl, num_steps)
    idx = batch_indices(len(x_tr), wl["batch_size"], seed,
                        wl["train_steps"])[:, :keep_rows]
    params0, key = init_params(seed, layers, wl["input_shape"])
    params0 = jax.tree.map(lambda a: a.astype(dt), params0)
    with jax.default_matmul_precision(precision):
        params, first, losses = _train(spec, params0, key,
                                       jnp.asarray(x_tr), jnp.asarray(y_tr),
                                       jnp.asarray(idx))
        correct, ekey = 0, jax.random.key(EVAL_SEED)
        for i in range(0, len(x_te), EVAL_BATCH):
            ekey, sub = jax.random.split(ekey)
            pred = _predict(spec, params, sub,
                            jnp.asarray(x_te[i:i + EVAL_BATCH]))
            correct += int((np.asarray(pred) == y_te[i:i + EVAL_BATCH]).sum())
        traced = _counts(spec, params, jnp.asarray(x_te[:wl["trace_samples"]]))
    as_np = lambda t: jax.tree.map(lambda a: np.asarray(a, np.float32), t)
    return {"params": as_np(params), "params0": as_np(params0),
            "first_grad": as_np(first),
            "losses": np.asarray(losses, np.float64),
            "accuracy": correct / max(len(x_te), 1),
            "counts": [np.asarray(c, np.float32) for c in traced]}

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
runs the matmuls at full float32 on any platform.  A conv layer is summed
by the JAX primitive ``lax.conv_general_dilated`` at the same precision.

It fits a cell of the paper's net-5 (128x128x2 events, T 124, batch 64) on
one 16 GB chip: events are held as bytes on the host and made once per
process, each step moves one batch to the device, the BPTT scan is
rematerialised in chunks of about sqrt(T) steps, and the trace dump sums
each layer's spikes inside the scan.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

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


@functools.lru_cache(maxsize=1)
def make_events(seed, num_classes, n_train, n_test, t, h, w):
    """Two-polarity event streams (N, T, H, W, 2) as uint8 in {0, 1}: a 5x5
    blob moving along a class-specific direction, plus 1% sensor noise.
    The last result is kept, read-only, so that the cells one process
    checks share one generation."""
    rng = np.random.default_rng(seed)
    angles = np.linspace(0, 2 * np.pi, num_classes, endpoint=False)
    speeds = 1.0 + 0.5 * (np.arange(num_classes) % 2)
    steps, rows, cols = np.arange(t), np.arange(h), np.arange(w)

    def draw(n):
        y = rng.integers(0, num_classes, size=n)
        x = np.zeros((n, t, h, w, 2), np.uint8)
        for i in range(n):
            ang, spd = angles[y[i]], speeds[y[i]]
            cy, cx = rng.uniform(h * 0.3, h * 0.7), rng.uniform(w * 0.3,
                                                                 w * 0.7)
            dy, dx = spd * np.sin(ang), spd * np.cos(ang)
            # the blob's square at every step, clipped at the edges;
            # astype truncates toward zero as int() does
            py = (cy + dy * steps).astype(np.int64) % h
            px = (cx + dx * steps).astype(np.int64) % w
            mask = ((np.abs(rows - py[:, None]) <= 2)[:, :, None]
                    & (np.abs(cols - px[:, None]) <= 2)[:, None, :])
            x[i, 1:, :, :, 0] = mask[1:] & ~mask[:-1]
            x[i, 1:, :, :, 1] = mask[:-1] & ~mask[1:]
            x[i] |= rng.random((t, h, w, 2)) < 0.01
        y = y.astype(np.int32)
        x.flags.writeable = y.flags.writeable = False
        return x, y

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
    """NHWC x HWIO convolution."""
    return jax.lax.conv_general_dilated(
        s, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _pool(s, window):
    return jax.lax.reduce_window(s, -jnp.inf, jax.lax.max,
                                 (1, window, window, 1),
                                 (1, window, window, 1), "VALID")


class Spec(NamedTuple):
    """What a cell's programs depend on: hashable, so one process compiles
    each program once for all the cells it checks."""
    layers: tuple
    input_shape: tuple
    num_steps: int
    num_classes: int
    dtype: str
    lr: float
    chunk: int          # time steps in each rematerialised chunk


def remat_steps(num_steps: int) -> int:
    """Time steps in each rematerialised chunk of the BPTT scan: about
    sqrt(T), so the backward pass holds about 2 sqrt(T) carries instead of
    T steps' residuals."""
    return max(1, round(math.sqrt(num_steps)))


def _scan(step, chunk, params, carry, xs, pack, unpack):
    """The stacked outputs of ``step(params, carry, x) -> (carry, y)`` over
    ``xs``'s leading axis.  Where ``chunk`` is shorter than T, the backward
    pass is rematerialised: the forward keeps the carry that enters each
    chunk of ``chunk`` steps, and the backward recomputes one chunk's
    carries at a time.  Kept carries are held as ``pack(carry)``;
    ``unpack`` rebuilds the carry bit for bit.

    It is written out, not left to ``jax.checkpoint`` of each chunk's scan,
    because autodiff of a scan of scans sums the parameters' gradient chunk
    by chunk and then over chunks, which rounds otherwise than one scan.
    Here it is one running sum from step T back to step 1, in one scan's
    order.  Where ``chunk`` does not divide T, the time axis is padded at
    its end with steps on zero input, whose outputs are dropped: their
    cotangents are zero, so they add exact zeros to that sum, and every
    chunk runs in the same loop."""
    if chunk >= xs.shape[0]:
        return jax.lax.scan(functools.partial(step, params), carry, xs)[1]
    return _chunked(step, chunk, pack, unpack, params, carry, xs)


def _chunks(a, chunk):
    """A time-major array padded with zeros at its end to whole chunks, as
    (chunks, chunk, ...)."""
    pad = jnp.zeros((-a.shape[0] % chunk,) + a.shape[1:], a.dtype)
    a = jnp.concatenate([a, pad])
    return a.reshape((-1, chunk) + a.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _chunked(step, chunk, pack, unpack, params, carry, xs):
    return jax.lax.scan(functools.partial(step, params), carry, xs)[1]


def _chunked_fwd(step, chunk, pack, unpack, params, carry, xs):
    def part(c, x):
        end, ys = jax.lax.scan(functools.partial(step, params), c, x)
        return end, (pack(c), ys)

    _, (starts, ys) = jax.lax.scan(part, carry, _chunks(xs, chunk))
    t = xs.shape[0]
    ys = jax.tree.map(lambda y: y.reshape((-1,) + y.shape[2:])[:t], ys)
    return ys, (params, xs, starts)


def _chunked_bwd(step, chunk, pack, unpack, res, ct_ys):
    params, xs, starts = res

    def back_chunk(cts, part):
        start, x, ct_y = part
        _, entering = jax.lax.scan(
            lambda c, x: (step(params, c, x)[0], pack(c)), unpack(start), x)

        def back_step(cts, inputs):
            acc, ct_carry = cts
            c, x, ct_y = inputs
            _, vjp = jax.vjp(lambda p, c: step(p, c, x), params, unpack(c))
            g_params, ct_carry = vjp((ct_carry, ct_y))
            return (jax.tree.map(jnp.add, acc, g_params), ct_carry), None

        return jax.lax.scan(back_step, cts, (entering, x, ct_y),
                            reverse=True)[0], None

    carry = unpack(jax.tree.map(lambda s: s[0], starts))
    cts = (jax.tree.map(jnp.zeros_like, params),
           jax.tree.map(jnp.zeros_like, carry))
    cts, _ = jax.lax.scan(
        back_chunk, cts,
        (starts, _chunks(xs, chunk),
         jax.tree.map(lambda a: _chunks(a, chunk), ct_ys)), reverse=True)
    return cts[0], cts[1], None


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def run(spec, params, spikes_in, emit):
    """Run the network over a (T, B, features) input train in {0, 1}, each
    step cast to the spec's dtype and shaped as the spec's input.
    ``emit(ins, out)`` gives what each step stacks: ``ins``, the step's
    input and then the spikes entering each later spiking layer (pooled
    where a pool precedes it), and ``out``, the last layer's output
    spikes."""
    dt = jnp.dtype(spec.dtype)
    batch = spikes_in.shape[1]
    shape, shapes = spec.input_shape, []
    for layer in spec.layers:
        shape = _out_shape(layer, shape)
        shapes.append(None if layer[0] == "pool" else (batch,) + shape)

    def step(params, states, s_in):
        s = first = s_in.astype(dt).reshape((batch,) + spec.input_shape)
        entering, new = [], []
        for layer, p, st in zip(spec.layers, params, states):
            if layer[0] == "pool":
                s = _pool(s, layer[1])
                new.append(None)
                continue
            entering.append(s)
            if layer[0] == "dense":
                cur = s.reshape(batch, -1) @ p["w"] + p["b"]
            else:
                cur = _conv(s, p["w"], layer[3], layer[4]) + p["b"]
            u = LIF_BETA * st[0] + cur - LIF_THRESHOLD * st[1]
            s = _spike(u - LIF_THRESHOLD)
            new.append((u, s))
        return new, emit([first] + entering[1:], s)

    # a kept carry is each layer's membrane, flat: its spikes are the
    # membrane over threshold, as ``step`` made them
    def pack(states):
        return [None if st is None else st[0].reshape(-1) for st in states]

    def unpack(flat):
        return [None if u is None else
                (u.reshape(sh), _spike(u - LIF_THRESHOLD).reshape(sh))
                for u, sh in zip(flat, shapes)]

    zeros = [None if sh is None else jnp.zeros((math.prod(sh),), dt)
             for sh in shapes]
    return _scan(step, spec.chunk, params, unpack(zeros), spikes_in, pack,
                 unpack)


def encode(key, x, num_steps):
    """Time-major input train (T, B, features) in {0, 1}: the rate code of
    images (Bernoulli per step, drawn in float32), or the transpose of
    pre-encoded events, in the type they came in."""
    if x.ndim == 5:
        return x.reshape(x.shape[:2] + (-1,)).transpose(1, 0, 2)
    probs = jnp.broadcast_to(x, (num_steps,) + x.shape)
    spikes = jax.random.bernoulli(key, probs)
    return spikes.reshape(spikes.shape[:2] + (-1,))


def _pooled_counts(out, num_classes):
    counts = out.sum(0)
    return counts.reshape(counts.shape[0], num_classes, -1).sum(-1)


def _output(ins, out):
    return out


def _input_counts(ins, out):
    return [s.astype(jnp.float32).reshape(s.shape[0], -1).sum(-1)
            for s in ins]


def loss(spec, params, spikes_in, y):
    logits = _pooled_counts(run(spec, params, spikes_in, _output),
                            spec.num_classes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


# ---------------------------------------------------------------------------
# one cell, end to end
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=0)
def _step(spec, state, xb, yb):
    """One training step on one batch: value_and_grad of the loss, then
    Adam.  ``state`` is (params, mu, nu, key, count); returns the next
    state, the loss and the gradient."""
    params, mu, nu, key, count = state
    dt, lr = jnp.dtype(spec.dtype), spec.lr
    key, sub = jax.random.split(key)
    value, g = jax.value_and_grad(
        lambda p: loss(spec, p, encode(sub, xb, spec.num_steps), yb))(params)
    count = count + 1
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, g)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v
                      + (1 - ADAM_B2) * jnp.square(g), nu, g)
    c1 = (1 - ADAM_B1 ** count.astype(jnp.float32)).astype(dt)
    c2 = (1 - ADAM_B2 ** count.astype(jnp.float32)).astype(dt)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS)),
        params, mu, nu)
    return (params, mu, nu, key, count), value, g


@functools.partial(jax.jit, static_argnums=0)
def _predict(spec, params, key, xb):
    out = run(spec, params, encode(key, xb, spec.num_steps), _output)
    return jnp.argmax(_pooled_counts(out, spec.num_classes), axis=-1)


@functools.partial(jax.jit, static_argnums=0)
def _counts(spec, params, xb):
    """Spike counts entering each spiking layer, (T, B) each: the input
    train, then every spiking layer's output but the last, pooled where a
    pool follows it; summed inside the scan, so no layer's whole train is
    held."""
    return run(spec, params,
               encode(jax.random.key(TRACE_SEED), xb, spec.num_steps),
               _input_counts)


def train_cell(wl: dict, num_steps: int, population: float, seed: int,
               dtype: str = "float32", keep_rows: int = None,
               precision: str = "default") -> dict:
    """Train one cell from its seed and return what the program produces
    for it: params (numpy), the loss of each training step, test accuracy
    and the per-layer input spike counts, plus the init and the first
    step's gradient of each leaf.

    Each step moves its batch to the device, in the type the data is held
    in; the whole data set never is.  ``keep_rows`` plants a fault for
    calibration: each step trains on the first ``keep_rows`` rows of its
    batch only, the mean taken over them."""
    dt = jnp.dtype(dtype)
    spec = Spec(tuple(topology(wl, population)), tuple(wl["input_shape"]),
                int(num_steps), wl["num_classes"], dt.name, wl["lr"],
                remat_steps(int(num_steps)))
    x_tr, y_tr, x_te, y_te = make_data(wl, num_steps)
    idx = batch_indices(len(x_tr), wl["batch_size"], seed,
                        wl["train_steps"])[:, :keep_rows]
    params0, key = init_params(seed, spec.layers, spec.input_shape)
    params0 = jax.tree.map(lambda a: a.astype(dt), params0)
    zeros = jax.tree.map(jnp.zeros_like, params0)
    state = (params0, zeros, zeros, key, jnp.zeros([], jnp.int32))
    losses = []
    with jax.default_matmul_precision(precision):
        for rows in idx:
            state, value, grad = _step(spec, state, jnp.asarray(x_tr[rows]),
                                       jnp.asarray(y_tr[rows]))
            if not losses:
                first = grad
            losses.append(value)
        params = state[0]
        correct, ekey = 0, jax.random.key(EVAL_SEED)
        for i in range(0, len(x_te), EVAL_BATCH):
            ekey, sub = jax.random.split(ekey)
            pred = _predict(spec, params, sub,
                            jnp.asarray(x_te[i:i + EVAL_BATCH]))
            correct += int((np.asarray(pred) == y_te[i:i + EVAL_BATCH]).sum())
        traced = _counts(spec, params,
                         jnp.asarray(x_te[:wl["trace_samples"]]))
    as_np = lambda t: jax.tree.map(lambda a: np.asarray(a, np.float32), t)
    return {"params": as_np(params), "params0": as_np(params0),
            "first_grad": as_np(first),
            "losses": np.asarray(jnp.stack(losses), np.float64),
            "accuracy": correct / max(len(x_te), 1),
            "counts": [np.asarray(c, np.float32) for c in traced]}

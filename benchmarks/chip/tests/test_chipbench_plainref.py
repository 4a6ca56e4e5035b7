"""The plain reference on the CPU: its rematerialised BPTT against one scan,
its byte events against the program's, its trace counts, summed inside the
scan, against counts summed from whole trains, and its conv against a
direct sum."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, os.path.join(os.path.dirname(os.path.dirname(CHIP)),
                                   "src")]

import catalog  # noqa: E402
import harness  # noqa: E402
import plainref  # noqa: E402
from repro.core import snn  # noqa: E402
from repro.data import synthetic  # noqa: E402
from work import topology  # noqa: E402

CELLS = os.path.join(HERE, "cells")
CAT = catalog.Catalog(root=CELLS,
                      benchmark=os.path.join(CELLS, "BENCHMARK.json"))


@pytest.mark.parametrize("name,num_steps,dtype", [
    ("tiny-mlp", 5, "float32"), ("tiny-conv", 3, "float32"),
    ("tiny-conv", 3, "bfloat16"), ("tiny-conv", 5, "float32")])
def test_chunked_scan_equals_one_scan_bit_for_bit(name, num_steps, dtype,
                                                  monkeypatch):
    """Chunks of remat_steps(T), the last one partial, give one scan's
    losses, parameters, first gradient and counts, bit for bit."""
    chunk = plainref.remat_steps(num_steps)
    assert 1 < chunk < num_steps and num_steps % chunk
    config = CAT.config(name)
    args = (config["workload"], num_steps, config["population"], 2 ** 33 + 7)
    chunked = plainref.train_cell(*args, dtype=dtype)
    monkeypatch.setattr(plainref, "remat_steps", lambda t: t)
    whole = plainref.train_cell(*args, dtype=dtype)
    for key in ("losses", "params", "first_grad", "counts"):
        got, want = jax.tree.leaves(chunked[key]), jax.tree.leaves(whole[key])
        assert len(got) == len(want)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b, err_msg=key)
    assert chunked["accuracy"] == whole["accuracy"]


@pytest.mark.parametrize("num_steps,chunk", [(3, 2), (16, 4), (51, 7),
                                             (124, 11)])
def test_remat_steps_is_about_the_root_of_t(num_steps, chunk):
    assert plainref.remat_steps(num_steps) == chunk


@pytest.mark.parametrize("t,h,w", [(5, 8, 8), (9, 12, 10)])
def test_events_are_the_programs_as_bytes(t, h, w):
    ours = plainref.make_events(11, 3, 6, 4, t, h, w)
    theirs = synthetic.make_events(seed=11, num_classes=3, n_train=6,
                                   n_test=4, t=t, h=h, w=w)
    assert ours[0].dtype == ours[2].dtype == np.uint8
    for a, b in zip(ours, (theirs.x_train, theirs.y_train, theirs.x_test,
                           theirs.y_test)):
        np.testing.assert_array_equal(a, b)
    # made once per process, and read-only since callers share them
    assert plainref.make_events(11, 3, 6, 4, t, h, w) is ours
    assert not ours[0].flags.writeable


@pytest.mark.parametrize("name", ["tiny-mlp", "tiny-conv"])
def test_counts_in_the_scan_equal_counts_of_whole_trains(name):
    """The trace dump's counts, summed step by step inside the scan, equal
    the program's whole input trains of each spiking layer summed here."""
    config = CAT.config(name)
    wl, t = config["workload"], config["num_steps"]
    spec = plainref.Spec(tuple(topology(wl, config["population"])),
                         tuple(wl["input_shape"]), t, wl["num_classes"],
                         "float32", wl["lr"], plainref.remat_steps(t))
    params, _ = plainref.init_params(5, spec.layers, spec.input_shape)
    x = plainref.make_data(wl, t)[2][:16]
    counts = plainref._counts(spec, params, jnp.asarray(x))

    cfg = harness.build_workload(config, {"backend": "jnp"}).build(
        spec.num_steps, config["population"])
    spikes = plainref.encode(jax.random.key(plainref.TRACE_SEED),
                             jnp.asarray(x), spec.num_steps)
    spikes = spikes.astype(jnp.float32).reshape(
        spikes.shape[:2] + spec.input_shape)
    trains = snn.layer_input_trains(cfg, params, spikes)
    assert len(counts) == len(trains) == len(cfg.spiking_layers())
    for c, train in zip(counts, trains):
        assert c.shape == train.shape[:2]
        np.testing.assert_array_equal(
            c, train.reshape(train.shape[:2] + (-1,)).sum(-1))


def _direct_conv(s, w, stride, padding):
    """The NHWC x HWIO convolution summed term by term in float64, SAME
    padding split as XLA splits it, the odd pixel after."""
    s, w = np.asarray(s, np.float64), np.asarray(w, np.float64)
    k, _, _, cout = w.shape
    n, h, wd, _ = s.shape
    if padding == "SAME":
        oh, ow = -(-h // stride), -(-wd // stride)
        ph = max((oh - 1) * stride + k - h, 0)
        pw = max((ow - 1) * stride + k - wd, 0)
        s = np.pad(s, ((0, 0), (ph // 2, ph - ph // 2),
                       (pw // 2, pw - pw // 2), (0, 0)))
    else:
        oh, ow = (h - k) // stride + 1, (wd - k) // stride + 1
    out = np.zeros((n, oh, ow, cout))
    for y in range(oh):
        for x in range(ow):
            window = s[:, y * stride:y * stride + k, x * stride:x * stride + k]
            out[:, y, x] = np.einsum("nijc,ijcf->nf", window, w)
    return out


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                            (1, "VALID"), (2, "VALID")])
def test_conv_is_the_direct_sum(stride, padding):
    s = jax.random.bernoulli(jax.random.key(0), 0.3, (2, 9, 10, 3)).astype(
        jnp.float32)
    w = jax.random.normal(jax.random.key(1), (3, 3, 3, 5))
    with jax.default_matmul_precision("highest"):
        got = plainref._conv(s, w, stride, padding)
    want = _direct_conv(s, w, stride, padding)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

"""Compiles for a described TPU v5e chip: the main path's kernels at the
registered sizes, in compiled (Mosaic) mode.

Nothing runs here — the chip's compiler only has to accept the programs.
That catches what interpret mode cannot: a kernel whose scalar-prefetched
tile flags outgrow SMEM, a block the tiling refuses.  The topology is
described inside a fixture (never at import), and the kernels are steered
into compiled mode by patching the backend JAX reports, so every other
test keeps interpreting them.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import optim
from repro.core import snn, train_snn, workloads
from repro.distributed import cellstack
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_kernels():
    """Kernels in compiled mode for this module only; traces cached in
    interpret mode must not leak in, nor compiled-mode ones out."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        jax.clear_caches()
        yield
    jax.clear_caches()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def test_mnist_mlp_fused_train_step(one_chip, compiled_kernels):
    """The registered mnist-mlp cell at T=25, population 2.0
    (784-256-256-40, batch 64): one whole BPTT train step on the fused
    GEMM+LIF backend."""
    wl = workloads.get("mnist-mlp")
    cfg = wl.build(25, 2.0)
    tx = optim.adam(wl.lr)
    params, opt_state, key = jax.eval_shape(
        lambda: train_snn.init_cell(cfg, tx, 0))
    x = jax.ShapeDtypeStruct((wl.batch_size,) + wl.input_shape, jnp.float32)
    y = jax.ShapeDtypeStruct((wl.batch_size,), jnp.int32)
    step = train_snn.make_train_step(cfg, tx, "spike_gemm_fused")
    _compiled_text(step, *_on(one_chip, (params, opt_state, key, x, y)))


def _dvs_conv_layers():
    """(input HWC, Conv spec) of each conv layer of dvs-conv at p=2.0."""
    cfg = workloads.get("dvs-conv").build(16, 2.0)
    shape, out = cfg.input_shape, []
    for spec, next_shape in zip(cfg.layers, snn.output_shapes(cfg)):
        if isinstance(spec, snn.Conv):
            out.append((shape, spec))
        shape = next_shape
    return out


@pytest.mark.parametrize("layer", [0, 1], ids=["conv1", "conv2"])
@pytest.mark.parametrize("cells,batch,train", [
    (None, 64, True), (cellstack.MAX_STACK, 64, True),
    (None, 256, False), (cellstack.MAX_STACK, 256, False)],
    ids=["train-b64", "stack16-train-b64", "eval-b256", "stack16-eval-b256"])
def test_dvs_conv_kernel_at_registered_shapes(one_chip, compiled_kernels,
                                              layer, cells, batch, train):
    """The block-skip conv kernel at the registered dvs-conv p=2.0 layer
    shapes (32x32x2 -> 16, then 16x16x16 -> 32): forward plus VJP at the
    training batch of 64, forward at the evaluation batch of 256, each alone
    and as a vmap stack of ``cellstack.MAX_STACK`` cells."""
    (h, w, c), spec = _dvs_conv_layers()[layer]

    def fwd(s, wts):
        return ops.spike_conv_train(s, wts, stride=spec.stride,
                                    padding=spec.padding, **snn.KERNEL_BLOCKS)

    fn = (jax.value_and_grad(lambda s, wts: fwd(s, wts).sum(), argnums=(0, 1))
          if train else fwd)
    lead = (batch,) if cells is None else (cells, batch)
    wlead = () if cells is None else (cells,)
    if cells is not None:
        fn = jax.vmap(fn)
    s = jax.ShapeDtypeStruct(lead + (h, w, c), jnp.float32)
    wts = jax.ShapeDtypeStruct(wlead + (spec.kernel, spec.kernel, c,
                                        spec.features), jnp.float32)
    _compiled_text(fn, *_on(one_chip, (s, wts)))

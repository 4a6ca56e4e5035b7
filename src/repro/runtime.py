"""Where the program runs: the Pallas kernel mode and JAX's compile cache.

The kernels run in one of two modes, chosen from the platform JAX runs on
and nowhere else: on the CPU (the tests, ``JAX_PLATFORMS=cpu``) Pallas
interprets the kernel bodies; on a TPU they compile to Mosaic.  There is no
fallback between the two — any other backend is refused.

``enable_compile_cache`` is for entry points (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/*.py``), never for library import or
tests: it points JAX's persistent compilation cache at
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself), and
otherwise at one fixed directory inside the checkout.  The directory is
part of the cache key, so it never depends on a temp name, a pid or the
time.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root (``src/repro/runtime.py`` -> ``.``)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def kernel_interpret() -> bool:
    """True when the Pallas kernels must run interpreted (CPU), False when
    they compile for the chip (TPU); any other backend raises."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"the Pallas kernels run interpreted on 'cpu' or "
                       f"compiled on 'tpu'; JAX's backend is {backend!r}")


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<checkout>/.cache/jax``."""
    return (os.environ.get(CACHE_ENV)
            or os.path.join(REPO_ROOT, ".cache", "jax"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path

"""Stacked-cell training tests (repro.distributed.cellstack).

The load-bearing property is the BIT-EXACTNESS CONTRACT: a cell trained
inside a ``jit(vmap(train_step))`` stack must publish the identical
artifact — params, spike traces, accuracy — a solo ``TraceCache.resolve``
would have trained, so stacking is invisible to every cache consumer.
Parity runs over both matmul backends and both datapaths (rate-encoded
MLP, event-driven conv).  Mesh-sharded stacks run in a subprocess with
forced host devices, same idiom as tests/test_distributed.py.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.core import dse, snn, workloads
from repro.core.accelerator import arch
from repro.core.lif import LIFParams
from repro.core.workloads.cache import cell_key
from repro.distributed import cellfarm, cellstack

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(name="stack-mlp", **kw):
    base = dict(name=name, layers=(snn.Dense(12),), pcr=1,
                input_shape=(12, 12), n_train=96, n_test=32,
                train_steps=4, batch_size=32, trace_samples=16)
    base.update(kw)
    return dataclasses.replace(workloads.get("mnist-mlp"), **base)


def _conv(name="stack-conv", **kw):
    base = dict(name=name, layers=(snn.Conv(2, 3), snn.MaxPool(2),
                                   snn.Dense(6)),
                input_shape=(8, 8, 2), num_classes=4, pcr=1,
                n_train=64, n_test=16, train_steps=3, batch_size=16,
                trace_samples=8)
    base.update(kw)
    return dataclasses.replace(workloads.get("dvs-conv"), **base)


def _job(wl, T=2, pop=1.0, seed=0):
    return cellfarm.CellJob(workload=wl,
                            assignment={"num_steps": T, "population": pop},
                            seed=seed)


class TestStackSignature:
    def test_seed_and_shard_degrees_of_freedom_share_a_signature(self):
        """Seeds, data_seed, noise, n_train and the workload NAME are
        host-side per-cell knobs — they must not split a stack (this is
        what lets mnist-mlp and fmnist-mlp cells train together)."""
        wl = _mlp()
        variant = dataclasses.replace(wl, name="stack-mlp-b", data_seed=17,
                                      noise=0.35, n_train=64)
        sigs = {cellstack.stack_signature(_job(wl, seed=0)),
                cellstack.stack_signature(_job(wl, seed=3)),
                cellstack.stack_signature(_job(variant, seed=0))}
        assert len(sigs) == 1

    def test_compiled_shape_changes_split_the_group(self):
        wl = _mlp()
        base = cellstack.stack_signature(_job(wl, T=2))
        assert cellstack.stack_signature(_job(wl, T=3)) != base
        assert cellstack.stack_signature(_job(wl, pop=0.5)) != base
        wider = dataclasses.replace(wl, layers=(snn.Dense(16),))
        assert cellstack.stack_signature(_job(wider)) != base

    def test_recipe_and_numerics_split_the_group(self):
        wl = _mlp()
        base = cellstack.stack_signature(_job(wl))
        for variant in (
                dataclasses.replace(wl, train_steps=5),
                dataclasses.replace(wl, lr=1e-3),
                dataclasses.replace(wl, batch_size=16),
                dataclasses.replace(wl, n_test=16),
                dataclasses.replace(wl, trace_samples=8),
                dataclasses.replace(wl, matmul_backend="spike_gemm"),
                dataclasses.replace(wl, layers=(
                    snn.Dense(12, lif=LIFParams(beta=0.8)),))):
            assert cellstack.stack_signature(_job(variant)) != base

    def test_group_jobs_orders_and_partitions(self):
        wl = _mlp()
        jobs = [_job(wl, T=2, seed=0), _job(wl, T=3, seed=0),
                _job(wl, T=2, seed=1), _job(wl, T=3, seed=1)]
        groups = cellstack.group_jobs(jobs)
        assert sorted(sum(groups.values(), [])) == [0, 1, 2, 3]
        assert sorted(map(sorted, groups.values())) == [[0, 2], [1, 3]]


class TestStackedSoloParity:
    @pytest.mark.parametrize("backend", ["jnp", "spike_gemm"])
    @pytest.mark.parametrize("make_wl", [_mlp, _conv],
                             ids=["mlp", "dvs-conv"])
    def test_stacked_equals_solo_bit_for_bit(self, tmp_path, make_wl,
                                             backend):
        """The contract itself: stack-train a 2-cell group, then train the
        same recipes solo into a fresh cache — params, per-layer trace
        counts and accuracy must be IDENTICAL (assert_array_equal, not
        allclose), and the stacked cache must serve the solo recipe as a
        hit."""
        wl = dataclasses.replace(make_wl(), matmul_backend=backend)
        T = 3 if make_wl is _conv else 2
        jobs = [_job(wl, T=T, seed=s) for s in (0, 1)]

        stack_cache = workloads.TraceCache(root=str(tmp_path / "stack"))
        stats = {}
        outcomes = cellstack.resolve_stacked(jobs, stack_cache.root,
                                             cache=stack_cache, stats=stats)
        assert [o.trained for o in outcomes] == [True, True]
        assert stats["cells"] == 2 and stats["compile_seconds"] > 0

        solo_cache = workloads.TraceCache(root=str(tmp_path / "solo"))
        for job in jobs:
            solo = solo_cache.resolve(job.workload, job.assignment,
                                      seed=job.seed)
            assert not solo.cache_hit                 # actually trained solo
            stacked = stack_cache.resolve(job.workload, job.assignment,
                                          seed=job.seed)
            assert stacked.cache_hit                  # published == solo key
            for a, b in zip(jax.tree.leaves(solo.params),
                            jax.tree.leaves(stacked.params)):
                np.testing.assert_array_equal(a, b)
            assert len(solo.counts) == len(stacked.counts)
            for a, b in zip(solo.counts, stacked.counts):
                np.testing.assert_array_equal(a, b)
            assert solo.accuracy == stacked.accuracy


class TestResolveStacked:
    def test_cached_cells_resolve_without_training(self, tmp_path):
        wl = _mlp()
        cache = workloads.TraceCache(root=str(tmp_path))
        pre = _job(wl, seed=0)
        cache.resolve(pre.workload, pre.assignment, seed=pre.seed)
        stats = {}
        outcomes = cellstack.resolve_stacked(
            [pre, _job(wl, seed=1)], cache.root, cache=cache, stats=stats)
        assert [o.trained for o in outcomes] == [False, True]
        assert stats["cells"] == 1                    # only the miss trained
        assert outcomes[0].key == cell_key(wl, pre.assignment, 0)

    def test_max_stack_slabs_one_large_group(self, tmp_path):
        """A group bigger than max_stack trains in slabs; artifacts stay
        bit-identical to the unslabbed stack (slab membership must never
        leak into a cell)."""
        wl = _mlp()
        jobs = [_job(wl, seed=s) for s in range(3)]
        a = workloads.TraceCache(root=str(tmp_path / "a"))
        b = workloads.TraceCache(root=str(tmp_path / "b"))
        out_a = cellstack.resolve_stacked(jobs, a.root, cache=a, max_stack=2)
        out_b = cellstack.resolve_stacked(jobs, b.root, cache=b)
        assert all(o.trained for o in out_a + out_b)
        for job in jobs:
            slabbed = a.resolve(job.workload, job.assignment, seed=job.seed)
            whole = b.resolve(job.workload, job.assignment, seed=job.seed)
            for x, y in zip(jax.tree.leaves(slabbed.params),
                            jax.tree.leaves(whole.params)):
                np.testing.assert_array_equal(x, y)
            assert slabbed.accuracy == whole.accuracy

    def test_mixed_signatures_resolve_in_job_order(self, tmp_path):
        wl = _mlp()
        jobs = [_job(wl, T=3, seed=0), _job(wl, T=2, seed=0),
                _job(wl, T=2, seed=1)]
        cache = workloads.TraceCache(root=str(tmp_path))
        outcomes = cellstack.resolve_stacked(jobs, cache.root, cache=cache)
        assert all(o.trained for o in outcomes)
        assert [o.key for o in outcomes] == [
            cell_key(j.workload, j.assignment, j.seed) for j in jobs]


class TestResolveCellsStack:
    def test_stack_true_without_workers_never_spawns(self, tmp_path,
                                                     monkeypatch):
        """workers=0 + stack=True: everything (including the mixed-signature
        singleton) trains in-process as C>=1 stacks — the pool must not
        even be constructed."""
        def boom(_):
            raise AssertionError("pool constructed in stack-only mode")
        monkeypatch.setattr(cellfarm, "_get_pool", boom)
        wl = _mlp()
        jobs = [_job(wl, T=2, seed=0), _job(wl, T=2, seed=1),
                _job(wl, T=3, seed=0)]
        outcomes = cellfarm.resolve_cells(jobs, str(tmp_path), workers=0,
                                          stack=True)
        assert all(o.trained for o in outcomes)
        assert [o.key for o in outcomes] == [
            cell_key(j.workload, j.assignment, j.seed) for j in jobs]
        cache = workloads.TraceCache(root=str(tmp_path))
        for job in jobs:
            assert cache.contains(job.workload, job.assignment,
                                  seed=job.seed)

    def test_stack_true_with_pool_farms_only_singletons(self, tmp_path,
                                                        monkeypatch):
        """With a usable pool only >=2-cell groups stack; the lone leftover
        job short-circuits to a serial in-process resolve (1 job never
        justifies a spawn), so no pool is built here either."""
        def boom(_):
            raise AssertionError("1 leftover job must not build a pool")
        monkeypatch.setattr(cellfarm, "_get_pool", boom)
        monkeypatch.setattr(cellfarm.multiprocessing, "cpu_count",
                            lambda: 4)            # a real pool is available
        wl = _mlp()
        jobs = [_job(wl, T=2, seed=0), _job(wl, T=3, seed=0),
                _job(wl, T=2, seed=1)]
        outcomes = cellfarm.resolve_cells(jobs, str(tmp_path), workers=2,
                                          stack=True)
        assert all(o.trained for o in outcomes)
        assert [o.key for o in outcomes] == [
            cell_key(j.workload, j.assignment, j.seed) for j in jobs]

    def test_chip_backend_resolves_in_process_without_a_pool(self, tmp_path,
                                                             monkeypatch):
        """Off the CPU this process holds the chip, which a spawned child
        could not get: workers>=2 builds no pool, cells resolve here."""
        def boom(_):
            raise AssertionError("pool constructed while holding the chip")
        monkeypatch.setattr(cellfarm, "_get_pool", boom)
        monkeypatch.setattr(cellfarm.multiprocessing, "cpu_count", lambda: 4)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert cellfarm._worker_count(10, 8) == 1
        wl = _mlp()
        jobs = [_job(wl, T=2, seed=0), _job(wl, T=3, seed=0)]
        outcomes = cellfarm.resolve_cells(jobs, str(tmp_path), workers=2)
        assert all(o.trained and o.error is None for o in outcomes)
        assert [o.key for o in outcomes] == [
            cell_key(j.workload, j.assignment, j.seed) for j in jobs]

    def test_failed_stack_raises_on_the_chip_degrades_on_cpu(self, tmp_path,
                                                             monkeypatch):
        def refused(*args, **kwargs):
            raise RuntimeError("stack refused")
        monkeypatch.setattr(cellstack, "resolve_stacked", refused)
        jobs = [_job(_mlp(), T=2, seed=0)]
        out = cellfarm.resolve_cells(jobs, str(tmp_path / "cpu"), workers=0,
                                     stack=True)
        assert out[0].trained and out[0].error is None   # resolved serially
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="stack refused"):
            cellfarm.resolve_cells(jobs, str(tmp_path / "tpu"), workers=0,
                                   stack=True)

    def test_worker_count_caps(self, monkeypatch):
        monkeypatch.setattr(cellfarm.multiprocessing, "cpu_count",
                            lambda: 16)
        monkeypatch.setattr(cellfarm, "MAX_POOL_WORKERS", 2)
        assert cellfarm._worker_count(10, None) == 2      # module cap
        assert cellfarm._worker_count(10, 1) == 1         # explicit request
        assert cellfarm._worker_count(1, 8) == 1          # never > jobs
        monkeypatch.setattr(cellfarm, "MAX_POOL_WORKERS", 64)
        monkeypatch.setattr(cellfarm.multiprocessing, "cpu_count",
                            lambda: 3)
        assert cellfarm._worker_count(10, None) == 3      # cpu cap

    def test_pool_reuse_and_idempotent_shutdown(self):
        cellfarm.shutdown_pool()
        p = cellfarm._get_pool(2)
        assert cellfarm._get_pool(2) is p                 # reused, not rebuilt
        cellfarm.shutdown_pool()
        assert cellfarm._pool is None
        cellfarm.shutdown_pool()                          # idempotent


class TestStudyStack:
    def test_coexplore_stack_matches_serial(self, tmp_path):
        """The front-end acceptance path: a datasets axis of two same-shape
        workload variants under stack=True yields the exact serial frontier
        (bit-exact training makes strict equality the right assertion) and
        charges the stacked cells as farmed misses — the parent cache only
        ever sees hits."""
        wl_a = _mlp(name="stack-co-a")
        wl_b = _mlp(name="stack-co-b", data_seed=17, noise=0.35)
        kw = dict(datasets=(wl_a, wl_b), num_steps=(2,), max_lhr=2)
        serial_cache = workloads.TraceCache(root=str(tmp_path / "a"))
        serial = dse.coexplore(cache=serial_cache, **kw)

        stack_cache = workloads.TraceCache(root=str(tmp_path / "b"))
        stacked = dse.coexplore(cache=stack_cache, stack=True, **kw)
        assert stacked.study.farmed_misses == 2
        assert stack_cache.misses == 0 and stack_cache.hits == 2

        def rows(t):
            cols = [np.asarray(t.columns[k], np.float64).reshape(len(t), -1)
                    for k in sorted(t.columns) if k != "dataset"]
            a = np.concatenate(cols, axis=1)
            return a[np.lexsort(a.T)]

        np.testing.assert_array_equal(rows(stacked.frontier),
                                      rows(serial.frontier))

    def test_hardware_only_explore_rejects_stack(self):
        cfg = arch.from_layer_sizes("hw", (16, 8), num_steps=2)
        space = dse.SearchSpace.product_lhr(cfg, max_lhr=2)
        counts = [np.full(2, 2.0)]
        with pytest.raises(ValueError, match="hardware-only"):
            dse.explore(space, counts=counts, stack=True)


class TestMeshStack:
    def test_single_device_mesh_is_none_and_specs_lead_with_cells(self):
        assert cellstack.stack_mesh(4) is None            # 1 CPU device here
        specs = cellstack.cell_specs({"w": np.zeros((2, 3)),
                                      "b": np.zeros(3)})
        assert all(s == cellstack.P("cells")
                   for s in jax.tree.leaves(
                       specs, is_leaf=lambda x: hasattr(x, "index")))

    def test_mesh_sharded_stack_matches_solo(self):
        """4 forced host devices, 4 cells: the stack shards over the
        ``"cells"`` mesh (asserted inside) and still publishes bit-exact
        artifacts — mesh partitioning of the vmapped program must not
        perturb a single cell."""
        code = """
        import dataclasses
        import numpy as np
        import jax
        from repro.core import snn, workloads
        from repro.distributed import cellfarm, cellstack

        wl = dataclasses.replace(
            workloads.get("mnist-mlp"), name="mesh-stack",
            layers=(snn.Dense(8),), pcr=1, input_shape=(12, 12),
            n_train=64, n_test=16, train_steps=2, batch_size=16,
            trace_samples=8)
        asn = {"num_steps": 2, "population": 1.0}
        jobs = [cellfarm.CellJob(workload=wl, assignment=asn, seed=s)
                for s in range(4)]
        assert len(jax.devices()) == 4
        assert cellstack.stack_mesh(4) is not None
        assert cellstack.stack_mesh(3) is None      # 3 cells don't divide

        import tempfile
        with tempfile.TemporaryDirectory() as root:
            cache = workloads.TraceCache(root=root + "/stack")
            out = cellstack.resolve_stacked(jobs, cache.root, cache=cache)
            assert all(o.trained for o in out)
            solo = workloads.TraceCache(root=root + "/solo")
            for job in jobs:
                a = solo.resolve(wl, asn, seed=job.seed)
                b = cache.resolve(wl, asn, seed=job.seed)
                assert b.cache_hit
                for x, y in zip(jax.tree.leaves(a.params),
                                jax.tree.leaves(b.params)):
                    np.testing.assert_array_equal(x, y)
                for x, y in zip(a.counts, b.counts):
                    np.testing.assert_array_equal(x, y)
                assert a.accuracy == b.accuracy
        print("MESH-STACK-OK")
        """
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.path.join(REPO, "src"))
        res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, env=env,
                             timeout=560)
        assert res.returncode == 0, \
            f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
        assert "MESH-STACK-OK" in res.stdout

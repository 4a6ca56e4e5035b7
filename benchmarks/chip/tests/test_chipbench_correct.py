"""The check that decides ``correct``, driven through a whole run of a tiny
cell on the CPU (the harness's look for a chip skipped): a sound run is
correct, and each fault the cells can have, planted under the timed path,
makes it false, as does the control (the bfloat16 reference in the
program's place).  The fault of a missing exchange between chips has no
place here: no cell spans chips."""
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, os.path.join(os.path.dirname(os.path.dirname(CHIP)),
                                   "src")]

import catalog  # noqa: E402
import harness  # noqa: E402
import plainref  # noqa: E402
import verdict  # noqa: E402
from repro.core import train_snn  # noqa: E402
from repro.core.workloads import cache as cache_mod  # noqa: E402

CELLS = os.path.join(HERE, "cells")
CAT = catalog.Catalog(root=CELLS,
                      benchmark=os.path.join(CELLS, "BENCHMARK.json"))
SEED = 2 ** 33 + 12345          # larger than 32 signed bits hold


def run(cell):
    return harness.run(cell, SEED, 0.1, False, time.perf_counter(), cat=CAT,
                       require_tpu=False)


@pytest.mark.parametrize("cell", ["tiny-solo", "tiny-conv-solo"])
def test_sound_run_is_correct(cell):
    result = run(cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"cells_per_min", "setup_s"}


def _unchanged_state(monkeypatch):
    make = train_snn.make_train_step

    def broken(cfg, tx, backend=None):
        step = make(cfg, tx, backend)

        def same(params, opt_state, key, x, y):
            _, _, loss = step(params, opt_state, key, x, y)
            return params, opt_state, loss
        return same
    monkeypatch.setattr(train_snn, "make_train_step", broken)


def _half_batch(monkeypatch):
    make = train_snn.make_train_step

    def broken(cfg, tx, backend=None):
        step = make(cfg, tx, backend)

        def half(params, opt_state, key, x, y):
            n = x.shape[0] // 2
            return step(params, opt_state, key, x[:n], y[:n])
        return half
    monkeypatch.setattr(train_snn, "make_train_step", broken)


def _altered_answer(monkeypatch):
    write = cache_mod.TraceCache._write_cell

    def broken(self, cell_dir, workload, params, counts, meta):
        counts = [np.array(c) for c in counts]
        counts[0][0, 0] += 1.0
        return write(self, cell_dir, workload, params, counts, meta)
    monkeypatch.setattr(cache_mod.TraceCache, "_write_cell", broken)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_answer],
                         ids=["unchanged_state", "half_batch",
                              "altered_answer"])
@pytest.mark.parametrize("cell", ["tiny-solo", "tiny-conv-solo"])
def test_fault_makes_run_incorrect(cell, fault, monkeypatch):
    fault(monkeypatch)
    result = run(cell)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("name", ["tiny-mlp", "tiny-conv"])
def test_control_fails_the_check(name):
    """The reference in bfloat16, put in the program's place, is not
    correct against the float32 reference."""
    config = CAT.config(name)
    failed = 0
    for seed in (1, 2, 3):
        args = (config["workload"], config["num_steps"],
                config["population"], seed)
        control = plainref.train_cell(*args, dtype="bfloat16")
        numbers = verdict.readings(control, plainref.train_cell(*args))
        failed += not verdict.judge(numbers, config["limits"])[0]
    assert failed == 3


def test_half_batch_shows_in_the_first_losses():
    """Half of each batch left out, in the reference put in the program's
    place, parts the first steps' losses from the whole batch's."""
    config = CAT.config("tiny-mlp")
    args = (config["workload"], config["num_steps"], config["population"], 4)
    half = plainref.train_cell(*args, keep_rows=8)
    numbers = verdict.readings(half, plainref.train_cell(*args))
    assert numbers["loss_gap"] > config["limits"]["loss_gap"]


def test_losses_are_kept_by_cell_seed_and_the_trainer_restored(tmp_path):
    original = train_snn.train
    cell = CAT.cell("tiny-solo")
    wl = harness.build_workload(cell["config"], cell["traffic"])
    with harness.Losses().kept() as losses:
        assert train_snn.train is not original
        cache = cache_mod.TraceCache(str(tmp_path))
        art = cache.resolve(wl, {"num_steps": 4}, seed=11, quant_bits=())
    assert train_snn.train is original
    assert not art.cache_hit
    assert list(losses.by_seed) == [11]
    steps = cell["config"]["workload"]["train_steps"]
    assert len(losses.by_seed[11]) == steps
    assert all(np.isfinite(losses.by_seed[11]))


def test_loss_gaps_need_every_step_compared():
    gaps = verdict.loss_gaps({"losses": [2.0, 1.0, 0.5]},
                             {"losses": [2.0, 2.0, 0.25]}, 3)
    assert gaps == [0.0, 0.5, 1.0]
    with pytest.raises(ValueError, match="fewer than 3"):
        verdict.loss_gaps({"losses": [2.0]}, {"losses": [2.0] * 3}, 3)

"""A tiny cell of net-5's layer kinds (event input, two convs with OR-pools,
a Dense layer, population readout) through a whole run of the harness on
the CPU, with the program's BPTT rematerialised as net-5's is on the chip:
a sound run is correct under limits 0, and the half-batch fault and the
bfloat16 control are not.  Its files are under ``net5/``."""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, os.path.join(os.path.dirname(os.path.dirname(CHIP)),
                                   "src")]

import catalog  # noqa: E402
import harness  # noqa: E402
import plainref  # noqa: E402
import verdict  # noqa: E402
from repro.core import snn, train_snn  # noqa: E402

CELLS = os.path.join(HERE, "net5")
CAT = catalog.Catalog(root=CELLS,
                      benchmark=os.path.join(CELLS, "BENCHMARK.json"))
CELL = "tiny-net5-solo"
SEED = 2 ** 33 + 4321           # larger than 32 signed bits hold


@pytest.fixture
def remat(monkeypatch):
    """The program's BPTT rematerialised at this size (the budget set to
    0), each call of the remat counted; the trainer's kept programs are
    dropped before and after, so none is shared with other tests."""
    calls = []
    scan = snn._remat_scan

    def counted(*args):
        calls.append(args[1])
        return scan(*args)

    monkeypatch.setattr(snn, "REMAT_BUDGET_BYTES", 0)
    monkeypatch.setattr(snn, "_remat_scan", counted)
    train_snn._jitted_train_step.cache_clear()
    yield calls
    train_snn._jitted_train_step.cache_clear()


def run():
    return harness.run(CELL, SEED, 0.1, False, time.perf_counter(), cat=CAT,
                       require_tpu=False)


def test_sound_run_is_correct_with_the_remat(remat):
    config = CAT.cell(CELL)["config"]
    chunk = plainref.remat_steps(config["num_steps"])
    assert 1 < chunk < config["num_steps"] and config["num_steps"] % chunk
    result = run()
    assert remat and set(remat) == {chunk}, "the program took no remat"
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert {k: v["value"] for k, v in result["checks"].items()} == dict.fromkeys(
        config["limits"], 0.0)


def test_half_batch_makes_the_run_incorrect(remat, monkeypatch):
    make = train_snn.make_train_step

    def broken(cfg, tx, backend=None):
        step = make(cfg, tx, backend)

        def half(params, opt_state, key, x, y):
            n = x.shape[0] // 2
            return step(params, opt_state, key, x[:n], y[:n])
        return half
    monkeypatch.setattr(train_snn, "make_train_step", broken)
    result = run()
    assert remat
    assert not result["correct"], result["checks"]


def test_control_fails_the_check():
    """The reference in bfloat16, put in the program's place, is not
    correct against the float32 reference, on each of three seeds."""
    config = CAT.config("tiny-net5")
    for seed in (1, 2, 3):
        args = (config["workload"], config["num_steps"],
                config["population"], seed)
        control = plainref.train_cell(*args, dtype="bfloat16")
        numbers = verdict.readings(control, plainref.train_cell(*args))
        assert not verdict.judge(numbers, config["limits"])[0], numbers


@pytest.mark.parametrize("programs,want", [
    ({"jit_spike_counts": [0.5, 2], "jit_train_step": [9.0, 10]}, 250.0),
    ({"jit_train_step": [9.0, 10]}, None),
    ({"jit_spike_counts": [0.0, 0]}, None)], ids=["dump", "none", "zero"])
def test_trace_dump_reads_the_dump_program(programs, want):
    reader = catalog.Catalog().metric("trace_dump.device_ms")
    assert reader.read({"trace": {"programs": programs}}) == want

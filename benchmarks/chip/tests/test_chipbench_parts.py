"""The benchmark's parts on the CPU: loading by name, the peaks table, and
the dense work functions against hand counts."""
import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHIP, os.path.join(os.path.dirname(os.path.dirname(CHIP)),
                                   "src")]

import catalog  # noqa: E402
import work  # noqa: E402

BENCH = catalog.Catalog().benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(cell):
    spec = catalog.Catalog().cell(cell)
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert spec["traffic"]["route"] == "solo"
    assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s",
                                                       "cells_per_min"}
    assert spec["per_layer"], "every cell reports a per-layer metric"


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_file_is_the_one_named(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    assert os.path.relpath(
        os.path.join(CHIP, "configs", config + ".json"),
        catalog.CHECKOUT) == entry["file"]
    assert catalog.Catalog().config(config)["name"] == config


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_metric_reader_loads_and_reads_nothing_from_nothing(metric):
    reader = catalog.Catalog().metric(metric)
    record = {"cells": 0, "window_s": 1.0, "chips": 1, "compile_s": 0.0,
              "untraced_cells": 0, "untraced_s": 1.0,
              "train_steps": 150, "step_flops": 1, "peak": None,
              "trace": None}
    assert reader.read(record) is None


def test_unknown_names_are_refused():
    cat = catalog.Catalog()
    with pytest.raises(KeyError):
        cat.cell("no-such-cell")
    with pytest.raises(ValueError):
        cat.config("../BENCHMARK")
    with pytest.raises(FileNotFoundError):
        cat.traffic("no-such-traffic")


def test_peaks_are_keyed_by_device_kind():
    peak = catalog.Catalog().peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        catalog.Catalog().peaks("TPU v99")


def test_dense_layer_work_by_hand():
    net3 = catalog.Catalog().config("net-3")["workload"]
    gemms = work.layer_gemms(net3, 1.0, 64)
    assert gemms == [(64, 784, 1024), (64, 1024, 1024), (64, 1024, 300)]
    assert work.gemm_flops(*gemms[0]) == 102_760_448
    # 3 x T=51 x 2 x 64 x (784*1024 + 1024*1024 + 1024*300)
    assert work.train_step_flops(net3, 51, 1.0) == 42_273_865_728


def test_conv_layer_work_by_hand():
    # the registered dvs-conv at population 2.0
    dvs = {"input_shape": [32, 32, 2], "num_classes": 8, "pcr": 2,
           "layers": [["conv", 8, 3, 1, "SAME"], ["pool", 2],
                      ["conv", 16, 3, 1, "SAME"], ["pool", 2],
                      ["dense", 64]]}
    gemms = work.layer_gemms(dvs, 2.0, 64)
    # conv1: 64 x 32 x 32 output positions, im2col K = 3*3*2, 16 channels;
    # conv2 after a 2x2 pool: 64 x 16 x 16, K = 3*3*16, 32 channels;
    # dense 8*8*32 -> 128, classifier 128 -> 8 x 2
    assert gemms == [(65536, 18, 16), (16384, 144, 32), (64, 2048, 128),
                     (64, 128, 16)]
    assert work.gemm_flops(*gemms[0]) == 37_748_736


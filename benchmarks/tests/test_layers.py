"""The per-layer readers on hand-worked traces: what counts as an evaluation,
what a reader with nothing to read returns."""

import json
import os

import pytest

from benchmarks import trace_reduce
from benchmarks.layers import dense_vg_roofline, device_idle_pct, fit_mfu, sparse_fused_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"hbm_gb_per_s": 819.0, "bf16_tflop_per_s": 197.0}


def config(name):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        return json.load(f)


def run_on(planes, name, rows, units):
    return {"config": config(name), "rows": rows, "peaks": PEAKS,
            "trace": trace_reduce.reduce(planes, n_units=units)}


def test_dense_roofline_and_mfu_from_the_small_trace():
    with open(os.path.join(HERE, "small_trace.json")) as f:
        planes = json.load(f)
    run = run_on(planes, "lr-epsilon", rows=1, units=2)
    # One row of 2,000 bfloat16 features: 4,000 + 12 + 16,000 bytes an evaluation.
    least = 20_012 / 819e9
    # Three kernel calls, 4,000 ns of device time, in a traced window of 14,000 ns.
    assert dense_vg_roofline.read(run) == pytest.approx(100 * 3 * least / 4000e-9)
    assert fit_mfu.read(run) == pytest.approx(100 * 3 * least / 14000e-9)
    assert sparse_fused_roofline.read(run) is None  # not this configuration's kernel
    assert device_idle_pct.read(run) == pytest.approx(100 * 8200 / 14000)


def test_a_sparse_evaluation_is_counted_once_though_it_makes_three_calls():
    call = "%fused_value_gradient_sums.{} = {} custom-call(...)"
    ops = [
        [call.format(6, "f32[64,128]"), 100, 10],  # level-2 matvec
        [call.format(7, "(f32[1,2], f32[2,128], f32[64,128])"), 120, 60],  # the fused kernel
        [call.format(8, "f32[2,128]"), 190, 10],  # level-2 rmatvec
        [call.format(10, "(f32[1,2], f32[2,128], f32[64,128])"), 210, 60],  # a value-only trial
        ["%fusion.3 = f32[8] fusion(...)", 280, 20],
    ]
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [["fit:0", 0, 400]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
    ]
    run = run_on(planes, "glmix-movielens", rows=1000, units=1)
    least = (8 * 9000 + 12 * 1000 + 8 * 201) / 819e9
    assert sparse_fused_roofline.read(run) == pytest.approx(100 * 2 * least / 140e-9)
    assert fit_mfu.read(run) == pytest.approx(100 * 2 * least / 400e-9)
    assert dense_vg_roofline.read(run) is None


def test_a_reader_with_no_trace_returns_nothing():
    run = {"config": config("lr-epsilon"), "rows": 1, "peaks": PEAKS, "trace": None}
    for reader in (dense_vg_roofline, sparse_fused_roofline, fit_mfu, device_idle_pct):
        assert reader.read(run) is None

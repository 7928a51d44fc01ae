"""The byte and operation counts against shapes worked by hand."""

import json
import os

import pytest

from benchmarks import work

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"hbm_gb_per_s": 819.0, "bf16_tflop_per_s": 197.0}


def config(name):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_dense_counts():
    w = work.dense_value_gradient(400_000, 2_000, 2)
    assert w["bytes"] == 1_600_000_000 + 4_800_000 + 16_000
    assert w["flops"] == 3_200_000_000
    seconds, binds = work.least_seconds(w, PEAKS)
    assert binds == "hbm"
    assert seconds == pytest.approx(1_604_816_000 / 819e9)


def test_sparse_counts():
    w = work.sparse_value_gradient(1_000, 9_000, 201)
    assert w == {"bytes": 72_000 + 12_000 + 1_608, "flops": 36_000}
    assert work.least_seconds(w, PEAKS)[1] == "hbm"


def test_the_mxu_binds_where_operations_outweigh_bytes():
    assert work.least_seconds({"bytes": 1e3, "flops": 1e12}, PEAKS) == (pytest.approx(1e12 / 197e12), "mxu")


def test_counts_follow_the_configuration_not_the_layout():
    eps = config("lr-epsilon")
    assert work.fixed_effect_evaluation(eps, eps["rows"]) == work.dense_value_gradient(400_000, 2_000, 2)
    ml = config("glmix-movielens")
    assert work.fixed_effect_evaluation(ml, 1_000) == work.sparse_value_gradient(1_000, 9_000, 201)

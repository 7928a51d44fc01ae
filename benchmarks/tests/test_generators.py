"""The seed changes the numbers and never the shapes."""

import json
import os

import numpy as np

from benchmarks.generators import dense_unit_rows, movielens_shape

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_movielens_pattern_is_the_configurations_and_values_the_seeds():
    cfg = config("glmix-movielens")
    a = movielens_shape.generate(cfg, 1, rows=30_000)
    b = movielens_shape.generate(cfg, 3_000_000_019, rows=30_000)
    for part in ("train", "validation"):
        sa, sb = a[part]["shards"]["g"], b[part]["shards"]["g"]
        assert np.array_equal(sa["indices"], sb["indices"])
        for tag in ("userId", "movieId"):
            assert np.array_equal(a[part]["id_tags"][tag], b[part]["id_tags"][tag])
        assert not np.array_equal(sa["values"], sb["values"])
        assert not np.array_equal(a[part]["labels"], b[part]["labels"])
    idx = a["train"]["shards"]["g"]["indices"]
    assert idx.shape == (30_000, 9) and (np.diff(idx, axis=1) > 0).all() and (idx[:, -1] == 200).all()
    users, movies = a["train"]["id_tags"]["userId"], a["train"]["id_tags"]["movieId"]
    assert len(np.unique(users)) == 30_000 // 145 and len(np.unique(movies)) == 50
    same = movielens_shape.generate(cfg, 1, rows=30_000)
    assert np.array_equal(same["train"]["shards"]["g"]["values"], a["train"]["shards"]["g"]["values"])


def test_dense_rows_have_unit_norm_and_follow_the_seed():
    cfg = config("lr-epsilon")
    a = dense_unit_rows.generate(cfg, 5, rows=4_000)
    b = dense_unit_rows.generate(cfg, 2**31 + 5, rows=4_000)
    xa = np.asarray(a["train"]["shards"]["g"])
    assert xa.shape == (4_000, 2_000)
    assert np.allclose(np.linalg.norm(xa, axis=1), 1.0, atol=1e-5)
    assert not np.array_equal(xa, np.asarray(b["train"]["shards"]["g"]))
    again = dense_unit_rows.generate(cfg, 5, rows=4_000)
    assert np.array_equal(xa, np.asarray(again["train"]["shards"]["g"]))
    assert 0.3 < float(np.mean(np.asarray(a["train"]["labels"]))) < 0.7

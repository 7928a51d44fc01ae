"""MovieLens-shaped GLMix rows: the pattern from the configuration, the numbers from the seed.

The sparsity pattern (which features each row holds, which user and movie it
belongs to; every id occurs at least once) is drawn from the configuration's own
`structure_seed`. The feature values, the true fixed and per-entity
coefficients and the labels are drawn from `--seed`. So every seed gives the
same static shapes (entity counts, bucket shapes, spill) and one set of
compiled programs serves all runs of a check, while every answer still changes
with the seed. The pattern is to this model what a vocabulary is to a language
model. Host numpy; the rows reach the device through the program's data set.
"""

import numpy as np


def _ids_covering(rng, n: int, count: int) -> np.ndarray:
    """n ids in [0, count), each id at least once, in random order."""
    ids = np.concatenate([np.arange(count), rng.integers(0, count, size=n - count)])
    rng.shuffle(ids)
    return ids.astype(np.int64)


def pattern(config: dict, rows: int, validation_rows: int) -> dict:
    gen = config["generator"]
    rng = np.random.default_rng(gen["structure_seed"])
    d, k = gen["named_features"], gen["nnz_per_row"]
    n_users = max(gen["min_users"], rows // gen["rows_per_user"])
    n_movies = max(gen["min_movies"], rows // gen["rows_per_movie"])
    n_all = rows + validation_rows
    # k distinct named features a row: a sorted draw with repeats from
    # [0, d - k] plus 0..k-1 is strictly increasing. The intercept rides last.
    named = np.sort(rng.integers(0, d - k + 1, size=(n_all, k)), axis=1) + np.arange(k)
    indices = np.concatenate([named, np.full((n_all, 1), d)], axis=1).astype(np.int32)
    users = np.concatenate(
        [_ids_covering(rng, rows, n_users), rng.integers(0, n_users, size=validation_rows)]
    )
    movies = np.concatenate(
        [_ids_covering(rng, rows, n_movies), rng.integers(0, n_movies, size=validation_rows)]
    )
    return {"indices": indices, "users": users, "movies": movies,
            "n_users": n_users, "n_movies": n_movies}


def generate(config: dict, seed: int, rows=None) -> dict:
    gen = config["generator"]
    d, k = gen["named_features"], gen["nnz_per_row"]
    n_train = rows or config["rows"]
    n_val = max(n_train // 10, 1) if rows else config["validation_rows"]
    pat = pattern(config, n_train, n_val)
    rng = np.random.default_rng(seed)
    n_all = n_train + n_val
    values = np.concatenate(
        [rng.standard_normal((n_all, k), np.float32), np.ones((n_all, 1), np.float32)], axis=1
    )
    w_true = (rng.standard_normal(d + 1) * gen["fixed_scale"]).astype(np.float32)
    u_true = (rng.standard_normal(pat["n_users"]) * gen["entity_scale"]).astype(np.float32)
    m_true = (rng.standard_normal(pat["n_movies"]) * gen["entity_scale"]).astype(np.float32)
    margin = (values * w_true[pat["indices"]]).sum(axis=1) + u_true[pat["users"]] + m_true[pat["movies"]]
    labels = (rng.random(n_all) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)

    def part(lo, hi):
        return {
            "shards": {"g": {"indices": pat["indices"][lo:hi], "values": values[lo:hi], "dim": d + 1}},
            "labels": labels[lo:hi],
            "id_tags": {"userId": pat["users"][lo:hi], "movieId": pat["movies"][lo:hi]},
        }

    return {"train": part(0, n_train), "validation": part(n_train, n_all),
            "rows": n_train, "validation_rows": n_val}

"""Plain reference for one coordinate-descent pass of a logistic GLMix.

What the configuration states, coordinate by coordinate in order, from a zero
model: each coordinate minimises
    sum_i m_i [softplus(z_i) - y_i z_i] + reg_weight / 2 |w|^2,
    z_i = x_i.w + (the scores of the coordinates trained before it),
with the stated truncated L-BFGS (references/lbfgs.py). A fixed effect is one
problem over all rows. A random effect is one problem per entity over the
entity's active rows: all of its rows, or where it has more than the cap, the
cap rows of smallest priority (`priorities`, the keyed reservoir the
configuration states). Its scores cover every row of the entity. An entity's
coefficients live in the shard's own feature space; a feature none of its
active rows holds has no data and stays 0 under L2 from a zero start, which is
what the program's per-entity index map amounts to.

float32 throughout, on rows made dense block by block, no program code. `storage`
rounds the training feature values through a lower-precision type: the control.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import lbfgs, metrics


def priorities(codes: np.ndarray) -> np.ndarray:
    """splitmix64 mix of (entity code, row index), as the configuration states it."""
    with np.errstate(over="ignore"):
        x = codes.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
        x += np.arange(len(codes), dtype=np.uint64) * np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return x


def active_blocks(ids: np.ndarray, cap: int) -> tuple:
    """(entity ids (E,), rows (E, S) int32, mask (E, S)): each entity's active
    rows, padded to the largest active count."""
    entities, codes = np.unique(ids, return_inverse=True)
    order = np.lexsort((priorities(codes), codes))
    counts = np.bincount(codes, minlength=len(entities))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(len(ids)) - starts[codes[order]]
    keep = rank < cap
    rows_kept, ent_kept, rank_kept = order[keep], codes[order][keep], rank[keep]
    width = int(min(counts.max(), cap))
    rows = np.zeros((len(entities), width), np.int32)
    mask = np.zeros((len(entities), width), np.float32)
    rows[ent_kept, rank_kept] = rows_kept
    mask[ent_kept, rank_kept] = 1.0
    return entities, rows, mask


def _cap(coordinate: dict, rows: int) -> int:
    return next(
        t["active_upper_bound"]
        for t in coordinate["active_upper_bound_by_rows"]
        if t["up_to_rows"] is None or rows <= t["up_to_rows"]
    )


CHUNK_CELLS = 1 << 18  # rows densified at a time: 2**18 x d float32 is ~0.2 GB at d = 201


def _dense(idx, val, d):
    """(..., K) stored entries -> (..., d) dense rows, exactly: d is small, and
    a dense row is what the sparse one stands for. A scatter-add into the
    gradient says the same and takes the chip a thousand times as long."""
    return jnp.sum(val[..., None] * (idx[..., None] == jnp.arange(d, dtype=idx.dtype)), axis=-2)


@jax.jit
def _batched_objective(W, idx, val, y, off, mask, l2):
    """E problems at once: idx, val (E, S, K); y, off, mask (E, S); W (E, D).
    Problems are taken a chunk at a time so that the dense rows stay small."""
    E, S, K = idx.shape
    D = W.shape[1]
    per = max(1, min(E, CHUNK_CELLS // S))
    pad = (-E) % per

    def chunks(a):
        a = jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a
        return a.reshape((E + pad) // per, per, *a.shape[1:])

    def one(args):
        w, i, v, yy, oo, mm = args
        x = _dense(i, v, D)  # (per, S, D)
        z = jnp.sum(x * w[:, None, :], axis=-1) + oo
        f = jnp.sum(mm * (jax.nn.softplus(z) - yy * z), axis=-1)
        u = mm * (jax.nn.sigmoid(z) - yy)
        return f, jnp.sum(u[..., None] * x, axis=1)

    f, g = jax.lax.map(one, tuple(chunks(a) for a in (W, idx, val, y, off, mask)))
    f, g = f.reshape(-1)[:E], g.reshape(-1, D)[:E]
    return f + 0.5 * l2 * jnp.sum(W * W, axis=-1), g + l2 * W


@jax.jit
def _pooled_objective(w, idx, val, y, off, l2):
    """One problem over all rows (a fixed effect): row chunks, sums pooled."""
    N, K = idx.shape
    D = w.shape[0]
    per = min(N, CHUNK_CELLS)
    pad = (-N) % per

    def chunks(a):
        a = jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a
        return a.reshape((N + pad) // per, per, *a.shape[1:])

    def one(args):
        i, v, yy, oo, mm = args
        x = _dense(i, v, D)  # (per, D)
        z = jnp.sum(x * w, axis=-1) + oo
        u = mm * (jax.nn.sigmoid(z) - yy)
        return jnp.sum(mm * (jax.nn.softplus(z) - yy * z)), jnp.sum(u[:, None] * x, axis=0)

    live = jnp.ones((N,), jnp.float32)
    f, g = jax.lax.map(one, tuple(chunks(a) for a in (idx, val, y, off, live)))
    return (jnp.sum(f) + 0.5 * l2 * jnp.dot(w, w))[None], (jnp.sum(g, axis=0) + l2 * w)[None]


def _scores(gathered, val):
    """Row scores from each row's gathered (N, K) coefficients."""
    return jnp.sum(val * gathered, axis=-1)


def solve(config: dict, problem: dict, storage=None) -> dict:
    if config["coordinate_descent_iterations"] != 1:
        raise ValueError("this reference follows one coordinate-descent pass")
    train, validation = problem["train"], problem["validation"]
    n = len(train["labels"])
    y = jnp.asarray(train["labels"], jnp.float32)
    summed = jnp.zeros((n,), jnp.float32)
    val_summed = jnp.zeros((len(validation["labels"]),), jnp.float32)
    coefficients = {}
    for c in config["coordinates"]:
        shard = train["shards"][c["shard"]]
        d = shard["dim"]
        idx = jnp.asarray(shard["indices"], jnp.int32)
        val = jnp.asarray(shard["values"], jnp.float32)
        if storage is not None:
            val = val.astype(jnp.dtype(storage)).astype(jnp.float32)
        v_idx = jnp.asarray(validation["shards"][c["shard"]]["indices"], jnp.int32)
        v_val = jnp.asarray(validation["shards"][c["shard"]]["values"], jnp.float32)
        opt = c["optimizer"]
        l2 = jnp.float32(c["reg_weight"])
        if c["kind"] == "fixed":
            fun = lambda W: _pooled_objective(W[0], idx, val, y, summed, l2)
            W, _ = lbfgs.minimize(
                fun, jnp.zeros((1, d), jnp.float32),
                max_iterations=opt["max_iterations"], tolerance=opt["tolerance"],
            )
            w = W[0]
            coefficients[c["id"]] = np.asarray(w)
            summed = summed + _scores(w[idx], val)
            val_summed = val_summed + _scores(w[v_idx], v_val)
            continue
        ids = np.asarray(train["id_tags"][c["tag"]])
        entities, rows, mask = active_blocks(ids, _cap(c, n))
        rows_d, mask_d = jnp.asarray(rows), jnp.asarray(mask)
        b_idx, b_val = idx[rows_d], val[rows_d]
        b_y, b_off = y[rows_d], summed[rows_d]
        fun = lambda W: _batched_objective(W, b_idx, b_val, b_y, b_off, mask_d, l2)
        W, _ = lbfgs.minimize(
            fun, jnp.zeros((len(entities), d), jnp.float32),
            max_iterations=opt["max_iterations"], tolerance=opt["tolerance"],
        )
        del b_idx, b_val, b_y, b_off
        full = np.zeros((int(entities.max()) + 1, d), np.float32)
        full[entities] = np.asarray(W)
        coefficients[c["id"]] = full
        full_d = jnp.asarray(full)
        summed = summed + _scores(full_d[jnp.asarray(ids)[:, None], idx], val)
        v_ids = jnp.asarray(np.asarray(validation["id_tags"][c["tag"]]))
        val_summed = val_summed + _scores(full_d[v_ids[:, None], v_idx], v_val)
    return {
        "coefficients": coefficients,
        "metric": metrics.auc(np.asarray(val_summed), np.asarray(validation["labels"])),
    }

"""Plain reference for `glm_sparse_lbfgs`'s objective on rows that lie on
several chips, a part a chip.

The statement is `glm_sparse_lbfgs`'s: stored entries (K a row: a feature id
and a float32 value each), the objective
    sum_i c_i [softplus(z_i) - y_i z_i] + reg_weight / 2 |w|^2,   z_i = sum_k v_ik w[j_ik],
where c_i is 1 for a row and 0 for a pad row (the part's `weights`; a part
without them has no pad rows), and the stated optimizer (references/lbfgs.py)
from a zero start, in float32. Each chip's part stays where it lies: the
coefficients are copied to the chip, the chip adds up value and gradient over
its own rows a block at a time (`take`, a row sum, `segment_sum`; the last
block starts early enough to be whole and skips the rows the block before it
counted), and the chips' partial values and gradients are added on the first.
Validation scores are the same row sums, brought to the host (4 B a row) with
the labels; pad rows are left out of the AUC. `storage` rounds the stored
values through a lower type: that is the control. No program code.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import lbfgs, metrics


def _scores(w, idx, val):
    return jnp.sum(val * jnp.take(w, idx), axis=-1)


def _partial(w, idx, val, y, c, *, per: int):
    """(value, gradient) of the loss over one part's rows, `per` rows a block."""
    n = idx.shape[0]

    def one(carry, block):
        f, g = carry
        start = jnp.minimum(block * per, n - per)
        rows = lambda a: jax.lax.dynamic_slice_in_dim(a, start, per)
        i, v, yy = rows(idx), rows(val), rows(y)
        # Rows before this block's nominal start belong to the block before it.
        cc = jnp.where(start + jnp.arange(per) >= block * per, rows(c), 0.0)
        z = _scores(w, i, v)
        u = cc * (jax.nn.sigmoid(z) - yy)
        g = g + jax.ops.segment_sum((v * u[:, None]).reshape(-1), i.reshape(-1), w.shape[0])
        return (f + jnp.sum(cc * (jax.nn.softplus(z) - yy * z)), g), None

    (f, g), _ = jax.lax.scan(one, (jnp.float32(0.0), jnp.zeros_like(w)), jnp.arange(-(-n // per)))
    return f, g


def _chip_of(a):
    return next(iter(a.devices()))


def solve(config: dict, problem: dict, storage=None) -> dict:
    coordinate = config["coordinates"][0]
    opt = coordinate["optimizer"]
    l2 = jnp.float32(coordinate["reg_weight"])
    train = problem["train"]
    shard = train["shards"][coordinate["shard"]]
    labels = train["labels"].parts
    weights = train["weights"].parts if "weights" in train else [jnp.ones_like(y) for y in labels]
    values = shard["values"].parts
    if storage is not None:
        values = [v.astype(jnp.dtype(storage)).astype(jnp.float32) for v in values]
    parts = list(zip(shard["indices"].parts, values, labels, weights))
    first = _chip_of(labels[0])
    per = min(config["reference"]["row_block"], min(len(y) for y in labels))
    partial = jax.jit(_partial, static_argnames="per")

    def objective(W):
        w = W[0]
        # Every chip is handed its call before any result is waited for.
        sums = [partial(jax.device_put(w, _chip_of(y)), i, v, y, c, per=per) for i, v, y, c in parts]
        sums = jax.device_put(sums, first)
        f = sum(s[0] for s in sums) + 0.5 * l2 * jnp.dot(w, w)
        g = sum(s[1] for s in sums) + l2 * w
        return f[None], g[None]

    with jax.default_matmul_precision("highest"):
        W, info = lbfgs.minimize(
            objective, jax.device_put(jnp.zeros((1, shard["dim"]), jnp.float32), first),
            max_iterations=opt["max_iterations"], tolerance=opt["tolerance"],
        )
        w = W[0]
        del parts, values
        held = problem["validation"]
        held_shard = held["shards"][coordinate["shard"]]
        scores = [
            jax.jit(_scores)(jax.device_put(w, _chip_of(i)), i, v)
            for i, v in zip(held_shard["indices"].parts, held_shard["values"].parts)
        ]
        scores = np.concatenate([np.asarray(s) for s in scores])
    held_labels = np.concatenate([np.asarray(y) for y in held["labels"].parts])
    real = np.concatenate([np.asarray(c) for c in held["weights"].parts]) > 0 if "weights" in held else slice(None)
    return {
        "coefficients": {coordinate["id"]: np.asarray(w)},
        "metric": metrics.auc(scores[real], held_labels[real]),
        "info": info,
    }

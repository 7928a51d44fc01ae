"""Plain reference for a wide sparse, L2-regularised logistic fixed effect, by
the stated optimizer.

The configuration states the rows as stored entries (K a row: a feature id and
a float32 value each), the objective
    sum_i [softplus(z_i) - y_i z_i] + reg_weight / 2 |w|^2,   z_i = sum_k v_ik w[j_ik],
and the optimizer (references/lbfgs.py) with its iteration limit and
tolerance. The reference does just that from a zero start, in float32: the
coefficients taken at the ids, a row sum, and the gradient added up feature by
feature (`segment_sum`), a block of rows at a time so that a block's
temporaries are all it holds beside the rows. Validation scores are the same
row sums. `storage` rounds the stored values through a lower type: that is the
control. No program code.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import lbfgs, metrics


def _blocks(a, per: int):
    return a.reshape(a.shape[0] // per, per, *a.shape[1:])


def _scores(w, idx, val):
    return jnp.sum(val * jnp.take(w, idx), axis=-1)


@jax.jit
def _objective(W, idx, val, y, l2):
    """idx, val (B, R, K) and y (B, R): B blocks of R rows."""
    w = W[0]

    def one(carry, block):
        f, g = carry
        i, v, yy = block
        z = _scores(w, i, v)
        u = jax.nn.sigmoid(z) - yy
        g = g + jax.ops.segment_sum((v * u[:, None]).reshape(-1), i.reshape(-1), w.shape[0])
        return (f + jnp.sum(jax.nn.softplus(z) - yy * z), g), None

    (f, g), _ = jax.lax.scan(one, (jnp.float32(0.0), jnp.zeros_like(w)), (idx, val, y))
    return (f + 0.5 * l2 * jnp.dot(w, w))[None], (g + l2 * w)[None]


def solve(config: dict, problem: dict, storage=None) -> dict:
    coordinate = config["coordinates"][0]
    opt = coordinate["optimizer"]
    l2 = jnp.float32(coordinate["reg_weight"])
    train = problem["train"]
    shard = train["shards"][coordinate["shard"]]
    n = len(train["labels"])
    per = min(config["reference"]["row_block"], n)
    if n % per:
        raise ValueError(f"{n} rows are not whole blocks of {per}")
    val = jnp.asarray(shard["values"], jnp.float32)
    if storage is not None:
        val = val.astype(jnp.dtype(storage)).astype(jnp.float32)
    idx = _blocks(jnp.asarray(shard["indices"], jnp.int32), per)
    val = _blocks(val, per)
    y = _blocks(jnp.asarray(train["labels"], jnp.float32), per)
    with jax.default_matmul_precision("highest"):
        W, info = lbfgs.minimize(
            lambda W: _objective(W, idx, val, y, l2),
            jnp.zeros((1, shard["dim"]), jnp.float32),
            max_iterations=opt["max_iterations"], tolerance=opt["tolerance"],
        )
        w = W[0]
        del idx, val, y
        held = problem["validation"]["shards"][coordinate["shard"]]
        scores = jax.jit(_scores)(
            w, jnp.asarray(held["indices"], jnp.int32), jnp.asarray(held["values"], jnp.float32)
        )
    return {
        "coefficients": {coordinate["id"]: np.asarray(w)},
        "metric": metrics.auc(np.asarray(scores), np.asarray(problem["validation"]["labels"])),
        "info": info,
    }

"""Plain reference for a dense, L2-regularised fixed effect solved by the stated
trust-region Newton method (references/tron.py).

The configuration states the rows' training storage (bfloat16, accumulated in
float32), the task and with it the loss l(z, y) of the margin z_i = x_i.w
(logistic: softplus(z) - y z; linear: (z - y)^2 / 2), the objective
    sum_i l(z_i, y_i) + reg_weight / 2 |w|^2,
its Hessian-vector product  X^T (D * (X v)) + reg_weight v,  D_i = l''(z_i)
(logistic: s (1 - s), s = sigmoid(z); linear: 1), and the optimizer with its
step limit and tolerance. The reference does just that from a zero start, in
float32 at `highest` matmul precision, one block of rows at a time so that no
float32 copy of the whole matrix is ever made. D is kept from the objective's
last evaluation, as LIBLINEAR keeps it, so a product reads the rows once and
never recomputes the margins. Validation scores read the float32 rows, as the
configuration states. `storage` replaces the stated storage type with a lower
one: that is the control.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import metrics, tron

BLOCK_ROWS = 65_536


def block_count(rows: int) -> int:
    """The fewest equal blocks of at most BLOCK_ROWS rows (1 where only
    single rows divide such a count evenly: a small or prime row count)."""
    least = math.ceil(rows / BLOCK_ROWS)
    return next((k for k in range(least, 4 * least + 1) if rows % k == 0), 1)


def _scan_blocks(step, init, x, *per_row):
    """`lax.scan` of `step(carry, x_block, *per_row blocks)` over equal
    blocks of rows, each cut out where it lies (no reshape of the matrix)."""
    rows = x.shape[0]
    blocks = block_count(rows)
    size = rows // blocks

    def body(carry, i):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * size, size, 0)
        return step(carry, cut(x), *[cut(a) for a in per_row])

    return jax.lax.scan(body, init, jnp.arange(blocks))


def _dot(a, b):
    return jnp.dot(a, b, precision="highest", preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames="logistic")
def _objective(w, x, y, l2, *, logistic):
    """(f, g, D): value, gradient and the curvature weights at w."""

    def step(carry, xb, yb):
        f, g = carry
        z = _dot(xb, w)
        if logistic:
            s = jax.nn.sigmoid(z)
            loss, slope, curve = jax.nn.softplus(z) - yb * z, s - yb, s * (1.0 - s)
        else:
            loss, slope, curve = 0.5 * (z - yb) ** 2, z - yb, jnp.ones_like(z)
        return (f + jnp.sum(loss), g + _dot(slope, xb)), curve

    (f, g), curve = _scan_blocks(step, (jnp.float32(0.0), jnp.zeros_like(w)), x, y)
    return f + 0.5 * l2 * jnp.dot(w, w), g + l2 * w, curve.reshape(-1)


@jax.jit
def _hessian_vector(curve, v, x, l2):
    step = lambda hv, xb, db: (hv + _dot(db * _dot(xb, v), xb), None)
    return _scan_blocks(step, jnp.zeros_like(v), x, curve)[0] + l2 * v


def solve(config: dict, problem: dict, storage=None) -> dict:
    coordinate = config["coordinates"][0]
    opt = coordinate["optimizer"]
    l2 = jnp.float32(coordinate["reg_weight"])
    shard = coordinate["shard"]
    x = problem["train"]["shards"][shard]
    y = problem["train"]["labels"]
    logistic = {"LOGISTIC_REGRESSION": True, "LINEAR_REGRESSION": False}[config["task"]]
    stored = x.astype(jnp.dtype(storage or config["train_storage_dtype"]))
    # Held as bfloat16 (which every lower type fits in exactly) so the rows
    # cost the chip no more than the stated storage does.
    if stored.dtype.itemsize < 2:
        stored = stored.astype(jnp.bfloat16)
    w, info = tron.minimize(
        lambda w: _objective(w, stored, y, l2, logistic=logistic),
        lambda curve, v: _hessian_vector(curve, v, stored, l2),
        jnp.zeros((x.shape[1],), jnp.float32),
        max_iterations=opt["max_iterations"], tolerance=opt["tolerance"],
    )
    xv = problem["validation"]["shards"][shard]
    scores = jnp.dot(xv, w, precision="highest")
    labels = np.asarray(problem["validation"]["labels"])
    metric = (
        metrics.auc(np.asarray(scores), labels) if logistic
        else float(np.sqrt(np.mean((np.asarray(scores, np.float64) - labels) ** 2)))
    )
    return {"coefficients": {coordinate["id"]: np.asarray(w)}, "metric": metric, "info": info}

"""Plain reference for a dense, L2-regularised logistic fixed effect, by the
stated optimizer.

The configuration states the rows' training storage (bfloat16, accumulated in
float32), the objective
    sum_i [softplus(z_i) - y_i z_i] + reg_weight / 2 |w|^2,   z_i = x_i.w,
and the optimizer (references/lbfgs.py) with its iteration limit and
tolerance. The reference does just that, from a zero start, in float32 at
`highest` matmul precision over the rows as stored. Validation scores read the
float32 rows, as the configuration states. `storage` replaces the stated
storage type with a lower one: that is the control.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import lbfgs, metrics


@jax.jit
def _objective(W, x, y, l2):
    w = W[0]
    z = jnp.dot(x, w, precision="highest", preferred_element_type=jnp.float32)
    f = jnp.sum(jax.nn.softplus(z) - y * z) + 0.5 * l2 * jnp.dot(w, w)
    g = jnp.dot(jax.nn.sigmoid(z) - y, x, precision="highest", preferred_element_type=jnp.float32)
    return f[None], (g + l2 * w)[None]


def solve(config: dict, problem: dict, storage=None) -> dict:
    coordinate = config["coordinates"][0]
    opt = coordinate["optimizer"]
    l2 = jnp.float32(coordinate["reg_weight"])
    shard = coordinate["shard"]
    x = problem["train"]["shards"][shard]
    y = problem["train"]["labels"]
    stored = x.astype(jnp.dtype(storage or config["train_storage_dtype"]))
    # Held as bfloat16 (which every lower type fits in exactly) so the rows
    # cost the chip no more than the stated storage does.
    stored = stored.astype(jnp.bfloat16)
    W, info = lbfgs.minimize(
        lambda W: _objective(W, stored, y, l2),
        jnp.zeros((1, x.shape[1]), jnp.float32),
        max_iterations=opt["max_iterations"], tolerance=opt["tolerance"],
    )
    w = W[0]
    xv = problem["validation"]["shards"][shard]
    scores = jnp.dot(xv, w, precision="highest")
    return {
        "coefficients": {coordinate["id"]: np.asarray(w)},
        "metric": metrics.auc(np.asarray(scores), np.asarray(problem["validation"]["labels"])),
        "info": info,
    }

"""Dense rows of unit norm with Bernoulli labels, drawn on the device from a seed.

The shape of PASCAL `epsilon_normalized`: every row has norm 1. One jitted call
draws the training rows block by block (so the draw's temporaries stay a block
wide) and a second the validation rows; nothing is made on the host.
"""

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed, the rest fold in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _draw(key, w_true, blocks: int, block: int, d: int):
    def one(k):
        kx, ky = jax.random.split(k)
        x = jax.random.normal(kx, (block, d), jnp.float32)
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        z = jnp.dot(x, w_true, precision="highest")
        y = (jax.random.uniform(ky, (block,)) < jax.nn.sigmoid(z)).astype(jnp.float32)
        return x, y

    x, y = jax.lax.map(one, jax.random.split(key, blocks))
    return x.reshape(blocks * block, d), y.reshape(blocks * block)


def generate(config: dict, seed: int, rows=None) -> dict:
    gen = config["generator"]
    d = config["features"]
    n_train = rows or config["rows"]
    n_val = max(n_train // 4, 1) if rows else config["validation_rows"]
    block = min(gen["row_block"], n_train, n_val)
    if n_train % block or n_val % block:
        raise ValueError(f"rows {n_train}/{n_val} are not whole blocks of {block}")
    k_w, k_train, k_val = jax.random.split(seed_key(seed), 3)
    # Unit rows in a random direction give margins of standard deviation
    # |w| / sqrt(d) = margin_scale.
    w_true = jax.random.normal(k_w, (d,), jnp.float32) * gen["margin_scale"]
    draw = jax.jit(_draw, static_argnums=(2, 3, 4))
    x, y = draw(k_train, w_true, n_train // block, block, d)
    xv, yv = draw(k_val, w_true, n_val // block, block, d)
    return {
        "train": {"shards": {"g": x}, "labels": y, "id_tags": {}},
        "validation": {"shards": {"g": xv}, "labels": yv, "id_tags": {}},
        "rows": n_train,
        "validation_rows": n_val,
    }

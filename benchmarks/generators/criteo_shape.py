"""Criteo-shaped click rows: 39 fields, one hashed id a field a row, unit norm.

The shape of LIBSVM `criteo`: every row holds 39 non-zeros (13 discretised
numeric fields, 26 categorical), every value 1/sqrt(39), the ids in a space of
1,000,000. Here each field owns a disjoint range of that space (the
configuration's `field_sizes`, which sum to the width), so the 39 ids of a row
are distinct. Within a field the id's RANK is heavy-tailed: a discretised
power law, P(rank k) ~ the integral of x**-s over [k + 1, k + 2), so a few ids
sit in most rows and most ids in few. A rank becomes an id through an affine
bijection of the field's range (a fixed odd multiplier, an offset from the
seed), as a hash would scatter the popular values over the range.
Labels are Bernoulli from a true coefficient vector's margins, shifted so that
their mean over the training rows is the configuration's `mean_margin`.

ELL shapes are static whatever the pattern, so pattern and numbers both come
from `--seed`. Everything is drawn on the device in jitted calls, the rows a
block at a time; nothing is made on the host.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .dense_unit_rows import seed_key


def field_ranges(gen: dict) -> tuple:
    """(starts, sizes) of the fields' id ranges, as int32 arrays of one entry a field."""
    sizes = np.asarray(gen["field_sizes"], np.int64)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return starts.astype(np.int32), sizes.astype(np.int32)


SCRAMBLE = 4093  # a prime under 2**12: SCRAMBLE * rank stays inside 31 bits for any field under 2**19 ids


def scramble(rank, offset, size):
    """(SCRAMBLE * rank + offset) mod size on int32 arrays, without an integer
    division (the chip's compiler takes half a minute over one): the quotient
    from a float32 division is within one of the true one, and two selects
    repair it. A bijection of [0, size) where SCRAMBLE does not divide size."""
    prod = SCRAMBLE * rank + offset
    q = jnp.floor(prod.astype(jnp.float32) / size.astype(jnp.float32)).astype(jnp.int32)
    r = prod - q * size
    r = jnp.where(r < 0, r + size, r)
    return jnp.where(r >= size, r - size, r)


def _draw(key, w_true, starts, sizes, offsets, blocks: int, block: int, exponent: float):
    """(indices (N, F) int32, margins (N,)) of blocks * block rows. Inside, a
    block is (F, rows): the long axis last, as the chip's compiler wants it."""
    starts, sizes, offsets = starts[:, None], sizes[:, None], offsets[:, None]
    span = (sizes.astype(jnp.float32) + 1.0) ** (1.0 - exponent) - 1.0

    def one(k):
        u = jax.random.uniform(k, (sizes.shape[0], block), jnp.float32)
        # Inverse CDF of the density x**-s on [1, size + 1); rank = floor(x) - 1.
        x = (1.0 + u * span) ** (1.0 / (1.0 - exponent))
        rank = jnp.clip(x.astype(jnp.int32), 1, sizes) - 1
        ids = starts + scramble(rank, offsets, sizes)
        z = jnp.sum(jnp.take(w_true, ids), axis=0) / math.sqrt(sizes.shape[0])
        return ids, z

    ids, z = jax.lax.map(one, jax.random.split(key, blocks))  # (blocks, F, block)
    return ids.transpose(1, 0, 2).reshape(sizes.shape[0], blocks * block).T, z.reshape(blocks * block)


def _labels(key, z, shift):
    return (jax.random.uniform(key, z.shape) < jax.nn.sigmoid(z + shift)).astype(jnp.float32)


def require(config: dict) -> None:
    """Refuse, before a row is made, a program that lacks what the
    configuration names under `requires` (`module:attribute`). The commit
    before this configuration packs a sparse fixed effect first and judges the
    pack afterwards: at these shapes that asks the host for two planes of 125
    GB and takes the machine down with it, where this is an exit code."""
    import importlib

    for needed in config.get("requires", []):
        module, attribute = needed.split(":")
        if not hasattr(importlib.import_module(module), attribute):
            raise SystemExit(f"{config['name']}: this program cannot run the configuration: it has no {needed}")


def generate(config: dict, seed: int, rows=None) -> dict:
    require(config)
    gen = config["generator"]
    d = config["features"]
    starts, sizes = field_ranges(gen)
    if int(sizes.sum()) != d or len(sizes) != config["nnz_per_row"]:
        raise ValueError("field_sizes must be one a non-zero and sum to the width")
    n_train = rows or config["rows"]
    n_val = max(n_train // 8, 1) if rows else config["validation_rows"]
    block = min(gen["row_block"], n_train, n_val)
    if n_train % block or n_val % block:
        raise ValueError(f"rows {n_train}/{n_val} are not whole blocks of {block}")
    if np.any(sizes % SCRAMBLE == 0) or int(sizes.max()) >= 2**19:
        raise ValueError(f"a field's size must be under 2**19 and no multiple of {SCRAMBLE}")
    k_offset, k_w, k_train, k_val, k_y, k_yv = jax.random.split(seed_key(seed), 6)
    offsets = jax.random.randint(k_offset, sizes.shape, 0, jnp.asarray(sizes))
    # A row's margin sums 39 coefficients over sqrt(39): its standard deviation
    # over rows is about margin_scale.
    w_true = jax.random.normal(k_w, (d,), jnp.float32) * gen["margin_scale"]
    draw = jax.jit(_draw, static_argnums=(5, 6, 7))
    args = (w_true, jnp.asarray(starts), jnp.asarray(sizes), offsets)
    idx, z = draw(k_train, *args, n_train // block, block, gen["zipf_exponent"])
    idx_v, z_v = draw(k_val, *args, n_val // block, block, gen["zipf_exponent"])
    shift = gen["mean_margin"] - jnp.mean(z)
    labels = jax.jit(_labels)
    value = np.float32(1.0 / math.sqrt(len(sizes)))

    def part(indices, y):
        shard = {"indices": indices, "values": jnp.full(indices.shape, value, jnp.float32), "dim": d}
        return {"shards": {"g": shard}, "labels": y, "id_tags": {}}

    return {"train": part(idx, labels(k_y, z, shift)), "validation": part(idx_v, labels(k_yv, z_v, shift)),
            "rows": n_train, "validation_rows": n_val}

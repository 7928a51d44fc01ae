"""`criteo_shape`'s rows for a deployment over several chips: each chip's own
rows drawn on that chip, nothing whole anywhere.

The draw is `criteo_shape`'s (its fields, popularity, scramble and labels);
chip c draws its contiguous part of the rows from the part's key folded with c
(one program over a mesh of the chips, each on its own index), and the label
shift comes from the mean margin over every chip's rows. The
parts are of one length, `ceil(rows / chips)`: where the rows do not divide,
the last part ends in pad rows (index 0, value 0, label 0) and `weights` says
so with zeros. A part of the problem holds one array a chip (`PerChip`), in the
order of the chips; `rows` and `validation_rows` count the real rows.

`--rows N` is a rehearsal on whatever devices JAX has, up to the deployment's
chips.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .criteo_shape import SCRAMBLE, _draw, _labels, field_ranges, require
from .dense_unit_rows import seed_key


class PerChip:
    """One array a chip, the chips' rows in order. It answers what `run.py`'s
    `first_half` asks of an array (`len`, `shape`, a cut of the leading rows)
    without a whole copy on any device: a cut to n of N rows keeps the first
    n / N of every chip's rows."""

    def __init__(self, parts):
        self.parts = list(parts)

    def __len__(self):
        return sum(len(p) for p in self.parts)

    @property
    def shape(self):
        return (len(self), *self.parts[0].shape[1:])

    def __getitem__(self, rows):
        if not isinstance(rows, slice) or rows.start is not None or rows.step is not None:
            raise TypeError("PerChip takes a cut of the leading rows only")
        total = len(self)
        return PerChip([p[: len(p) * rows.stop // total] for p in self.parts])


def _rows(key, w_true, starts, sizes, offsets, real, *, blocks: int, block: int, exponent: float, rows: int):
    """One chip's part: `rows` rows of which the first `real` are drawn and the
    rest are pads. Returns the index plane, the margins, and the margins' sum
    over the real rows."""
    idx, z = _draw(key, w_true, starts, sizes, offsets, blocks, block, exponent)
    idx, z = idx[:rows], z[:rows]
    is_real = jnp.arange(rows) < real
    return jnp.where(is_real[:, None], idx, 0), z, jnp.sum(jnp.where(is_real, z, 0.0))


def _values_labels_weights(key, z, shift, real, *, nnz: int):
    """The value plane (1 / sqrt(nnz) in every real slot), labels and weights of
    one chip's part; a program of its own, so that the draw's temporaries are
    gone before the second plane exists."""
    is_real = jnp.arange(z.shape[0]) < real
    values = jnp.where(is_real[:, None], jnp.full((z.shape[0], nnz), 1.0 / math.sqrt(nnz), jnp.float32), 0.0)
    return values, jnp.where(is_real, _labels(key, z, shift), 0.0), is_real.astype(jnp.float32)


def generate(config: dict, seed: int, rows=None) -> dict:
    require(config)
    gen = config["generator"]
    d_features = config["features"]
    starts, sizes = field_ranges(gen)
    if int(sizes.sum()) != d_features or len(sizes) != config["nnz_per_row"]:
        raise ValueError("field_sizes must be one a non-zero and sum to the width")
    if np.any(sizes % SCRAMBLE == 0) or int(sizes.max()) >= 2**19:
        raise ValueError(f"a field's size must be under 2**19 and no multiple of {SCRAMBLE}")
    devices = jax.devices()[: config["deployment"]["chips"]]
    n_train = rows or config["rows"]
    n_val = max(n_train // 8, 1) if rows else config["validation_rows"]
    k_offset, k_w, k_train, k_val, k_y, k_yv = jax.random.split(seed_key(seed), 6)
    offsets = jax.random.randint(k_offset, sizes.shape, 0, jnp.asarray(sizes))
    # A row's margin sums 39 coefficients over sqrt(39): its standard deviation
    # over rows is about margin_scale.
    w_true = jax.random.normal(k_w, (d_features,), jnp.float32) * gen["margin_scale"]
    shared = (w_true, jnp.asarray(starts), jnp.asarray(sizes), offsets)
    # One program over the chips, each running the draw on its own index: one
    # compilation, where a call a chip would compile the same draw for each.
    mesh = Mesh(np.asarray(devices), ("chips",))
    over_chips = lambda f, in_specs: jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=P("chips"), check_vma=False)
    )

    def part(n, key, k_labels, mean_margin_over=None):
        """A part of the problem, n real rows over the chips."""
        per = -(-n // len(devices))
        block = min(gen["row_block"], per)

        def real():  # this chip's real rows: the rest of its part are pads
            return jnp.clip(n - jax.lax.axis_index("chips") * per, 0, per)

        def rows(key, *shared):
            idx, z, total = _rows(
                jax.random.fold_in(key, jax.lax.axis_index("chips")), *shared, real(),
                blocks=-(-per // block), block=block, exponent=gen["zipf_exponent"], rows=per,
            )
            return idx, z, total[None]

        def values_labels_weights(key, z, shift):
            key = jax.random.fold_in(key, jax.lax.axis_index("chips"))
            return _values_labels_weights(key, z, shift, real(), nnz=len(sizes))

        idx, z, totals = over_chips(rows, P())(key, *shared)
        if mean_margin_over is None:  # the training rows set the shift for both parts
            mean_margin_over = float(jnp.sum(totals)) / n
        shift = jnp.float32(gen["mean_margin"] - mean_margin_over)
        val, y, w = over_chips(values_labels_weights, (P(), P("chips"), P()))(k_labels, z, shift)

        def per_chip(a):
            held = {shard.device: shard.data for shard in a.addressable_shards}
            return PerChip(held[device] for device in devices)

        shard = {"indices": per_chip(idx), "values": per_chip(val), "dim": d_features}
        return {"shards": {"g": shard}, "labels": per_chip(y), "weights": per_chip(w), "id_tags": {}}, mean_margin_over

    train, mean_margin = part(n_train, k_train, k_y)
    validation, _ = part(n_val, k_val, k_yv, mean_margin)
    return {"train": train, "validation": validation, "rows": n_train, "validation_rows": n_val}

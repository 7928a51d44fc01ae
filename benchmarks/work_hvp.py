"""Bytes and operations one Hessian-vector product of a dense fixed effect
needs, from the configuration's shapes alone:  H v = X^T (D * (X v)) + l2 v
with the curvature weights D at the current coefficients w.

Counted as `work.py` counts an evaluation: one read of the training rows at
the stated storage, of the three per-row vectors (labels, offsets, weights:
what D is made of when it is not kept), of w and v, and one write of the
product: rows.d.storage + 12 rows + 12 d bytes. Operations: the margins X w,
X v and the transpose, 2 rows.d each: 6 rows.d. An implementation that keeps
D from its last evaluation needs 4 rows.d and 4 rows fewer bytes; the count
is the larger one, what a product costs from the rows, whatever implements
it. At 400,000 x 2,000 in bfloat16: 1,604.8 MB, 1.96 ms at 819 GB/s against
0.024 ms at 197 TFLOP/s: HBM binds.
"""

from . import work


def dense_hessian_vector(rows: int, d: int, storage_bytes: int) -> dict:
    return {"bytes": rows * d * storage_bytes + 12 * rows + 12 * d, "flops": 6 * rows * d}


def fixed_effect_product(config: dict, rows: int):
    """One product of the configuration's first fixed-effect objective, or
    None where its shard is not dense (no count is kept for a sparse one)."""
    coordinate = next(c for c in config["coordinates"] if c["kind"] == "fixed")
    shard = config["shards"][coordinate["shard"]]
    if shard["kind"] != "dense":
        return None
    return dense_hessian_vector(rows, shard["dim"], work.STORAGE_BYTES[config["train_storage_dtype"]])

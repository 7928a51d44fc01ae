"""Bytes and operations an objective evaluation needs, from the configuration's shapes.

Counted once per evaluation: one read of the training data, of the per-row
vectors (labels, offsets, weights) and one read and one write of the
coefficients. Nothing here looks at how the program lays its data out, so the
numbers read the same work whatever a later PR packs it into.
"""


def dense_value_gradient(rows: int, d: int, storage_bytes: int) -> dict:
    """X.w, the loss, and X^T u over `rows` dense rows of width d."""
    return {"bytes": rows * d * storage_bytes + 12 * rows + 8 * d, "flops": 4 * rows * d}


def sparse_value_gradient(rows: int, nnz: int, d: int) -> dict:
    """The same over `nnz` stored entries: a 4-byte index and a 4-byte value each."""
    return {"bytes": 8 * nnz + 12 * rows + 8 * d, "flops": 4 * nnz}


def least_seconds(work: dict, peaks: dict) -> tuple:
    """(seconds, which peak binds) for one evaluation on a chip with these peaks."""
    by_bytes = work["bytes"] / (peaks["hbm_gb_per_s"] * 1e9)
    by_flops = work["flops"] / (peaks["bf16_tflop_per_s"] * 1e12)
    return (by_bytes, "hbm") if by_bytes >= by_flops else (by_flops, "mxu")


STORAGE_BYTES = {"float32": 4, "bfloat16": 2}


def fixed_effect_evaluation(config: dict, rows: int) -> dict:
    """One evaluation of the configuration's first fixed-effect objective."""
    coordinate = next(c for c in config["coordinates"] if c["kind"] == "fixed")
    shard = config["shards"][coordinate["shard"]]
    if shard["kind"] == "dense":
        return dense_value_gradient(rows, shard["dim"], STORAGE_BYTES[config["train_storage_dtype"]])
    return sparse_value_gradient(rows, rows * shard["nnz_per_row"], shard["dim"])

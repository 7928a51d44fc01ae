"""Roofline share of the dense Hessian-vector kernel (ops/pallas_glm): the
least time its calls in the traced fits could take over the device time they
took. Least time a call: work_hvp.dense_hessian_vector, one read of X at the
stated storage beside the per-row vectors and three (d,) vectors; HBM binds
(1.96 ms against 0.024 ms at 400,000 x 2,000 in bfloat16). None where the
trace holds no `hessian_vector_sums` event: another solver, a sparse shard,
or the XLA fall-back, which is a fault of a TRON cell and not a result."""

from .hessian_vector import traced_products


def read(run):
    found = traced_products(run)
    if found is None:
        return None
    calls, seconds, least = found
    return 100.0 * calls * least / seconds

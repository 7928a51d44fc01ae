"""Roofline share of the fused sparse value+gradient kernel (ops/pallas_sparse,
its level-2 spill in the same call): least time over device time in the trace.

Least time a call: one read of every stored index and value (8 bytes an entry),
of labels, offsets and weights, and of the coefficients
(work.sparse_value_gradient), from the configuration's shapes and not from the
pack's array sizes. HBM binds (4 FLOPs against 8 bytes an entry).
"""

from .kernels import fixed_effect_kernel


def read(run):
    found = fixed_effect_kernel(run, "sparse")
    if found is None:
        return None
    calls, seconds, least, _ = found
    return 100.0 * calls * least / seconds

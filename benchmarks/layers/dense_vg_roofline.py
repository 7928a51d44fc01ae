"""Roofline share of the dense value+gradient kernel (ops/pallas_glm): the
least time its calls could take over the device time they took in the trace.

Least time a call: the larger of bytes / HBM peak and FLOPs / MXU peak for one
read of X at the configuration's stated storage, of labels, offsets and weights,
and of the coefficients (work.dense_value_gradient). At 400,000 x 2,000 in
bfloat16 that is 1.96 ms by bytes against 0.016 ms by FLOPs: HBM binds.
"""

from .kernels import fixed_effect_kernel


def read(run):
    found = fixed_effect_kernel(run, "dense")
    if found is None:
        return None
    calls, seconds, least, _ = found
    return 100.0 * calls * least / seconds

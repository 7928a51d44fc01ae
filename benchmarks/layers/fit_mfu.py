"""The whole fit's share of the chip's peak: the least time the traced fits'
fixed-effect objective evaluations need, over the traced fits' wall.

Least time an evaluation: from the configuration's shapes, one read of the
training data (work.py). These models are bandwidth-bound: the peak that binds
is HBM, not the MXU. Evaluations are counted as executions of the fixed-effect
objective kernel in the trace. The random-effect solves' evaluations are not
counted: the program does not report `fn_evals` per update yet, so their useful
work is left out and the share is a lower bound that cannot pass 100%.
"""

from .kernels import fixed_effect_kernel


def read(run):
    found = fixed_effect_kernel(run, "dense") or fixed_effect_kernel(run, "sparse")
    if found is None:
        return None
    calls, _, least, _ = found
    return 100.0 * calls * least / run["trace"]["window_s"]

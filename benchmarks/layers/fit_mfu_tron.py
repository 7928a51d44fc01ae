"""The whole fit's share of the chip's HBM peak where the solver is TRON: the
least time of the traced fits' value+gradient evaluations plus the least time
of their Hessian-vector products, over the traced fits' wall. `fit_mfu`
counts executions of the value+gradient kernel alone and would read a fit
whose passes over X are mostly products as mostly idle. Both kinds of pass
are counted as executions of their kernels in the trace (layers/kernels.py,
layers/hessian_vector.py), so the metric needs nothing of the program's
counters; None where the trace holds no product."""

from .hessian_vector import traced_products
from .kernels import fixed_effect_kernel


def read(run):
    products = traced_products(run)
    evaluations = fixed_effect_kernel(run, "dense")
    if products is None or evaluations is None:
        return None
    useful = evaluations[0] * evaluations[2] + products[0] * products[2]
    return 100.0 * useful / run["trace"]["window_s"]

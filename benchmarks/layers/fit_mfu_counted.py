"""The whole fit's share of the chip's peak, by counted evaluations: the least
time the traced fits' fixed-effect objective evaluations need
(`objective_evaluations{kind=fixed}` times work.py's least seconds; HBM binds),
over the traced fits' wall. `fit_mfu` counts evaluations as executions of a
Pallas call by name and so reads nothing where no such call runs; this one
reads the same work whatever implements it.
"""

from .sparse_vg_roofline import traced_evaluations


def read(run):
    counted = traced_evaluations(run)
    if counted is None:
        return None
    evaluations, least = counted
    return 100.0 * evaluations * least / run["trace"]["window_s"]

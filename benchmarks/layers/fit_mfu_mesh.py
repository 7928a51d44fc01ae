"""The whole fit's share of the mesh's peak, by counted evaluations:
`fit_mfu_counted` for rows cut over several chips. The least time of one
evaluation of ALL the rows on one chip (work.py; HBM binds) is divided by the
chips the trace saw, since each streams its own part, and the traced fits'
fixed-effect evaluations times that are taken over the traced fits' wall."""

from .sparse_vg_roofline import traced_evaluations


def read(run):
    counted = traced_evaluations(run)
    if counted is None:
        return None
    evaluations, least = counted
    return 100.0 * evaluations * least / run["trace"]["devices"] / run["trace"]["window_s"]

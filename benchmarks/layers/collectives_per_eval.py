"""Collective operations an objective evaluation: the traced fits' collectives
a chip (a `-done` half is its `-start`'s and is not counted again) over the
fixed-effect evaluations the optimizer counted. It says whether the gradient
is reduced once an evaluation or once a plane: with one reduction an
evaluation, a fit's 4 evaluations add 4 to the 5 collectives a fit has beside
them (two evaluation programs of two each, one scalar), and the metric reads
about 2; reduced after every plane's scatter-add it reads the non-zeros a row
(39) or more. All collectives are counted, not the gradient's alone: the trace
names an operation `all-reduce.5` in every program that has one, so its HLO
line cannot tell the solve's from another program's."""

from . import collectives
from .sparse_vg_roofline import traced_evaluations


def read(run):
    counted = traced_evaluations(run)
    if counted is None:
        return None
    trace = run["trace"]
    found = [name for name in collectives.names(trace) if "-done" not in name]
    if not found:
        return None
    return sum(trace["op_count"][name] for name in found) / counted[0]

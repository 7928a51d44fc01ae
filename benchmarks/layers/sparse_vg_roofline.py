"""Roofline share of the wide sparse value+gradient: the least time the traced
fits' fixed-effect objective evaluations need, over the device self time of
the operations that carry the entry stream.

Least time an evaluation: one read of every stored index and value (8 bytes an
entry), of labels, offsets and weights, and one read and one write of the
coefficients (work.sparse_value_gradient), from the configuration's shapes. HBM
binds (4 FLOPs against 8 bytes an entry). Evaluations are the ones the
optimizer counted (`objective_evaluations{kind=fixed}`, the same in every fit
of a cell whose iteration limit binds), not executions of a kernel by name, so
the number reads the same work whatever implements it.
"""

import math
import re

from .. import work
from .stages import window_evaluations_per_fit

# A kernel that holds the entry stream pins this `name=`; until one does, the
# stream is in XLA's gathers, scatters and fusions, which are known by their
# shapes: an operation carries the stream if an array in its HLO line holds at
# least rows x nnz elements ((rows, nnz), its transpose, the entries flat, or
# that padded). Nothing else in a fit is that large.
KERNEL_PREFIX = "sparse_value_gradient"
SHAPE = re.compile(r"\[(\d+(?:,\d+)*)\]")


def sparse_shard(config):
    """The first fixed effect's shard where it is a sparse one, or None."""
    coordinate = next((c for c in config["coordinates"] if c["kind"] == "fixed"), None)
    shard = config["shards"][coordinate["shard"]] if coordinate else None
    return shard if shard and shard["kind"] == "sparse" else None


def traced_evaluations(run):
    """(evaluations the traced fits made, least seconds each), or None."""
    trace, config = run["trace"], run["config"]
    if not trace or sparse_shard(config) is None:
        return None
    per_fit = window_evaluations_per_fit(run, "fixed")
    if not per_fit:
        return None
    least, _ = work.least_seconds(work.fixed_effect_evaluation(config, run["rows"]), run["peaks"])
    return per_fit * trace["units"], least


def entry_stream_seconds(run):
    """Device self time of the operations that hold the training shard's entry
    planes, or that a kernel named for them; None where none does."""
    trace = run["trace"]
    entries = run["rows"] * sparse_shard(run["config"])["nnz_per_row"]

    def carries(name):
        sizes = (math.prod(map(int, dims.split(","))) for dims in SHAPE.findall(trace["op_line"][name]))
        return name.startswith(KERNEL_PREFIX) or any(size >= entries for size in sizes)

    seconds = [s for name, s in trace["op_self_s"].items() if carries(name)]
    return sum(seconds) if seconds else None


def read(run):
    counted = traced_evaluations(run)
    if counted is None:
        return None
    seconds = entry_stream_seconds(run)
    if not seconds:
        return None
    evaluations, least = counted
    return 100.0 * evaluations * least / seconds

"""How the trace names the objective kernels, and their events' sums.

The Pallas calls carry no `name=` yet, so the trace names each custom call by
the jitted function that holds it: `value_gradient_sums[.n]` (ops/pallas_glm)
and `fused_value_gradient_sums[.n]` (ops/pallas_sparse). The sparse function
holds three calls an evaluation (level-2 matvec, the fused level-1 kernel,
level-2 rmatvec; XLA drops the last where the gradient is not used), all under
that one name. Device time is summed over all of them; an EVALUATION is counted
once, at the call whose result begins with the `f32[1,2]` pair (value, sum of
u) that only the value+gradient kernel returns.
"""

import re

from .. import work

FIXED_EFFECT_KERNEL = {
    "dense": re.compile(r"^value_gradient_sums(\.\d+)?$"),
    "sparse": re.compile(r"^fused_value_gradient_sums(\.\d+)?$"),
}
EVALUATION = re.compile(r"^%?[\w.]+ = \(f32\[1,2\]")


def fixed_effect_kernel(run, kind):
    """(evaluations, device seconds, least seconds an evaluation, which peak
    binds) of the fixed-effect objective kernel in the traced fits, or None where the
    configuration's fixed-effect shard is not of this kind or the trace holds
    no such event."""
    trace, config = run["trace"], run["config"]
    coordinate = next(c for c in config["coordinates"] if c["kind"] == "fixed")
    if not trace or config["shards"][coordinate["shard"]]["kind"] != kind:
        return None
    names = [n for n in trace["op_self_s"] if FIXED_EFFECT_KERNEL[kind].match(n)]
    calls = sum(trace["op_count"][n] for n in names if EVALUATION.match(trace["op_line"][n]))
    seconds = sum(trace["op_self_s"][n] for n in names)
    if not calls or seconds <= 0.0:
        return None
    least, binds = work.least_seconds(work.fixed_effect_evaluation(config, run["rows"]), run["peaks"])
    return calls, seconds, least, binds

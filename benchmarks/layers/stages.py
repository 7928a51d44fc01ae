"""What the program itself recorded over the window, from its process-wide
registry `telemetry.METRICS`: the seconds of its fit stages (histogram
`fit_stage_s{stage=<name>}`, one observation a fit) and the objective
evaluations its optimizers counted (counter
`objective_evaluations{coordinate=<id>,kind=fixed|random}`).

`run` holds no registry, and need not: the process made one fit in set-up
(the warm fit, whose numbers are `run["warm_fit_timing"]`) and then the
window's, so the window's total is the process total less the warm fit's.
A program that records neither (an earlier commit) reads None.
"""


def window_stage_seconds(run, stages):
    """Seconds the window's fits spent in `stages`, or None."""
    from photon_ml_tpu.utils import telemetry

    recorded = telemetry.METRICS.labeled_histograms("fit_stage_s")
    warm = (run["warm_fit_timing"] or {}).get("stages_s")
    if not recorded or warm is None:
        return None
    total = 0.0
    for stage in stages:
        if f"stage={stage}" not in recorded:
            return None
        total += recorded[f"stage={stage}"]["sum"] - warm[stage]
    return total


def share_of_window(run, stages):
    seconds = window_stage_seconds(run, stages)
    return None if seconds is None else 100.0 * seconds / run["window_s"]


def window_evaluations_per_fit(run, kind):
    """Objective evaluations of `kind` ("fixed" | "random") coordinates in
    the window, over its fits; None where the program counted none."""
    from photon_ml_tpu.utils import telemetry

    counted = telemetry.METRICS.labeled_counters("objective_evaluations")
    warm = (run["warm_fit_timing"] or {}).get("fn_evals")
    cids = [c for c, k in run["kinds"].items() if k == kind]
    keys = [f"coordinate={c},kind={kind}" for c in cids]
    if warm is None or not run["records"] or not any(k in counted for k in keys):
        return None
    total = sum(counted.get(k, 0) for k in keys) - sum(warm.get(c, 0) for c in cids)
    return total / len(run["records"])

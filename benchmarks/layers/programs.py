"""What the program itself recorded of making its programs ready, from its
process-wide registry `telemetry.METRICS`: self seconds of every trace,
lowering and backend step JAX reported (histogram
`program_ready_s{phase=trace|lower|cache_read|compile,stage=<name>}`) and the
programs that went to the backend (counters `compile_cache_requests` /
`compile_cache_hits` `{stage=<name>}`). `stage` is the program's innermost
open stage when JAX reported, or `none`: the benchmark's generator and
reference, and whatever else runs outside every stage of the program.

`run` holds no registry, and need not: the readers are called in the run's
own process. A program that keeps no such record (an earlier commit) reads
None. The first reading prints the table by stage on standard error, with
the `none` row, and the costliest programs by name where the program keeps
them.
"""

import sys

PHASES = ("trace", "lower", "cache_read", "compile")
_printed = False


def by_stage():
    """{stage: {"requests", "hits", <phase>: seconds}}, or None."""
    from photon_ml_tpu.utils import telemetry

    seconds = telemetry.METRICS.labeled_histograms("program_ready_s")
    if not seconds:
        return None
    requests = telemetry.METRICS.labeled_counters("compile_cache_requests")
    hits = telemetry.METRICS.labeled_counters("compile_cache_hits")
    table = {}

    def labels(key):  # "phase=trace,stage=cd/train"
        return dict(pair.split("=", 1) for pair in key.split(","))

    def row(stage):
        return table.setdefault(stage, {"requests": 0, "hits": 0, **dict.fromkeys(PHASES, 0.0)})

    for key, snapshot in seconds.items():
        row(labels(key)["stage"])[labels(key)["phase"]] += snapshot["sum"]
    for name, counted in (("requests", requests), ("hits", hits)):
        for key, n in counted.items():
            row(labels(key)["stage"])[name] += n
    _print_once(table)
    return table


def staged(column):
    """`column` summed over the stages of the program (every stage but
    `none`), or None; `column` is a function of one row."""
    table = by_stage()
    if table is None:
        return None
    return sum(column(row) for stage, row in table.items() if stage != "none")


def _print_once(table):
    global _printed
    if _printed:
        return
    _printed = True
    width = max(len(stage) for stage in table)
    print(f"programs {'stage'.ljust(width)} requests hits " + " ".join(p.rjust(10) for p in PHASES),
          file=sys.stderr)
    for stage, row in sorted(table.items(), key=lambda kv: -sum(kv[1][p] for p in PHASES)):
        cells = " ".join(f"{row[p]:10.4f}" for p in PHASES)
        print(f"programs {stage.ljust(width)} {row['requests']:8d} {row['hits']:4d} {cells}", file=sys.stderr)
    from photon_ml_tpu.utils import compile_cache

    records = compile_cache.programs()
    for r in sorted(records, key=lambda r: -sum(r[p] for p in PHASES))[:5]:
        cells = " ".join(f"{p}={r[p]:.4f}" for p in PHASES)
        print(f"programs costliest {r['program']} stage={r['stage']} hit={r['hit']} {cells}", file=sys.stderr)

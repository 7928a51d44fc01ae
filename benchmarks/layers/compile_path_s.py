"""Seconds the program spent making its programs ready: `program_ready_s`
summed over every phase (trace, lower, cache read, compile; self time, so
nested traces count once) and over the stages of the program, `none` left
out. The whole process: the window adds nothing while nothing retraces."""

from .programs import PHASES, staged


def read(run):
    return staged(lambda row: sum(row[p] for p in PHASES))

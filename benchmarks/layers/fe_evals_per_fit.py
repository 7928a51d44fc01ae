"""Objective evaluations a fit's fixed-effect solves made, as the optimizer
counted them (`OptResult.fn_evals`: line-search trials included): the
window's `objective_evaluations{kind=fixed}` over the window's fits."""

from .stages import window_evaluations_per_fit


def read(run):
    return window_evaluations_per_fit(run, "fixed")

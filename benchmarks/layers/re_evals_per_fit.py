"""Objective evaluations a fit's random-effect solves made, summed over
their entities: the window's `objective_evaluations{kind=random}` over the
window's fits. Kept for the GLMix cell, beside `re_update_share_pct`."""

from .stages import window_evaluations_per_fit


def read(run):
    return window_evaluations_per_fit(run, "random")

"""Share of the window spent in `GameEstimator._fit` outside coordinate
descent and evaluation: the program's stages `fit/revalidate` (prepare on a
prepared estimator, config checks, the validation suite, scoring specs) +
`fit/validation_prep` + `fit/coordinates` + `fit/publish` over the window's
seconds."""

from .stages import share_of_window

STAGES = ("fit/revalidate", "fit/validation_prep", "fit/coordinates", "fit/publish")


def read(run):
    return share_of_window(run, STAGES)

"""Share of the window spent scoring and evaluating the validation set: the
program's stages `cd/validation_score` + `cd/validation_evaluate` (inside
coordinate descent, after each accepted update) + `fit/final_evaluate`
(`transformer.evaluate`, after it) over the window's seconds."""

from .stages import share_of_window

STAGES = ("cd/validation_score", "cd/validation_evaluate", "fit/final_evaluate")


def read(run):
    return share_of_window(run, STAGES)

"""Programs that went to the backend, read back or compiled, under the
stages of the program: `compile_cache_requests{stage}`, `none` left out."""

from .programs import staged


def read(run):
    return staged(lambda row: row["requests"])

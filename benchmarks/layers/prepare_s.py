"""Wall of `est.prepare(train)` in set-up (host clock)."""


def read(run):
    return run["prepare_s"]

"""Programs compiled (cache requests less cache hits) by the end of set-up; 0 on a warm checkout."""


def read(run):
    return run["compiled_in_setup"]

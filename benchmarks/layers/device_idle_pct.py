"""1 - union of device-operation intervals over the traced fits (device trace)."""


def read(run):
    trace = run["trace"]
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"]) if trace else None

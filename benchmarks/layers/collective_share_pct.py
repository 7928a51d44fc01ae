"""Share of the device's busy time spent in collective operations: their self
time in the traced fits over the busy time, a mean over the chips (device
trace). None where the trace holds no collective (one chip)."""

from . import collectives


def read(run):
    trace = run["trace"]
    found = collectives.names(trace) if trace else []
    if not found or not trace["busy_s"]:
        return None
    return 100.0 * sum(trace["op_self_s"][name] for name in found) / trace["busy_s"]

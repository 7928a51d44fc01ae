"""Share of the window spent in fixed-effect coordinate updates: the sum of
`CoordinateUpdateEvent.seconds` of fixed coordinates in the window's fits over
the window's seconds."""


def share(run, kind):
    seconds = [s for fit, cid, s in run["events"] if fit >= 0 and run["kinds"][cid] == kind]
    return 100.0 * sum(seconds) / run["window_s"] if seconds else None


def read(run):
    return share(run, "fixed")

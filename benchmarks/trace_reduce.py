"""From a profiler trace (.xplane.pb) to busy time, per-operation time and idle gaps.

`load` turns the file into plain lists; `reduce` works on those lists alone, so
the arithmetic is tested on a small hand-checked trace without a profiler.

Device planes are the planes whose name starts with `/device:TPU:`; their
`XLA Ops` line holds one event for each operation that ran, nested where an
operation (a `while`, a fusion's parent) contains others. Host spans are the
`fit:<i>` annotations the driver writes round each unit of work and the
`update_end:<coordinate>` marks it writes where a coordinate update ended.
"""

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
UNIT_PREFIX = "fit:"
MARK_PREFIX = "update_end:"


def find_trace(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str) -> list:
    """[{name, lines: [{name, events: [(name, start_ns, duration_ns)]}]}]"""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def short(name: str) -> str:
    """`%fusion.3 = f32[...] fusion(...)` -> `fusion.3`: the trace names a
    device operation by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: list) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def self_times(events: list) -> list:
    """[(name, self_ns)]: each event's duration less what its children cover.
    Events of one line nest by containment."""
    out = []
    stack = []  # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


def host_spans(planes: list) -> tuple:
    """(units, marks): units are (name, start, end) of `fit:<i>` spans, marks
    are (coordinate, time) of update ends, both sorted by time."""
    units, marks = [], []
    for plane in planes:
        if plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(UNIT_PREFIX):
                    units.append((name, start, start + dur))
                elif name.startswith(MARK_PREFIX):
                    marks.append((name[len(MARK_PREFIX):], start))
    return sorted(units, key=lambda u: u[1]), sorted(marks, key=lambda m: m[1])


def host_label(t: float, units: list, marks: list) -> str:
    """What the host was doing at time t, by the driver's spans."""
    for _, lo, hi in units:
        if lo <= t < hi:
            for coordinate, at in marks:
                if lo <= at < hi and t < at:
                    return f"fit, up to end of update {coordinate}"
            return "fit, after the last update (validation, evaluation)"
    return "between fits"


def reduce(planes: list, n_units=None) -> dict:
    """Busy union, per-operation self time and idle gaps over the traced
    units (the first `n_units` `fit:<i>` spans, or all of them)."""
    units, marks = host_spans(planes)
    if not units:
        raise ValueError("the trace holds no fit:<i> span")
    units = units[: n_units or len(units)]
    w_lo, w_hi = units[0][1], units[-1][2]
    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE_PREFIX)]
    busy_ns, op_self, op_count, idle, op_line = 0.0, {}, {}, {}, {}
    gaps = []
    n_dev = 0
    for plane in devices:
        ops = [l for l in plane["lines"] if l["name"] == OPS_LINE]
        if not ops:
            continue
        n_dev += 1
        events = []
        for name, start, dur in ops[0]["events"]:
            lo, hi = max(start, w_lo), min(start + dur, w_hi)
            if hi > lo:
                events.append((short(name), lo, hi - lo))
                op_line.setdefault(short(name), name[:400])
        covered = union([(s, s + d) for _, s, d in events])
        busy_ns += sum(hi - lo for lo, hi in covered)
        for name, ns in self_times(events):
            op_self[name] = op_self.get(name, 0.0) + ns
            op_count[name] = op_count.get(name, 0) + 1
        edges = [w_lo] + [t for iv in covered for t in iv] + [w_hi]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi > lo:
                label = host_label((lo + hi) / 2.0, units, marks)
                idle[label] = idle.get(label, 0.0) + (hi - lo)
                gaps.append((hi - lo, label))
    if not n_dev:
        raise ValueError(f"no device plane with an '{OPS_LINE}' line in the trace")
    return {
        "devices": n_dev,
        "units": len(units),
        "window_s": (w_hi - w_lo) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "op_self_s": {k: v / n_dev / 1e9 for k, v in op_self.items()},
        "op_count": {k: v / n_dev for k, v in op_count.items()},
        "op_line": op_line,  # the start of each operation's whole HLO line: its result shapes
        "idle_by_label_s": {k: v / n_dev / 1e9 for k, v in idle.items()},
        "longest_gap_s": max(gaps)[0] / 1e9 if gaps else 0.0,
    }


def breakdown(reduced: dict) -> dict:
    """The ten device operations with most self time, each with the start of
    its HLO line (the result's shape says whose work a `fusion.23` is), and
    the idle time by what the host was doing."""
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    said = lambda k: reduced["op_line"][k].split(" = ", 1)[-1][:100]
    ops = [[k if said(k) == k else f"{k} = {said(k)}", v] for k, v in top(reduced["op_self_s"])]
    return {"device_ops": ops, "idle_gaps": top(reduced["idle_by_label_s"])}

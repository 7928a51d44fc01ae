"""photon-obs: inspect the telemetry artifacts a run persists (ISSUE 11).

Three subcommands over the three file artifacts of utils/telemetry.py:

  * `trace <trace.json>` — summarize a Chrome trace-event export (span
    count, per-thread tracks, wall coverage). `--min-coverage P` exits
    nonzero when the span union covers less than P% of the traced wall —
    the acceptance gate for "spans cover the run".
  * `journal <journal.jsonl>` — event counts by type; `--validate`
    re-checks every line against its contracts.JOURNAL_EVENT_SCHEMAS
    schema and exits nonzero on any invalid line.
  * `profile <profile.json>` — pretty-print a run profile read through
    the loud `read_profile` contract (stage table, dispatch decisions,
    topology, roofline, and the programs the process made ready: by
    stage, hits, seconds by phase, every compiled one by name).
  * `decisions <journal.jsonl>` — the control-plane timeline (ISSUE 19):
    every `plan_decision`, `autopilot_decision`, and `shadow_verdict`
    event in emit order, with the evidence each decision carried and
    its outcome, plus the autopilot's rollback/quarantine annotations.
    Exits nonzero when ANY journal line is schema-invalid — an operator
    auditing the controller must not read a corrupt journal as clean.
  * `profile diff <a> <b>` — typed key-wise comparison of two run
    profiles: per-stage wall deltas, dispatch-decision changes,
    plan-block decision changes (added/removed/value- or source-
    changed), topology changes, and the programs each run made ready
    by stage. The operator tool for "what did the
    planner change between rounds". Exits nonzero when either profile
    violates its contract (read_profile refusal) or the kinds differ.

Load the trace itself in Perfetto (https://ui.perfetto.dev) or
chrome://tracing; this CLI is the headless companion.

Usage: python -m photon_ml_tpu.cli.obs --help
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional, Tuple

from photon_ml_tpu.utils import telemetry


def _interval_union_us(spans: List[Tuple[float, float]]) -> float:
    """Total microseconds covered by the union of [start, end) intervals."""
    total = 0.0
    end = None
    for s, e in sorted(spans):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def cmd_trace(args) -> int:
    with open(args.path) as f:
        doc = json.load(f)
    events = [e for e in doc.get("traceEvents", []) if e.get("ph") == "X"]
    threads = {
        e["tid"]: e["args"]["name"]
        for e in doc.get("traceEvents", [])
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    if not events:
        print("no spans recorded (was PHOTON_TRACE=1 set?)")
        return 1
    intervals = [(e["ts"], e["ts"] + e.get("dur", 0.0)) for e in events]
    t0 = min(s for s, _ in intervals)
    t1 = max(e for _, e in intervals)
    wall_us = max(t1 - t0, 1e-9)
    covered = _interval_union_us(intervals)
    coverage = 100.0 * covered / wall_us
    by_thread: dict = {}
    for e in events:
        by_thread.setdefault(e["tid"], []).append(e)
    print(f"trace: {len(events)} span(s), {len(by_thread)} thread track(s), "
          f"{wall_us / 1e6:.3f}s traced wall")
    print(f"span coverage of traced wall: {coverage:.1f}%")
    for tid, evs in sorted(by_thread.items(), key=lambda kv: -len(kv[1])):
        name = threads.get(tid, str(tid))
        top = max(evs, key=lambda e: e.get("dur", 0.0))
        print(
            f"  {name:32s} {len(evs):6d} span(s)  "
            f"longest: {top['name']} ({top.get('dur', 0.0) / 1e3:.1f} ms)"
        )
    span_ids = {e["args"].get("span_id") for e in events}
    orphans = [
        e
        for e in events
        if e["args"].get("parent_id") is not None
        and e["args"]["parent_id"] not in span_ids
    ]
    if orphans:
        print(f"WARNING: {len(orphans)} span(s) reference a missing parent")
    if args.min_coverage is not None and coverage < args.min_coverage:
        print(
            f"FAIL: coverage {coverage:.1f}% < required {args.min_coverage}%"
        )
        return 1
    return 0


def cmd_journal(args) -> int:
    n_ok, errors = telemetry.validate_journal(args.path)
    counts: dict = {}
    with open(args.path) as f:
        for raw in f:
            raw = raw.strip()
            if not raw:
                continue
            try:
                etype = json.loads(raw).get("type")
            except ValueError:
                etype = "<unparseable>"
            counts[etype] = counts.get(etype, 0) + 1
    total = sum(counts.values())
    print(f"journal: {total} line(s), {n_ok} valid, {len(errors)} invalid")
    for etype in sorted(counts, key=counts.get, reverse=True):
        print(f"  {etype:24s} {counts[etype]}")
    for err in errors[:20]:
        print(f"  INVALID: {err}")
    if args.validate and errors:
        return 1
    return 0


# Event types rendered as first-class timeline rows; the autopilot's
# rollback/quarantine events ride along as indented annotations so the
# operator sees WHY a rule went quiet right under the decision stream.
_DECISION_TYPES = (
    "plan_decision",
    "autopilot_decision",
    "shadow_verdict",
    # Precision-ladder transitions (ISSUE 20): every quantize/restore
    # step is a first-class, auditable control-plane decision.
    "tier_demote",
    "tier_restore",
)
_ANNOTATION_TYPES = ("autopilot_rollback", "rule_quarantined")


def _fmt_evidence(ev) -> str:
    if not ev:
        return ""
    if isinstance(ev, dict):
        parts = []
        for k in sorted(ev):
            v = ev[k]
            if isinstance(v, float):
                parts.append(f"{k}={v:.4g}")
            else:
                parts.append(f"{k}={json.dumps(v, default=str)}")
        return " ".join(parts)
    return json.dumps(ev, default=str)


def cmd_decisions(args) -> int:
    n_ok, errors = telemetry.validate_journal(args.path)
    rows: List[dict] = []
    with open(args.path) as f:
        for raw in f:
            raw = raw.strip()
            if not raw:
                continue
            try:
                doc = json.loads(raw)
            except ValueError:
                continue  # already reported by validate_journal
            if doc.get("type") in _DECISION_TYPES + _ANNOTATION_TYPES:
                rows.append(doc)
    counts: dict = {}
    for doc in rows:
        counts[doc["type"]] = counts.get(doc["type"], 0) + 1
    print(
        f"decisions: {len(rows)} control-plane event(s) "
        f"({', '.join(f'{counts[t]} {t}' for t in sorted(counts)) or 'none'})"
    )
    t0 = rows[0].get("ts", 0.0) if rows else 0.0
    for doc in rows:
        try:
            dt = float(doc.get("ts", t0)) - float(t0)
        except (TypeError, ValueError):
            dt = 0.0
        etype = doc["type"]
        if etype == "plan_decision":
            line = (
                f"plan      {doc.get('decision')} = "
                f"{json.dumps(doc.get('value'), default=str)} "
                f"[{doc.get('source')}] "
                f"(fallback {json.dumps(doc.get('fallback'), default=str)})"
            )
        elif etype == "autopilot_decision":
            action = doc.get("action") or {}
            what = (
                f"{action.get('kind')}"
                + (f" tenant={action.get('tenant')}" if action.get("tenant") else "")
                if isinstance(action, dict)
                else "(no action)"
            )
            line = (
                f"autopilot {doc.get('rule')}: {what} -> {doc.get('outcome')}"
            )
            ev = _fmt_evidence(doc.get("evidence"))
            if ev:
                line += f"  | {ev}"
        elif etype == "shadow_verdict":
            line = (
                f"shadow    {doc.get('challenger')} vs "
                f"{doc.get('champion')}: {doc.get('decision')} "
                f"after {doc.get('windows')} window(s) "
                f"({doc.get('evaluator')}: "
                f"{doc.get('challenger_metric')} vs "
                f"{doc.get('champion_metric')}) — {doc.get('reason')}"
            )
        elif etype in ("tier_demote", "tier_restore"):
            arrow = "v" if etype == "tier_demote" else "^"
            bytes_key = (
                "freed_bytes" if etype == "tier_demote" else "repinned_bytes"
            )
            line = (
                f"tier {arrow}    tenant={doc.get('tenant')} "
                f"{doc.get('from_tier')} -> {doc.get('to_tier')} "
                f"[{doc.get('reason')}] "
                f"({bytes_key}={doc.get(bytes_key)})"
            )
            ev = _fmt_evidence(doc.get("evidence"))
            if ev:
                line += f"  | {ev}"
        elif etype == "autopilot_rollback":
            action = doc.get("action") or {}
            kind = action.get("kind") if isinstance(action, dict) else action
            line = (
                f"  ROLLBACK  {doc.get('rule')} ({kind}): "
                f"{doc.get('reason')}"
            )
        else:  # rule_quarantined
            line = (
                f"  QUARANTINE {doc.get('rule')} after "
                f"{doc.get('rollbacks')} rollback(s): {doc.get('reason')}"
            )
        print(f"  +{dt:9.3f}s  {line}")
    if errors:
        print(f"{len(errors)} schema-invalid journal line(s):")
        for err in errors[:20]:
            print(f"  INVALID: {err}")
        return 1
    return 0


def cmd_profile(args) -> int:
    profile = telemetry.read_profile(args.path)  # loud missing-key contract
    topo = profile["device_topology"]
    print(
        f"{profile['kind']} profile: {profile['wall_s']}s wall on "
        f"{topo['device_count']}x {topo['platform']} "
        f"({topo.get('device_kind', '?')})"
    )
    roof = profile["roofline"].get("hbm_gb_per_s")
    if roof:
        print(f"  HBM roofline: {roof} GB/s")
    print("  stages:")
    stages = profile["stages"]
    width = max((len(k) for k in stages), default=0)
    for k in sorted(stages, key=lambda k: -float(stages[k] or 0)):
        print(f"    {k.ljust(width)}  {float(stages[k]):10.3f}s")
    print("  dispatch decisions:")
    for k, v in sorted(profile["dispatch"].items()):
        print(f"    {k}: {json.dumps(v, default=str)}")
    shapes = profile["bucket_shapes"]
    if shapes:
        print("  bucket shapes:")
        for k, v in sorted(shapes.items()):
            print(f"    {k}: {json.dumps(v)[:120]}")
    counters = (profile.get("metrics") or {}).get("counters") or {}
    nonzero = {k: v for k, v in counters.items() if v}
    print(f"  nonzero counters: {json.dumps(nonzero) if nonzero else '(none)'}")
    _print_programs(profile.get("programs") or {})
    return 0


def _stage_seconds(stage: dict) -> float:
    return sum(float(v) for v in stage["seconds"].values())


def _print_programs(block: dict) -> None:
    """The `programs` block (utils/compile_cache.summary): what the
    process made ready, by the stage that asked, and every miss by name."""
    stages = block.get("stages") or {}
    if not stages:
        return
    phases = list(next(iter(stages.values()))["seconds"])
    width = max(len(k) for k in stages)
    print("  programs made ready, by stage (seconds by phase):")
    print(f"    {'stage'.ljust(width)}  programs  hits  " + "  ".join(p.rjust(10) for p in phases))
    for name in sorted(stages, key=lambda k: -_stage_seconds(stages[k])):
        st = stages[name]
        cells = "  ".join(f"{float(st['seconds'][p]):10.3f}" for p in phases)
        print(f"    {name.ljust(width)}  {st['programs']:8d}  {st['hits']:4d}  {cells}")
    for miss in block.get("misses") or []:
        cells = ", ".join(f"{p} {float(miss[p]):.3f}s" for p in phases if miss.get(p))
        print(f"    compiled: {miss['program']} under {miss['stage']} ({cells})")


def _plan_decisions(profile: dict) -> dict:
    """decision name -> (value, source) from a profile's plan block;
    empty for unplanned / pre-planner (r06-era) profiles."""
    block = profile.get("plan") or {}
    return {
        d["decision"]: (d.get("value"), d.get("source"))
        for d in block.get("decisions", [])
        if isinstance(d, dict) and "decision" in d
    }


def cmd_profile_diff(path_a: str, path_b: str) -> int:
    """Typed key-wise diff of two run profiles (see module doc). Returns
    nonzero on contract violations — a profile that cannot be read
    loudly must fail the operator's comparison, not silently skip."""
    try:
        a = telemetry.read_profile(path_a)
        b = telemetry.read_profile(path_b)
    except (ValueError, OSError) as exc:
        print(f"CONTRACT VIOLATION: {exc}")
        return 1
    if a.get("kind") != b.get("kind"):
        print(
            f"CONTRACT VIOLATION: profile kinds differ "
            f"({a.get('kind')!r} vs {b.get('kind')!r}) — comparing a fit "
            "profile to a serve profile is not a round-over-round diff"
        )
        return 1
    print(
        f"{a['kind']} profiles: {path_a} ({a['wall_s']}s) vs "
        f"{path_b} ({b['wall_s']}s)"
    )

    # -- topology (a mismatch here means the diff crosses hardware)
    topo_a, topo_b = a["device_topology"], b["device_topology"]
    topo_changed = {
        k: (topo_a.get(k), topo_b.get(k))
        for k in sorted({*topo_a, *topo_b})
        if topo_a.get(k) != topo_b.get(k)
    }
    if topo_changed:
        print("  topology changes:")
        for k, (va, vb) in topo_changed.items():
            print(f"    {k}: {va!r} -> {vb!r}")

    # -- stage walls (typed: every key of either side, delta annotated)
    st_a, st_b = a["stages"], b["stages"]
    keys = sorted({*st_a, *st_b})
    width = max((len(k) for k in keys), default=0)
    print("  stage deltas (a -> b):")
    for k in keys:
        va = float(st_a.get(k) or 0.0)
        vb = float(st_b.get(k) or 0.0)
        mark = "" if abs(vb - va) < 1e-4 else f"  ({vb - va:+.3f}s)"
        print(f"    {k.ljust(width)}  {va:10.3f}s -> {vb:10.3f}s{mark}")

    # -- dispatch decisions (the runtime choices each run took)
    d_a, d_b = a["dispatch"], b["dispatch"]
    changed = [
        k for k in sorted({*d_a, *d_b}) if d_a.get(k) != d_b.get(k)
    ]
    if changed:
        print("  dispatch-decision changes:")
        for k in changed:
            print(
                f"    {k}: {json.dumps(d_a.get(k), default=str)} -> "
                f"{json.dumps(d_b.get(k), default=str)}"
            )
    else:
        print("  dispatch decisions: identical")

    # -- plan blocks (what the planner chose, round over round)
    plan_a, plan_b = _plan_decisions(a), _plan_decisions(b)
    added = sorted(set(plan_b) - set(plan_a))
    removed = sorted(set(plan_a) - set(plan_b))
    altered = sorted(
        k for k in set(plan_a) & set(plan_b) if plan_a[k] != plan_b[k]
    )
    if not (plan_a or plan_b):
        print("  plan blocks: none on either side (unplanned runs)")
    elif not (added or removed or altered):
        print(f"  plan decisions: identical ({len(plan_b)})")
    else:
        print("  plan-block changes:")
        for k in added:
            v, s = plan_b[k]
            print(f"    + {k} = {json.dumps(v, default=str)} [{s}]")
        for k in removed:
            v, s = plan_a[k]
            print(f"    - {k} (was {json.dumps(v, default=str)} [{s}])")
        for k in altered:
            va, sa = plan_a[k]
            vb, sb = plan_b[k]
            print(
                f"    ~ {k}: {json.dumps(va, default=str)} [{sa}] -> "
                f"{json.dumps(vb, default=str)} [{sb}]"
            )

    # -- programs made ready (what each run asked the backend for)
    pr_a = (a.get("programs") or {}).get("stages") or {}
    pr_b = (b.get("programs") or {}).get("stages") or {}
    if pr_a or pr_b:
        print("  programs made ready (a -> b: programs, hits, seconds):")
        absent = {"programs": 0, "hits": 0, "seconds": {}}
        for k in sorted({*pr_a, *pr_b}):
            sa, sb = pr_a.get(k, absent), pr_b.get(k, absent)
            print(
                f"    {k}: {sa['programs']} -> {sb['programs']} programs, "
                f"{sa['hits']} -> {sb['hits']} hits, "
                f"{_stage_seconds(sa):.3f}s -> {_stage_seconds(sb):.3f}s"
            )
        for side, block in (("a", a), ("b", b)):
            for miss in (block.get("programs") or {}).get("misses") or []:
                print(f"    compiled in {side}: {miss['program']} under {miss['stage']}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m photon_ml_tpu.cli.obs",
        description="Inspect photon-trace telemetry artifacts "
        "(trace.json / journal.jsonl / profile.json)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("trace", help="summarize a Chrome trace export")
    t.add_argument("path")
    t.add_argument(
        "--min-coverage",
        type=float,
        default=None,
        help="exit 1 when span union covers less than this %% of the "
        "traced wall",
    )
    j = sub.add_parser("journal", help="summarize/validate a run journal")
    j.add_argument("path")
    j.add_argument(
        "--validate",
        action="store_true",
        help="exit 1 when any line fails its schema",
    )
    d = sub.add_parser(
        "decisions",
        help="control-plane timeline: plan / autopilot / shadow decisions "
        "with evidence and outcome (exits 1 on schema-invalid lines)",
    )
    d.add_argument("path")
    pr = sub.add_parser(
        "profile",
        help="pretty-print a run profile, or `profile diff <a> <b>`",
    )
    pr.add_argument(
        "paths",
        nargs="+",
        metavar="ARG",
        help="<profile.json>  |  diff <a.json> <b.json>",
    )
    return p


def main(argv: Optional[List[str]] = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.cmd == "trace":
        return cmd_trace(args)
    if args.cmd == "journal":
        return cmd_journal(args)
    if args.cmd == "decisions":
        return cmd_decisions(args)
    if args.paths[0] == "diff":
        if len(args.paths) != 3:
            parser.error("profile diff takes exactly two profile paths")
        return cmd_profile_diff(args.paths[1], args.paths[2])
    if len(args.paths) != 1:
        parser.error("profile takes one path (or: profile diff <a> <b>)")
    args.path = args.paths[0]
    return cmd_profile(args)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))

"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Names no cell, configuration or metric: it loads `workloads/<cell>.json`, the
configuration, generator, driver and reference those name, and, traced, the
`layers/<metric>.py` reader of every per-layer metric that `BENCHMARK.json`
lists for the cell. The last line of standard output is the result.

`--rows N` is a rehearsal at a small size: it runs every step on whatever
device JAX has, and always ends `"correct": false`.
"""

import time

T_START = time.perf_counter()

import argparse
import importlib
import json
import os
import random
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def module(kind: str, name: str):
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def reported_in(metric: dict, cell: str, manifest: dict) -> bool:
    """Is this metric reported in this cell: in its `workloads`, or, without
    that key, wherever the end-to-end metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = next(m for m in manifest["end_to_end"] if m["name"] == metric["moves"])
        return reported_in(moved, cell, manifest)
    return True


def first_half(part: dict) -> dict:
    """The first half of a data part's rows, every array cut alike."""
    n = len(part["labels"]) // 2

    def cut(a):
        if isinstance(a, dict):  # a sparse shard: index and value planes, and its width
            return {k: (v[:n] if hasattr(v, "shape") else v) for k, v in a.items()}
        return a[:n]

    return {
        "shards": {k: cut(v) for k, v in part["shards"].items()},
        "labels": part["labels"][:n],
        "id_tags": {k: v[:n] for k, v in part["id_tags"].items()},
    }


def attach(chips: int, rehearsal: bool) -> dict:
    import jax

    devices = jax.devices()
    peaks = load_json(HERE, "peaks.json")
    kind = devices[0].device_kind
    if not rehearsal:
        if devices[0].platform != "tpu":
            raise SystemExit(f"run.py: no accelerator: JAX found {devices[0].platform}")
        if kind not in peaks:
            raise SystemExit(f"run.py: device kind {kind!r} is not in peaks.json")
        if len(devices) < chips:
            raise SystemExit(f"run.py: the cell needs {chips} chips, JAX found {len(devices)}")
    return {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "peaks": peaks.get(kind),
        "devices": devices,
    }


class Tracer:
    """Traces the first `units` units of the window, then stops."""

    def __init__(self, units: int):
        self.units = units
        self.directory = None
        self.active = False

    def on_unit(self, i: int) -> None:
        import jax

        if self.units and i == 0:
            self.directory = tempfile.mkdtemp(prefix="bench_trace_")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self.active = True
        elif self.active and i >= self.units:
            jax.profiler.stop_trace()
            self.active = False


def main(argv=None, *, chip_required=True, limits=None) -> int:
    """`chip_required=False` and `limits` are for the tests under tests/: they
    skip the look for a chip and judge a small run by limits of their own."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rows", type=int, default=None, help="rehearsal size; never correct")
    ap.add_argument("--control", default=None,
                    help="also read the reference at these storage types, or half_batch (for setting limits)")
    ap.add_argument("--dump", default=None, help="write the reduced trace and the run's facts here")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    manifest = load_json(ROOT, "BENCHMARK.json")
    workload = load_json(HERE, "workloads", f"{args.workload}.json")
    config = load_json(HERE, "configs", f"{workload['config']}.json")
    rehearsal = args.rows is not None

    import jax
    from photon_ml_tpu.utils import compile_cache, telemetry

    device = attach(workload["chips"], rehearsal or not chip_required)
    compile_cache.enable()

    def compiled() -> int:
        get = telemetry.METRICS.get_counter
        return int(get("compile_cache_requests") - get("compile_cache_hits"))

    driver = module("drivers", workload["driver"])
    problem = module("generators", config["generator"]["name"]).generate(config, args.seed, args.rows)
    state = driver.setup(config, workload, problem)
    compiled_in_setup = compiled()
    setup_s = time.perf_counter() - T_START

    tracer = Tracer(workload["traffic"]["trace_units"] if args.trace else 0)
    records, window_s = driver.window(state, args.seconds, tracer.on_unit)
    compiled_in_window = compiled() - compiled_in_setup

    stats = [d.memory_stats() or {} for d in device["devices"]]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    end_to_end = driver.end_to_end(state, records, window_s)
    end_to_end["setup_s"] = setup_s

    # The fits compared: a sample drawn from the seed, the last fit always in it.
    rng = random.Random(args.seed)
    n_compare = min(workload["traffic"]["compare_fits"], len(records))
    picked = sorted(set(rng.sample(range(len(records)), n_compare - 1)) | {len(records) - 1})
    fits = [
        {"coefficients": driver.outputs(state, records[i]), "metric": records[i]["metric"]}
        for i in picked
    ]
    run = {
        "config": config, "workload": workload, "rows": problem["rows"],
        "records": [{"seconds": r["seconds"]} for r in records], "window_s": window_s,
        "events": list(state.events), "kinds": dict(state.kinds),
        "prepare_s": state.prepare_s, "warm_fit_s": state.warm_fit_s,
        "warm_fit_timing": state.warm_fit_timing, "setup_s": setup_s,
        "compiled_in_setup": compiled_in_setup, "peaks": device["peaks"], "trace": None,
    }
    del records
    driver.release(state)

    compare = importlib.import_module("benchmarks.compare")
    reference = module("references", config["reference"]["name"])
    t_reference = time.perf_counter()
    solved = reference.solve(config, problem)
    reference_s = time.perf_counter() - t_reference
    values = compare.numbers(fits, solved)
    noted = compare.notes(fits, solved)
    values["compiled_in_window"] = compiled_in_window
    rows = compare.judge(values, config["limits"] if limits is None else limits)
    correct = all(r["ok"] for r in rows) and not (rehearsal and chip_required)
    for control in filter(None, (args.control or "").split(",")):
        # The reference in the program's place, one step down: a lower storage
        # type, or (`half_batch`) the second half of the rows left out.
        if control == "half_batch":
            planted = reference.solve(config, dict(problem, train=first_half(problem["train"])))
        else:
            planted = reference.solve(config, problem, storage=control)
        read = {**compare.notes([planted], solved), **compare.numbers([planted], solved)}
        for name, value in sorted(read.items()):
            print(f"control[{control}] {name} = {value!r}", file=sys.stderr)

    device_out = {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"],
        "memory_peak_bytes": memory_peak,
    }
    result = {"correct": correct, "attempted": len(run["records"]), "failed": 0}
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    if args.trace:
        trace_reduce = importlib.import_module("benchmarks.trace_reduce")
        planes = trace_reduce.load(trace_reduce.find_trace(tracer.directory))
        shutil.rmtree(tracer.directory, ignore_errors=True)
        try:
            run["trace"] = trace_reduce.reduce(planes, tracer.units)
        except ValueError:
            if not rehearsal:  # a rehearsal on the CPU has no device plane
                raise
        if run["trace"]:
            if args.dump:  # what planes and lines the trace holds, for a look by hand
                run["trace"]["lines"] = {
                    p["name"]: {l["name"]: len(l["events"]) for l in p["lines"]} for p in planes
                }
            device_out["busy_s"] = run["trace"]["busy_s"]
            device_out["window_s"] = run["trace"]["window_s"]
        metrics = {}
        for metric in manifest["per_layer"]:
            if reported_in(metric, args.workload, manifest):
                value = module("layers", metric["name"]).read(run)
                if value is not None:
                    metrics[metric["name"]] = value
        if run["trace"]:
            result["breakdown"] = trace_reduce.breakdown(run["trace"])
    else:
        metrics = {
            m["name"]: end_to_end[m["name"]]
            for m in manifest["end_to_end"]
            if reported_in(m, args.workload, manifest) and m["name"] in end_to_end
        }
    result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in metrics.items()}
    result["device"] = device_out
    # Beside the contract's keys: where this run's own time went (host clock).
    result["phases_s"] = {
        "setup": setup_s, "prepare": run["prepare_s"], "warm_fit": run["warm_fit_s"],
        "window": window_s, "reference": reference_s, "whole": time.perf_counter() - T_START,
    }
    result["noted"] = noted
    result["compared"] = {r["name"]: {"value": r["value"], "limit": r["limit"]} for r in rows}
    if args.dump:
        import numpy as np

        run["norms"] = {
            cid: [float(np.linalg.norm(fits[-1]["coefficients"][cid])), float(np.linalg.norm(ref))]
            for cid, ref in solved["coefficients"].items()
        }
        run["metric"] = [fits[-1]["metric"], solved["metric"]]
        run["phases_s"] = result["phases_s"]
        with open(args.dump, "w") as f:
            json.dump({k: v for k, v in run.items() if k not in ("config", "workload")}, f, indent=1)
    if rehearsal and chip_required:
        print("rehearsal (--rows): never correct", file=sys.stderr)
    for name, value in sorted(noted.items()):
        print(f"noted {name} = {value!r}", file=sys.stderr)
    for r in rows:
        print(f"compared {r['name']} = {r['value']!r} limit {r['limit']!r} ok={r['ok']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

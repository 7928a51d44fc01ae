"""Driver `refit`: a prepared estimator, `GameEstimator.fit` called back to back.

Set-up builds the data sets and ONE estimator, runs `prepare` and one untimed
warm fit; the window drives that same estimator: `est.fit(train, validation,
[cfg])` from a zero start, closed loop, one caller, until the first whole fit
at or after `--seconds`. Every fit ends with its coefficients ready on the
device and its validation metric on the host.

This is the only file of the benchmark that names the program's classes. It
reads the configuration's `coordinates` and builds what they say; it holds no
name of a configuration or a cell.
"""

import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np


class State:
    """What set-up hands to the window: the estimator, its data, the events."""

    def __init__(self):
        self.estimator = None
        self.train = None
        self.validation = None
        self.opt_configs = None
        self.rows = 0
        self.events = []  # (fit index or -1, coordinate, seconds)
        self.fit_index = -1  # -1 outside the window
        self.prepare_s = None
        self.warm_fit_s = None
        self.warm_fit_timing = None
        self.kinds = {}  # coordinate id -> "fixed" | "random"


def _dataset(part):
    from photon_ml_tpu.data.containers import SparseFeatures
    from photon_ml_tpu.data.game_dataset import GameDataset

    shards = {}
    for name, feats in part["shards"].items():
        if isinstance(feats, dict):
            shards[name] = SparseFeatures(
                jnp.asarray(feats["indices"]), jnp.asarray(feats["values"]), feats["dim"]
            )
        else:
            shards[name] = feats
    return GameDataset.build(shards, part["labels"], id_tags=part["id_tags"])


def _estimator(config, rows, emitter):
    from photon_ml_tpu.data.game_dataset import (
        FixedEffectDataConfig,
        RandomEffectDataConfig,
    )
    from photon_ml_tpu.estimators.game_estimator import GameEstimator
    from photon_ml_tpu.evaluation.suite import EvaluatorType
    from photon_ml_tpu.optimize.config import (
        CoordinateOptimizationConfig,
        OptimizerConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    data_configs, opt_configs = {}, {}
    for c in config["coordinates"]:
        if c["kind"] == "fixed":
            data_configs[c["id"]] = FixedEffectDataConfig(c["shard"])
        else:
            cap = next(
                t["active_upper_bound"]
                for t in c["active_upper_bound_by_rows"]
                if t["up_to_rows"] is None or rows <= t["up_to_rows"]
            )
            data_configs[c["id"]] = RandomEffectDataConfig(
                c["tag"], c["shard"], active_upper_bound=cap, min_bucket=c["min_bucket"]
            )
        o = c["optimizer"]
        opt_configs[c["id"]] = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType[o["type"]],
                max_iterations=o["max_iterations"],
                tolerance=o["tolerance"],
            ),
            regularization=RegularizationContext(RegularizationType[c["regularization"]]),
            reg_weight=c["reg_weight"],
        )
    est = GameEstimator(
        TaskType[config["task"]],
        data_configs,
        coordinate_descent_iterations=config["coordinate_descent_iterations"],
        validation_evaluators=[EvaluatorType.parse(e) for e in config["evaluators"]],
        event_emitter=emitter,
    )
    return est, opt_configs


def _fit(state):
    """One fit, finished: coefficients ready on the device, metric on the host."""
    result = state.estimator.fit(state.train, state.validation, [state.opt_configs])[0]
    leaves = []
    for cid in result.model.coordinate_ids:
        m = result.model[cid]
        leaves.append(
            m.coefficients.means if state.kinds[cid] == "fixed" else m.coefficients_matrix
        )
    jax.block_until_ready(leaves)
    return result.model, float(result.evaluation.primary_value)


def setup(config, workload, problem):
    from photon_ml_tpu.utils.observability import CoordinateUpdateEvent, EventEmitter

    state = State()
    state.rows = problem["rows"]
    state.kinds = {c["id"]: c["kind"] for c in config["coordinates"]}
    emitter = EventEmitter()

    def on_update(event):
        state.events.append((state.fit_index, event.coordinate, event.seconds))
        # A mark on the profiler's clock where this update ended.
        with jax.profiler.TraceAnnotation(f"update_end:{event.coordinate}"):
            pass

    emitter.register(on_update, CoordinateUpdateEvent)
    state.train = _dataset(problem["train"])
    state.validation = _dataset(problem["validation"])
    state.estimator, state.opt_configs = _estimator(config, state.rows, emitter)
    t0 = time.perf_counter()
    state.estimator.prepare(state.train)
    state.prepare_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _fit(state)
    state.warm_fit_s = time.perf_counter() - t0
    state.warm_fit_timing = dict(state.estimator.fit_timing)
    return state


def window(state, seconds, on_unit):
    """Fits back to back; returns one record a fit. `on_unit(i)` is called
    before fit i starts and lets the harness stop a trace between fits."""
    records = []
    t_open = time.perf_counter()
    while True:
        i = len(records)
        on_unit(i)
        state.fit_index = i
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"fit:{i}"):
            model, metric = _fit(state)
        t1 = time.perf_counter()
        records.append({"seconds": t1 - t0, "model": model, "metric": metric})
        if t1 - t_open >= seconds:
            break
    state.fit_index = -1
    on_unit(len(records))
    return records, time.perf_counter() - t_open


def end_to_end(state, records, window_s):
    walls = [r["seconds"] for r in records]
    out = {"train_rows_per_s": state.rows * len(records) / window_s}
    if len(walls) >= 20:
        out["fit_p90_s"] = statistics.quantiles(walls, n=10)[-1]
    return out


def outputs(state, record):
    """One fit's answer in a layout that owes nothing to the program's: per
    coordinate a (d,) vector, or for a random effect a (entity ids, d) matrix
    in the shard's own feature space with row = entity id."""
    specs = state.estimator.scoring_specs()
    out = {}
    for cid, kind in state.kinds.items():
        m = record["model"][cid]
        if kind == "fixed":
            out[cid] = np.asarray(m.coefficients.means, np.float32)
            continue
        matrix = np.asarray(m.coefficients_matrix, np.float32)
        spec = specs[cid]
        slots = np.asarray(spec.projector.slot_tables)
        keys = np.fromiter(spec.entity_index.keys(), np.int64, len(spec.entity_index))
        rows_of = np.fromiter(spec.entity_index.values(), np.int64, len(spec.entity_index))
        full = np.zeros((int(keys.max()) + 1, spec.projector.original_dim), np.float32)
        e, j = np.nonzero(slots[rows_of] >= 0)
        full[keys[e], slots[rows_of[e], j]] = matrix[rows_of[e], j]
        out[cid] = full
    return out


def release(state):
    """Drop the program's device state before the reference runs."""
    state.estimator = state.train = state.validation = None
    state.opt_configs = None

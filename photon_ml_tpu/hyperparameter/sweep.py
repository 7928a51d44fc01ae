"""Pod-parallel hyperparameter sweeps: the batched trial executor.

The GP/Sobol searchers (search.py) propose k-candidate qEI batches, but
until ISSUE 12 every candidate was evaluated one full training run at a
time — the serial loop the reference inherits from GameTrainingDriver
(GameTrainingDriver.scala:643-680). `SweepExecutor` is the
`BatchEvaluationFunction` that evaluates a (k, dim) candidate matrix of
regularization weights as parallel trials, three ways:

* **stacked** — the trial axis rides INSIDE one XLA dispatch: each trial's
  full coordinate-descent fit (the coordinates' `trial_train`/`trial_score`
  hooks — the same jitted solve recipes the serial loop dispatches) is
  `lax.scan`-sequenced over a leading trial axis of reg weights. Data is
  packed and uploaded once; k trials cost ONE dispatch, zero per-update
  host syncs, and zero per-trial Python — where the serial loop pays
  dispatch latency, a divergence-guard bool fetch, span/timing glue and a
  full validation round per coordinate update per trial. scan (not vmap)
  carries the trial axis deliberately: vmapping the solve changes the
  batched matmuls' reduction order and breaks the bitwise contract, while
  a scanned body executes the exact per-trial op sequence — stacked trials
  are BITWISE-equal to the serial per-trial loop (tests/test_sweep.py).
  The trial axis is HBM-charged (models + score vectors per trial); rounds
  that exceed PHOTON_SWEEP_MAX_STACK or the device budget split
  automatically (`stack_decisions` records every split).

* **shard_group** — for fits too big to stack: the device fleet partitions
  into trial groups (PHOTON_SWEEP_SHARD_GROUPS; one group per device by
  default) and each group runs ONE trial's serial fit concurrently —
  groups of >1 device run the PR 7 entity-sharded sweep inside the group
  ("Distributed Function Minimization in Apache Spark", PAPERS.md: N
  concurrent distributed optimizations). Dispatch is async per group, so
  device compute overlaps across trials. Single-device groups are
  bitwise-equal to the serial loop (same programs, same device kind);
  multi-device groups carry PR 7's sharded-training parity.

* **serial** — the reference loop itself (`run_coordinate_descent` per
  candidate): the parity anchor the other two modes are pinned against,
  and the fallback when neither engages.

Between searcher rounds the executor streams per-trial timing + values
back (`TrialRecord`), emits `trial_start`/`trial_finish` journal events,
and warm-starts each round's trials from the incumbent's coefficients
(Snap ML's hierarchical pipelining framing: proposal, stacked solves and
result streaming stay concurrent workstreams). `finalize()` re-fits the
winning config COLD so the returned winner model is bitwise-equal to a
standalone fit of that config regardless of warm starting
(tests/test_sweep.py::TestExecutorSurface).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Callable, Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.coordinate import RandomEffectCoordinate
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.game.model import (
    Coefficients,
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.knobs import _FALSE as _STACK_OFF
from photon_ml_tpu.utils.knobs import _TRUE as _STACK_ON
from photon_ml_tpu.utils.knobs import get_knob

logger = logging.getLogger(__name__)

Array = jax.Array

# Fraction of the device's reported bytes_limit the stacked trial axis may
# charge (the rest is data + solver working set, already resident).
_STACK_BUDGET_FRACTION = 0.25


@dataclasses.dataclass
class TrialRecord:
    """One evaluated trial (the executor's per-trial telemetry record —
    exported through contracts.SWEEP_TRIAL_KEYS)."""

    trial: int
    round: int
    mode: str
    seconds: float
    value: float
    diverged_steps: int
    point: np.ndarray  # parameter-space candidate (tuned_ids order)

    def timing_entry(self) -> Dict[str, object]:
        return {
            "trial": self.trial,
            "round": self.round,
            "mode": self.mode,
            "seconds": round(self.seconds, 4),
            "value": self.value,
            "diverged_steps": self.diverged_steps,
        }


@dataclasses.dataclass
class SweepResult:
    """finalize()'s summary: every trial, the winner, and the winner's
    COLD refit (bitwise-equal to a standalone fit of the winning config)."""

    trials: List[TrialRecord]
    best_trial: int
    best_point: np.ndarray
    best_value: float
    winner_model: GameModel
    winner_value: float
    winner_refit_s: float
    stack_decisions: List[Dict[str, object]]


class SweepExecutor:
    """Batched trial evaluation behind the `BatchEvaluationFunction` seam.

    `coordinates` is the ordered cid -> coordinate mapping of the MAIN
    (replicated) fit; `tuned_ids` names the coordinates whose reg weight
    the candidate columns drive (untuned coordinates keep
    `base_reg_weights`). `trial_scorers[cid](arrays)` maps a coordinate's
    model arrays to validation margins (traceable — the stacked program
    computes them in-dispatch); the trial VALUE is the validation suite's
    PRIMARY metric of (offsets + sum of margins), computed through ONE
    shared jitted metric program in every mode (`_value_program`) — so
    trial values, and hence searcher trajectories, are mode-invariant by
    construction. Construct through `GameEstimator.sweep_executor` (which
    wires prepared data, scorers, and the shard-group builder).
    """

    def __init__(
        self,
        coordinates: Mapping[str, object],
        tuned_ids: Sequence[str],
        num_iterations: int,
        *,
        task,
        base_reg_weights: Mapping[str, float],
        validation_suite,
        validation_offsets,
        num_validation_samples: int,
        trial_scorers: Mapping[str, Callable],
        maximize: bool = False,
        seed: int = 0,
        mode: Optional[str] = None,
        warm_start: bool = True,
        max_stack: Optional[int] = None,
        shard_groups: Optional[int] = None,
        group_builder: Optional[Callable] = None,
        on_event: Optional[Callable] = None,
    ):
        if mode not in (None, "stacked", "shard_group", "serial"):
            raise ValueError(f"unknown sweep mode {mode!r}")
        self.coordinates = dict(coordinates)
        self.ids = list(self.coordinates.keys())
        self.tuned_ids = list(tuned_ids)
        unknown = [c for c in self.tuned_ids if c not in self.coordinates]
        if unknown:
            raise ValueError(f"tuned_ids name unknown coordinates {unknown}")
        self.num_iterations = int(num_iterations)
        self.task = task
        self.base_reg_weights = dict(base_reg_weights)
        self.validation_suite = validation_suite
        self.validation_offsets = validation_offsets
        self.num_validation_samples = int(num_validation_samples)
        self.trial_scorers = dict(trial_scorers)
        self.maximize = bool(maximize)
        self.seed = int(seed)
        self.mode = mode
        self.warm_start = bool(warm_start)
        self.max_stack = max_stack
        self.shard_groups = shard_groups
        self.group_builder = group_builder
        self.on_event = on_event

        first = next(iter(self.coordinates.values()))
        self._num_samples = first.dataset.num_samples
        self._base_offsets = first.dataset.offsets
        self._dtype = first.dataset.labels.dtype

        self.trials: List[TrialRecord] = []
        self.stack_decisions: List[Dict[str, object]] = []
        self._round = 0
        # Incumbent: best (value, trial index, point, model arrays) so far.
        # Updated identically in every mode (trial order, strict improvement)
        # so warm-started rounds stay mode-parity-comparable.
        self._best: Optional[Dict[str, object]] = None
        self._programs: Dict = {}
        self._value_prog = None
        self._group_contexts: Optional[List[Dict[str, object]]] = None
        # Debug/parity handle: the most recent round's per-trial model
        # arrays, in candidate order (tests pin stacked == serial on it).
        self.last_trial_models: List[Dict[str, Dict[str, Array]]] = []

    @property
    def rounds(self) -> int:
        """Proposal rounds evaluated so far."""
        return self._round

    def reset(self) -> None:
        """Forget every evaluated trial but KEEP compiled programs and
        group contexts — the warm-up hook: compile the round
        programs on throwaway candidates, reset, then run the measured
        sweep against warm programs (the standard timed-not-equal-warm-up
        protocol)."""
        self.trials.clear()
        self.stack_decisions.clear()
        self.last_trial_models = []
        self._round = 0
        self._best = None

    # ------------------------------------------------------------ model glue

    def _is_re(self, cid: str) -> bool:
        return isinstance(self.coordinates[cid], RandomEffectCoordinate)

    def _re_rows(self, cid: str) -> int:
        return self.coordinates[cid].re_dataset.num_entities + 1

    def _want_var(self, cid: str) -> bool:
        from photon_ml_tpu.types import VarianceComputationType

        cfg = self.coordinates[cid].config
        return cfg.variance_computation != VarianceComputationType.NONE

    def _zero_arrays(self, cid: str) -> Dict[str, Optional[Array]]:
        coord = self.coordinates[cid]
        if self._is_re(cid):
            shape = (self._re_rows(cid), coord.dim)
            m = jnp.zeros(shape, self._dtype)
            v = jnp.zeros(shape, self._dtype) if self._want_var(cid) else None
            return {"m": m, "v": v}
        feats = coord._features
        dim = feats.dim if hasattr(feats, "dim") else feats.shape[-1]
        w = jnp.zeros((dim,), self._dtype)
        var = jnp.zeros((dim,), self._dtype) if self._want_var(cid) else None
        return {"w": w, "var": var}

    def _model_to_arrays(self, cid: str, model) -> Dict[str, Optional[Array]]:
        if self._is_re(cid):
            m = model.coefficients_matrix
            rows = self._re_rows(cid)
            if m.shape[0] > rows:  # mesh-padded group fit: logical rows only
                m = m[:rows]
            v = getattr(model, "variances_matrix", None)
            if v is not None and v.shape[0] > rows:
                v = v[:rows]
            return {"m": m, "v": v}
        return {
            "w": model.coefficients.means,
            "var": model.coefficients.variances,
        }

    def _arrays_to_model(self, cid: str, arrays: Mapping[str, Optional[Array]]):
        if self._is_re(cid):
            return RandomEffectModel(
                arrays["m"], arrays.get("v"), self.task,
                n_entities=self._re_rows(cid) - 1,
            )
        return FixedEffectModel(
            Coefficients(arrays["w"], arrays.get("var")), self.task
        )

    def _arrays_to_game_model(self, arrays_by_cid) -> GameModel:
        return GameModel(
            {c: self._arrays_to_model(c, a) for c, a in arrays_by_cid.items()}
        )

    # ------------------------------------------------------------- valuation

    def _value_program(self):
        """ONE jitted program for the primary validation metric — shared by
        every evaluation mode, so trial values are bitwise-identical across
        modes by construction (and a trial's valuation costs one dispatch,
        not the eager metric's dozens — the suite's full evaluate() is for
        reporting, not the inner search loop)."""
        prog = self._value_prog
        if prog is None:
            suite = self.validation_suite
            prog = jax.jit(suite.metric_fn(suite.primary))
            self._value_prog = prog
        return prog

    def _value_device(self, val_scores_row: Array) -> Array:
        """The trial value as a DEVICE scalar (fetch deferred — stacked
        rounds stack a whole chunk's values into one host round trip)."""
        suite = self.validation_suite
        return self._value_program()(val_scores_row, suite.labels, suite.weights)

    def _value_of(self, arrays_by_cid: Mapping[str, Mapping]) -> float:
        """Trial value = primary validation metric of the trial's final
        model. The margin-sum ORDER (offsets first, then update-sequence
        order) is the canonical one the stacked program replicates
        in-trace, so values agree bitwise across modes."""
        total = self.validation_offsets
        if total is None:
            total = jnp.zeros((self.num_validation_samples,), self._dtype)
        for cid in self.ids:
            total = total + self.trial_scorers[cid](arrays_by_cid[cid])
        return float(self._value_device(total))

    # ----------------------------------------------------------- mode choice

    def _stackable(self) -> bool:
        return all(
            getattr(c, "_entity_mesh", None) is None
            for c in self.coordinates.values()
        )

    def _choose_mode(self, k: int) -> str:
        if self.mode is not None:
            return self.mode
        knob = str(get_knob("PHOTON_SWEEP_TRIAL_STACK")).strip().lower()
        multi = len(jax.devices()) > 1 and self.group_builder is not None
        if knob in _STACK_ON:
            if not self._stackable():
                raise ValueError(
                    "PHOTON_SWEEP_TRIAL_STACK forces trial stacking, but a "
                    "coordinate's store is entity-sharded — stacked trials "
                    "need the replicated store (use shard groups)"
                )
            return "stacked"
        if knob in _STACK_OFF:
            return "shard_group" if multi else "serial"
        if self._stackable():
            return "stacked"
        return "shard_group" if multi else "serial"

    # --------------------------------------------------------- public driver

    def evaluate_point(self, point: np.ndarray) -> float:
        """Scalar `EvaluationFunction` adapter (single-candidate round)."""
        return self.evaluate_batch(np.atleast_2d(np.asarray(point)))[0]

    def evaluate_batch(self, points: np.ndarray) -> List[float]:
        """Evaluate a (k, dim) candidate matrix; returns k values in order.

        This IS the `BatchEvaluationFunction` the searchers call between
        proposal rounds; it records TrialRecords, emits trial journal
        events, and advances the warm-start incumbent.
        """
        points = np.atleast_2d(np.asarray(points, np.float64))
        k = points.shape[0]
        if points.shape[1] != len(self.tuned_ids):
            raise ValueError(
                f"candidate matrix has {points.shape[1]} columns for "
                f"{len(self.tuned_ids)} tuned coordinates"
            )
        mode = self._choose_mode(k)
        round_idx = self._round
        self._round += 1
        base_trial = len(self.trials)
        for i in range(k):
            self._emit("trial_start", round=round_idx, trial=base_trial + i,
                       mode=mode)
        warm = self._best["arrays"] if (self.warm_start and self._best) else None
        with telemetry.span(
            "sweep_round", round=round_idx, mode=mode, trials=k
        ):
            if mode == "stacked":
                out = self._evaluate_stacked(points, warm)
            elif mode == "shard_group":
                out = self._evaluate_shard_group(points, warm)
            else:
                out = self._evaluate_serial(points, warm)
        values, models, seconds, diverged = out
        self.last_trial_models = models
        records = []
        for i in range(k):
            rec = TrialRecord(
                trial=base_trial + i,
                round=round_idx,
                mode=mode,
                seconds=seconds[i],
                value=values[i],
                diverged_steps=diverged[i],
                point=points[i].copy(),
            )
            records.append(rec)
            self.trials.append(rec)
            self._update_incumbent(rec, models[i])
        for rec in records:
            self._emit(
                "trial_finish", round=rec.round, trial=rec.trial,
                mode=rec.mode, seconds=rec.seconds, value=rec.value,
                diverged_steps=rec.diverged_steps,
            )
        return values

    def finalize(self) -> SweepResult:
        """COLD refit of the winning config through the serial loop: the
        deliverable model is bitwise-equal to a standalone fit of the
        winning config (warm-started trial models are search artifacts)."""
        if self._best is None:
            raise ValueError("finalize() needs at least one evaluated trial")
        best = self._best
        t0 = time.perf_counter()
        cd = run_coordinate_descent(
            self.coordinates,
            self.num_iterations,
            reg_weights=self._rw_map(best["point"]),
            seed=self.seed,
        )
        arrays = {
            cid: self._trial_arrays(cid, cd.model) for cid in self.ids
        }
        winner_value = self._value_of(arrays)
        refit_s = time.perf_counter() - t0
        return SweepResult(
            trials=list(self.trials),
            best_trial=int(best["trial"]),
            best_point=np.asarray(best["point"]),
            best_value=float(best["value"]),
            winner_model=cd.model,
            winner_value=winner_value,
            winner_refit_s=refit_s,
            stack_decisions=list(self.stack_decisions),
        )

    # ---------------------------------------------------------------- shared

    def _emit(self, etype: str, **fields) -> None:
        telemetry.emit_event(etype, **fields)
        if self.on_event is not None:
            try:
                self.on_event(etype, **fields)
            except Exception:  # noqa: BLE001 - observer must not kill trials
                logger.warning("sweep on_event hook failed", exc_info=True)

    def _rw_map(self, point: np.ndarray) -> Dict[str, float]:
        rw = dict(self.base_reg_weights)
        for j, cid in enumerate(self.tuned_ids):
            rw[cid] = float(point[j])
        return rw

    def _rw_stack(self, points: np.ndarray) -> jnp.ndarray:
        """(k, n_coordinates) reg weights in update-sequence order."""
        k = points.shape[0]
        cols = []
        for cid in self.ids:
            if cid in self.tuned_ids:
                cols.append(points[:, self.tuned_ids.index(cid)])
            else:
                cols.append(np.full(k, self.base_reg_weights[cid]))
        return jnp.asarray(np.stack(cols, axis=1), self._dtype)

    def _update_incumbent(self, rec: TrialRecord, arrays) -> None:
        v = rec.value
        if not np.isfinite(v):
            return
        better = self._best is None or (
            v > self._best["value"] if self.maximize else v < self._best["value"]
        )
        if better:
            self._best = {
                "value": v,
                "trial": rec.trial,
                "point": rec.point,
                "arrays": arrays,
            }

    # ---------------------------------------------------------------- serial

    def _evaluate_serial(self, points, warm):
        """The reference's per-trial loop (`run_coordinate_descent` per
        candidate) — the parity anchor the batched modes are pinned
        against (the shard-group worker runs its own copy of this loop
        against group-local coordinates)."""
        coords = self.coordinates
        initial = (
            self._arrays_to_game_model(warm) if warm is not None else None
        )
        values, models, seconds, diverged = [], [], [], []
        for i in range(points.shape[0]):
            t0 = time.perf_counter()
            with telemetry.span("sweep_trial", index=i, mode="serial"):
                cd = run_coordinate_descent(
                    coords,
                    self.num_iterations,
                    initial_models=initial,
                    reg_weights=self._rw_map(points[i]),
                    seed=self.seed,
                )
            arrays = {
                cid: self._trial_arrays(cid, cd.model) for cid in self.ids
            }
            values.append(self._value_of(arrays))
            models.append(arrays)
            seconds.append(time.perf_counter() - t0)
            diverged.append(int(cd.diverged_steps))
        return values, models, seconds, diverged

    def _trial_arrays(self, cid: str, game_model) -> Dict[str, Optional[Array]]:
        """A trained coordinate's arrays — or the zeros model when EVERY
        update of the coordinate was rejected by the divergence guard and
        the serial loop kept no model at all (the stacked program's
        where-carry lands on the same zeros, so the fallback preserves
        cross-mode parity instead of crashing the sweep on the exact
        trial the guard exists for)."""
        if cid in game_model:
            return self._model_to_arrays(cid, game_model[cid])
        return self._zero_arrays(cid)

    # --------------------------------------------------------------- stacked

    def _per_trial_bytes(self) -> int:
        """HBM the trial axis charges per trial: the stacked model outputs
        (carry + collected output per coordinate) plus the per-trial score
        and offset vectors live inside the scan."""
        itemsize = np.dtype(self._dtype).itemsize
        total = 0
        for cid in self.ids:
            coord = self.coordinates[cid]
            if self._is_re(cid):
                cells = self._re_rows(cid) * coord.dim
            else:
                feats = coord._features
                cells = feats.dim if hasattr(feats, "dim") else feats.shape[-1]
            per_model = cells * itemsize * (2 if self._want_var(cid) else 1)
            total += 2 * per_model  # scan carry + stacked output
            if not self._is_re(cid) and self._want_var(cid):
                # Last-update offsets output for the FE variance replay.
                total += self._num_samples * itemsize
        # scores + summed + residual/offsets + validation total
        total += (3 * self._num_samples + self.num_validation_samples) * itemsize
        return total

    def _stack_plan(self, k: int) -> List[int]:
        cap = self.max_stack
        if cap is None:
            cap = int(get_knob("PHOTON_SWEEP_MAX_STACK"))
        cap = max(1, cap)
        per_trial = self._per_trial_bytes()
        budget = None
        try:
            stats = jax.devices()[0].memory_stats()
            budget = stats.get("bytes_limit") if stats else None
        except Exception:  # noqa: BLE001 - CPU backends report nothing
            budget = None
        if budget:
            fit = max(1, int(budget * _STACK_BUDGET_FRACTION) // per_trial)
            cap = min(cap, fit)
        chunks = [cap] * (k // cap)
        if k % cap:
            chunks.append(k % cap)
        self.stack_decisions.append(
            {
                "k": k,
                "max_stack": cap,
                "per_trial_bytes": int(per_trial),
                "budget_bytes": int(budget) if budget else None,
                "chunks": list(chunks),
            }
        )
        return chunks

    def _evaluate_stacked(self, points, warm):
        rw_stack = self._rw_stack(points)
        k = points.shape[0]
        chunks = self._stack_plan(k)
        values, models, seconds, diverged = [], [], [], []
        start = 0
        for chunk in chunks:
            rw_chunk = rw_stack[start : start + chunk]
            t0 = time.perf_counter()
            program = self._stacked_program(chunk, warm is not None)
            if warm is not None:
                out = program(rw_chunk, warm)
            else:
                out = program(rw_chunk)
            out_models, out_scores, out_div, out_fe_offs, out_fe_acc = out
            # One dispatch evaluated `chunk` trials; valuation dispatches
            # the shared jitted metric per trial and fetches ALL chunk
            # values in one host round trip (fetch-per-trial would hand
            # back most of the amortization win on a latency-bound link).
            chunk_value_devs = [
                self._value_device(out_scores[t]) for t in range(chunk)
            ]
            chunk_values = [
                float(v) for v in np.asarray(jnp.stack(chunk_value_devs))
            ]
            # Fixed-effect variances: the serial loop computes them as a
            # SEPARATE `_variance_fn` dispatch after each solve, and that
            # program inlined into the stacked trace lowers with ~1e-9
            # fusion drift (the PR 9 in-jit-fusion lesson). The in-trace
            # copy feeds only the divergence guard (finiteness is immune
            # to the drift); the RETURNED variances are recomputed here
            # through the exact serial dispatch — same program, the
            # trial's final (offsets, coefficients, reg weight) — so
            # stacked models stay bitwise-equal to serial ones. RE
            # variances need no fixup: both paths compute them inside the
            # same `_train_scan` program.
            fe_vars: Dict[str, list] = {}
            for cid, offs in out_fe_offs.items():
                coord = self.coordinates[cid]
                ds0 = coord.dataset
                ci = self.ids.index(cid)
                acc = np.asarray(out_fe_acc[cid])
                # A trial whose EVERY update for this coordinate was
                # rejected keeps the in-trace zeros variance (the serial
                # loop kept no model at all) — recomputing would report
                # the zero model's variance instead.
                fe_vars[cid] = [
                    coord._variance_fn(
                        coord._features,
                        ds0.labels,
                        offs[t],
                        ds0.weights,
                        out_models[cid]["w"][t],
                        rw_chunk[t, ci],
                    )
                    if bool(acc[t])
                    else out_models[cid]["var"][t]
                    for t in range(chunk)
                ]
            wall = time.perf_counter() - t0
            for t in range(chunk):
                values.append(chunk_values[t])
                trial_arrays = {
                    cid: {
                        key: (None if a is None else a[t])
                        for key, a in out_models[cid].items()
                    }
                    for cid in self.ids
                }
                for cid, vs in fe_vars.items():
                    trial_arrays[cid]["var"] = vs[t]
                models.append(trial_arrays)
                seconds.append(wall / chunk)
                diverged.append(int(out_div[t]))
            start += chunk
        return values, models, seconds, diverged

    def _stacked_program(self, k: int, warm: bool):
        """The one-dispatch round program for a k-trial chunk: lax.scan of
        the full per-trial coordinate-descent fit (trial_train/trial_score
        hooks + the serial loop's exact residual/commit/guard arithmetic)
        over the (k, n_coordinates) reg-weight matrix. Compiled once per
        (chunk size, warm-start arity); rounds reuse it."""
        key = (k, warm)
        cached = self._programs.get(key)
        if cached is not None:
            return cached
        ids = self.ids
        coords = self.coordinates
        # Materialize every RE coordinate's lazily-built state NOW, outside
        # the trace: trial_train/trial_score read `dataset.shards[...]`
        # (ShardDict upload) and `_scan_group_list()` (stacked scan
        # operands) — either one building INSIDE the trace would cache a
        # tracer (leak) instead of device arrays. One synchronous touch
        # per coordinate; FE coordinates hold `_features` already.
        for cid in ids:
            if self._is_re(cid):
                coords[cid].dataset.shards[coords[cid].re_dataset.feature_shard]
                coords[cid]._scan_group_list()
        n = self._num_samples
        dtype = self._dtype
        base_offsets = self._base_offsets
        num_iterations = self.num_iterations
        is_re = {cid: self._is_re(cid) for cid in ids}
        sampling = {
            cid: getattr(coords[cid].config, "down_sampling_rate", 1.0) < 1.0
            for cid in ids
        }
        want_var = {cid: self._want_var(cid) for cid in ids}
        zeros_arrays = {cid: self._zero_arrays(cid) for cid in ids}
        scorers = self.trial_scorers
        val_offsets = self.validation_offsets
        n_val = self.num_validation_samples
        root_key = jax.random.PRNGKey(self.seed)

        def guard_ok(arrays, scores):
            ok = jnp.bool_(True)
            for a in arrays:
                if a is not None:
                    ok = ok & jnp.all(jnp.isfinite(a))
            return ok & jnp.all(jnp.isfinite(scores))

        # What one REJECTED update costs the diverged counter: the serial
        # loop re-solves a deterministic divergence once per granted
        # attempt and counts each, so the stacked guard charges the same
        # (1 + retries) per rejection — TrialRecord.diverged_steps is
        # mode-invariant for the deterministic divergences that exist
        # without host-side fault injection (the `solve` fault site is a
        # host hook and never fires inside the trace). Baked at program
        # build like every other host-side gate.
        reject_cost = 1 + faults.solve_retry_attempts()

        def one_trial(rw_row, warm_arrays):
            models = {}
            scores = {}
            # Offsets at each FE coordinate's LAST update — and whether
            # ANY update was accepted — collected as outputs: the
            # host-side FE variance recomputation (see
            # `_evaluate_stacked`) replays the serial `_variance_fn`
            # dispatch with exactly these.
            fe_offs = {}
            fe_acc = {}
            summed = jnp.zeros((n,), dtype)
            if warm_arrays is not None:
                # Warm models contribute scores immediately, exactly as
                # run_coordinate_descent seeds summed scores from initial
                # models before the loop.
                for cid in ids:
                    models[cid] = dict(warm_arrays[cid])
                    s = (
                        coords[cid].trial_score(models[cid]["m"])
                        if is_re[cid]
                        else coords[cid].trial_score(models[cid]["w"])
                    )
                    scores[cid] = s
                    summed = summed + s
            div = jnp.zeros((), jnp.int32)
            for it in range(num_iterations):
                for ci, cid in enumerate(ids):
                    step = it * len(ids) + ci
                    coord = coords[cid]
                    prev = scores.get(cid, jnp.zeros((n,), dtype))
                    residual = summed - prev
                    offsets = base_offsets + residual
                    rw = rw_row[ci]
                    old = models.get(cid, zeros_arrays[cid])
                    if is_re[cid]:
                        # Fresh variance scatter target per update, as the
                        # serial train() allocates.
                        var0 = (
                            jnp.zeros_like(old["m"]) if want_var[cid] else None
                        )
                        m_new, v_new = coord.trial_train(
                            offsets, old["m"], var0, rw
                        )
                        new = {"m": m_new, "v": v_new}
                        new_scores = coord.trial_score(m_new)
                        guarded = (m_new, v_new)
                    else:
                        key_t = (
                            jax.random.fold_in(root_key, step)
                            if sampling[cid]
                            else jax.random.PRNGKey(0)
                        )
                        w_new, var_new = coord.trial_train(
                            offsets, old["w"], rw, key_t
                        )
                        new = {"w": w_new, "var": var_new}
                        new_scores = coord.trial_score(w_new)
                        guarded = (w_new, var_new)
                    ok = guard_ok(guarded, new_scores)
                    if not is_re[cid] and want_var[cid]:
                        # Offsets of the last ACCEPTED update (a rejected
                        # update keeps the previous variance — and hence
                        # the previous offsets — exactly as the serial
                        # loop's last-good model does).
                        fe_offs[cid] = jnp.where(
                            ok, offsets, fe_offs.get(cid, offsets)
                        )
                        fe_acc[cid] = fe_acc.get(cid, jnp.bool_(False)) | ok
                    # The divergence guard, per trial: a non-finite update
                    # is rejected in place (the serial loop's bounded
                    # re-solve of a deterministic program reproduces the
                    # same divergence, so both end at last-good).
                    models[cid] = {
                        name: (
                            None
                            if a is None
                            else jnp.where(ok, a, old.get(name))
                        )
                        for name, a in new.items()
                    }
                    scores[cid] = jnp.where(ok, new_scores, prev)
                    summed = jnp.where(ok, residual + new_scores, summed)
                    div = div + jnp.where(ok, 0, reject_cost).astype(jnp.int32)
            total = val_offsets
            if total is None:
                total = jnp.zeros((n_val,), dtype)
            for cid in ids:
                arrays = models.get(cid, zeros_arrays[cid])
                total = total + scorers[cid](arrays)
            return models, total, div, fe_offs, fe_acc

        if warm:

            def round_fn(rw_stack, warm_arrays):
                def scan_step(carry, rw_row):
                    return carry, one_trial(rw_row, warm_arrays)

                _, outs = jax.lax.scan(scan_step, 0, rw_stack)
                return outs

        else:

            def round_fn(rw_stack):
                def scan_step(carry, rw_row):
                    return carry, one_trial(rw_row, None)

                _, outs = jax.lax.scan(scan_step, 0, rw_stack)
                return outs

        program = jax.jit(round_fn)
        self._programs[key] = program
        return program

    # ------------------------------------------------------------ shard group

    def _groups(self) -> List[Dict[str, object]]:
        if self._group_contexts is not None:
            return self._group_contexts
        if self.group_builder is None:
            raise ValueError(
                "shard-group evaluation needs a group_builder (construct "
                "the executor through GameEstimator.sweep_executor)"
            )
        devices = jax.devices()
        g = self.shard_groups
        if g is None:
            g = int(get_knob("PHOTON_SWEEP_SHARD_GROUPS"))
        if g <= 0:
            g = len(devices)
        g = max(1, min(g, len(devices)))
        # Balanced split: when g does not divide the fleet, the first
        # len(devices) % g groups take one extra device — every device
        # belongs to exactly one group, none idles silently.
        base, extra = divmod(len(devices), g)
        contexts = []
        cursor = 0
        for gi in range(g):
            size = base + (1 if gi < extra else 0)
            devs = devices[cursor : cursor + size]
            cursor += size
            if size == 1 and devs[0] == devices[0] and self._stackable():
                # The group that is exactly the default device reuses the
                # main (already-resident) coordinates — cloning them there
                # would hold the dataset twice on that device for zero
                # parity benefit (same programs either way).
                coords = self.coordinates
            else:
                coords = self.group_builder(devs)
            contexts.append(
                {"index": gi, "devices": devs, "coordinates": coords}
            )
        logger.info(
            "sweep shard groups: %s",
            " + ".join(f"{len(c['devices'])}dev" for c in contexts),
        )
        self._group_contexts = contexts
        return contexts

    def _place_warm(self, warm, devices):
        """Warm-start arrays placed for a group: single-device groups get a
        plain device_put; multi-device groups replicate (the RE train path
        re-shards its matrix onto the group mesh itself)."""
        if warm is None:
            return None
        if len(devices) == 1:
            put = lambda a: None if a is None else jax.device_put(a, devices[0])
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from photon_ml_tpu.parallel.mesh import make_mesh

            sh = NamedSharding(make_mesh(devices), P())
            put = lambda a: None if a is None else jax.device_put(a, sh)
        return {
            cid: {name: put(a) for name, a in arrays.items()}
            for cid, arrays in warm.items()
        }

    def _evaluate_shard_group(self, points, warm):
        contexts = self._groups()
        g = len(contexts)
        k = points.shape[0]
        results: List[Optional[tuple]] = [None] * k
        errors: List[BaseException] = []

        def worker(ctx, trial_idxs):
            try:
                placed = self._place_warm(warm, ctx["devices"])
                initial = (
                    self._arrays_to_game_model(placed)
                    if placed is not None
                    else None
                )
                for i in trial_idxs:
                    t0 = time.perf_counter()
                    with telemetry.span(
                        "sweep_trial", index=i, mode="shard_group",
                        group=ctx["index"],
                    ):
                        cd = run_coordinate_descent(
                            ctx["coordinates"],
                            self.num_iterations,
                            initial_models=initial,
                            reg_weights=self._rw_map(points[i]),
                            seed=self.seed,
                        )
                        # Block inside the trial wall so the reported
                        # seconds are the trial's, not the collector's.
                        for cid in self.ids:
                            arrays = self._trial_arrays(cid, cd.model)
                            jax.block_until_ready(
                                arrays["m" if self._is_re(cid) else "w"]
                            )
                    results[i] = (cd, time.perf_counter() - t0)
            except BaseException as exc:  # noqa: BLE001 - re-raised by driver
                errors.append(exc)

        span_h = telemetry.span_handoff()

        def run_worker(ctx, idxs):
            with telemetry.adopt_span(span_h):
                worker(ctx, idxs)

        threads = []
        for gi, ctx in enumerate(contexts):
            idxs = list(range(gi, k, g))
            if not idxs:
                continue
            t = threading.Thread(
                target=run_worker,
                args=(ctx, idxs),
                name=f"photon-sweep-group-{gi}",
            )
            threads.append(t)
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        values, models, seconds, diverged = [], [], [], []
        for i in range(k):
            cd, wall = results[i]
            # Pull the trial's model back to the main device (groups live
            # on their own devices/submeshes; valuation and warm-start
            # state are main-device).
            arrays = {}
            for cid in self.ids:
                raw = self._trial_arrays(cid, cd.model)
                arrays[cid] = {
                    name: (
                        None
                        if a is None
                        else jnp.asarray(np.asarray(a), self._dtype)
                    )
                    for name, a in raw.items()
                }
            values.append(self._value_of(arrays))
            models.append(arrays)
            seconds.append(wall)
            diverged.append(int(cd.diverged_steps))
        return values, models, seconds, diverged

"""Cyclic block coordinate descent over named GAME coordinates.

Counterpart of photon-lib algorithm/CoordinateDescent.scala:43-682. The
reference maintains per-coordinate score RDDs plus a running summedScores and
computes the residual for coordinate c as (summedScores - oldScores(c)),
exchanged via by-uid RDD joins with aggressive persist/unpersist juggling
(:325-354, :443-470). Here every coordinate's scores live in the SAME fixed
sample order on device, so the residual update is three elementwise vector
ops and the "exchange" is free — the static sample->slot layout shared by all
coordinates is what makes GAME cheap on TPU.

Supported, mirroring the reference:
  * update sequence = insertion order of `coordinates`
  * warm start from an initial GameModel (loaded or from a previous
    reg-weight sweep step)
  * locked coordinates (partial retraining, :55, :266-283): their models are
    fixed, they contribute scores only
  * per-iteration validation tracking with best-model selection by the
    primary evaluator (:499-652)
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Mapping, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.evaluation.suite import EvaluationResults, EvaluationSuite
from photon_ml_tpu.game.model import GameModel
from photon_ml_tpu.types import OptimizerType
from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.observability import (
    record_stage,
    set_stage_note,
    stage_timer,
)

logger = logging.getLogger(__name__)


def _model_arrays(model, scores) -> tuple:
    """The arrays a coordinate update's divergence guard must vet."""
    arrays = [scores]
    coeffs = getattr(model, "coefficients", None)
    if coeffs is not None:
        arrays.append(coeffs.means)
        if coeffs.variances is not None:
            arrays.append(coeffs.variances)
    matrix = getattr(model, "coefficients_matrix", None)
    if matrix is not None:
        arrays.append(matrix)
        if getattr(model, "variances_matrix", None) is not None:
            arrays.append(model.variances_matrix)
    return tuple(arrays)


# Per-coordinate sweep glue as TWO fused XLA programs (the scan-the-sweep
# companion to the coordinate-level scan in game/coordinate.py): residual +
# offset build is one dispatch, and the commit — new summed scores PLUS the
# divergence guard's all-finite reduction over every updated array — is one
# more, whose single boolean fetch is the sweep's only host sync. The ops
# are identical to the previous unfused expressions, so residuals, summed
# scores and the guard decision are bitwise unchanged.


@jax.jit
def _residual_offsets(summed, prev_scores, base_offsets):
    residual = summed - prev_scores
    return residual, base_offsets + residual


def _score_zeros(n: int, dtype, like):
    """A zero score vector placed WHERE the sample arrays live. On a
    single process this is exactly `jnp.zeros` (bitwise-identical
    dispatch). When `like` (the dataset's offsets) is a global array over
    a multi-process mesh, a process-local zeros array must not enter the
    residual computation — mixing addressable-only and global operands is
    the "Multiprocess computations aren't implemented" crash — so the
    zeros are assembled with the SAME (replicated) sharding."""
    sharding = getattr(like, "sharding", None)
    mesh = getattr(sharding, "mesh", None)
    if mesh is not None:
        from photon_ml_tpu.parallel.mesh import mesh_spans_processes

        if mesh_spans_processes(mesh):
            import numpy as np

            z = np.zeros((n,), dtype)
            return jax.make_array_from_callback(
                z.shape, sharding, lambda idx: z[idx]
            )
    return jnp.zeros((n,), dtype)


@jax.jit
def _commit_update(residual, new_scores, guarded_arrays):
    ok = jnp.bool_(True)
    for a in guarded_arrays:
        ok = ok & jnp.all(jnp.isfinite(a))
    return residual + new_scores, ok


def _recover_from_mesh_loss(
    exc,
    *,
    snapshot,
    validation_history,
    ckpt,
    ckpt_config_key,
    task,
    completed_steps,
):
    """Rebuild the outer-loop state after a mid-fit mesh loss.

    HAPPY PATH (in memory): the sweep-boundary snapshot's models
    reassemble to replicated host-backed models through the surviving
    replicas (`checkpoint.reassemble_model_in_memory` — the elastic
    checkpoint's any-shape reassembly without the filesystem round trip);
    the step cursor is UNCHANGED — the snapshot was taken at the later of
    (sweep start, resume cursor), so the existing `step <
    completed_steps` fast-forward already replays exactly the lost work.

    FALLBACK (the device fetch itself fails — the blocks really are
    gone): reload the durable checkpoint and resume from ITS cursor, the
    standard kill-resume protocol. No checkpoint configured re-raises the
    original MeshLoss.

    Returns (models, best_models, best_results, pass_results,
    completed_steps, source)."""
    from photon_ml_tpu.game.checkpoint import reassemble_model_in_memory

    snap_models, snap_pass, snap_vh_len, snap_best, snap_best_res = snapshot
    try:
        models = {
            cid: reassemble_model_in_memory(m)
            for cid, m in snap_models.items()
        }
        best_models = {
            cid: reassemble_model_in_memory(m)
            for cid, m in snap_best.items()
        }
    except Exception:
        logger.warning(
            "in-memory mesh-loss reassembly failed; falling back to the "
            "durable checkpoint",
            exc_info=True,
        )
        if ckpt is None or not ckpt.exists():
            raise exc
        state = ckpt.load(task, config_key=ckpt_config_key)
        validation_history[:] = list(state.validation_history)
        pass_results = (
            state.validation_history[-1][2]
            if state.validation_history
            else None
        )
        return (
            state.models,
            state.best_models or dict(state.models),
            state.best_results,
            pass_results,
            state.completed_steps,
            "checkpoint",
        )
    del validation_history[snap_vh_len:]
    return models, best_models, snap_best_res, snap_pass, completed_steps, "memory"


def _fetch_verdict(ok, stats):
    """(finite, counts) of one attempted update, with the ONE device-to-host
    fetch the divergence guard always made: the fixed effect's
    `OptResult.fn_evals`, `iterations` and (TRON) `hv_evals` are device
    scalars, ready since its solve ended, and ride along with the guard's
    boolean. A random effect's stats hold the counts on the host already
    (`_finish_train`'s one fetch). `counts` holds `fn_evals`, `iterations`
    and `rejected`, what the solve evaluated and threw away: its passes over
    the data less the first evaluation, one an iteration and the
    Hessian-vector products — a line-search solve's failed Armijo trials (a
    search that fails outright reads one short), a TRON solve's refused
    trial steps — and `hv_evals` where the stats carry that count (a fixed
    effect's TRON solve). Empty where the coordinate's train reports no
    count."""
    if getattr(stats, "fn_evals", None) is None:
        return bool(ok), {}
    names = ["fn_evals", "iterations"]
    if getattr(stats, "hv_evals", None) is not None:
        names.append("hv_evals")
    fetched = (ok, *(getattr(stats, name) for name in names))
    if isinstance(stats.fn_evals, jax.Array):
        fetched = jax.device_get(fetched)
    counts = {name: int(value) for name, value in zip(names, fetched[1:])}
    counts["rejected"] = (
        counts["fn_evals"] - getattr(stats, "solves", 1) - counts["iterations"]
        - counts.get("hv_evals", 0)
    )
    return bool(fetched[0]), counts


def _update_all_finite(model, scores) -> bool:
    """ONE scalar all-finite check over a coordinate update (new model +
    new scores): the and-reduction builds device-side, so the guard costs a
    single boolean fetch per coordinate update, not one per array."""
    ok = jnp.bool_(True)
    for a in _model_arrays(model, scores):
        ok = ok & jnp.all(jnp.isfinite(a))
    return bool(ok)


@dataclasses.dataclass
class CoordinateDescentResult:
    model: GameModel
    best_model: GameModel
    validation_history: List[Tuple[int, str, EvaluationResults]]
    timing: Dict[str, float]
    # Coordinate updates rejected by the divergence guard (a COUNT, kept
    # out of the seconds-valued `timing` dict so per-coordinate timing
    # artifacts stay pure wall clock). 0 on a clean run.
    diverged_steps: int = 0
    # Objective evaluations per coordinate, as its optimizer counted them
    # (every attempt of every update; line-search trials and
    # Hessian-vector products included). A coordinate whose train reports
    # none is absent.
    fn_evals: Dict[str, int] = dataclasses.field(default_factory=dict)
    # Line-search trials per coordinate that failed the Armijo test: of an
    # L-BFGS solve's evaluations, those beyond the first and one an
    # iteration, each a value+gradient evaluation thrown away. A TRON
    # coordinate is absent.
    line_search_rejected: Dict[str, int] = dataclasses.field(default_factory=dict)
    # Per TRON coordinate whose train reports them (a fixed effect's), what
    # its solves did: {"accepted", "rejected" (trial steps refused, each a
    # truncated CG and a value+gradient evaluation thrown away),
    # "hessian_vector_products" (among `fn_evals`; one a CG iteration, so
    # also the CG iterations), "kernel" ("pallas" | "xla": what computes a
    # product)}.
    tron: Dict[str, dict] = dataclasses.field(default_factory=dict)
    # Analytic wire bytes moved through entity-shard ring collectives by
    # the accepted coordinate updates (RandomEffectCoordinate.train sets
    # last_train_collective_bytes per sweep; 0 on the replicated path) —
    # the pod-scale accounting `fit_timing["sharding"]` reports.
    collective_bytes: int = 0
    # Mid-fit mesh losses recovered at a sweep boundary (ISSUE 13), and
    # the sweeps those recoveries repeated — each in-memory recovery
    # rolls the interrupted sweep back and replays it on the surviving
    # mesh, so a clean run reports 0/0 and a single loss reports 1/1.
    mesh_losses: int = 0
    repeated_sweeps: int = 0
    # The validation results of `model` itself, as this call computed them:
    # the last validation, taken with every coordinate of `model` scored. It
    # outlives a rejected update (the models did not change) and not a
    # mesh-loss rollback (they did, unevaluated). None where this call
    # evaluated nothing: no validation, a resume with no step left, every
    # update rejected.
    evaluation: Optional[EvaluationResults] = None


def run_coordinate_descent(
    coordinates: Mapping[str, object],
    num_iterations: int,
    *,
    initial_models: Optional[GameModel] = None,
    locked_coordinates: Optional[Set[str]] = None,
    validation_scorer=None,
    validation_suite: Optional[EvaluationSuite] = None,
    validation_offsets=None,
    reg_weights: Optional[Mapping[str, float]] = None,
    seed: int = 0,
    checkpoint_dir: Optional[str] = None,
    prefetch: bool = False,
    on_event=None,
    mesh_rebuilder=None,
    max_mesh_losses: int = 2,
    checkpoint_factory=None,
    stale_checkpoint: str = "error",
) -> CoordinateDescentResult:
    """Run cyclic coordinate descent (CoordinateDescent.run, :132-134).

    `coordinates`: ordered coordinate id -> FixedEffect/RandomEffectCoordinate.
    `validation_scorer(cid, model) -> scores` produces validation-set scores
    for one coordinate's model; the suite evaluates the summed scores.
    `reg_weights`: optional per-coordinate override (the sweep path).

    `prefetch=True` enables the host data-plane overlap: before solving
    coordinate k, the NEXT unlocked coordinate's `prefetch()` hook starts
    its device-shard upload on a background thread (ShardDict async
    materialization), so the transfer hides behind the solve instead of
    faulting synchronously at coordinate k+1's first gather. Prefetching
    changes only when uploads happen, never their content.

    `on_event(etype, **fields)` is the lifecycle hook (ISSUE 11): called
    with ("coordinate", iteration/coordinate/seconds/accepted/fn_evals)
    after every update and ("checkpoint", step/coordinate) after every
    durable save —
    the estimator forwards these as typed bus events into the run journal.

    `checkpoint_dir` enables checkpoint-restart of the outer loop (SURVEY
    §5.3's replacement for Spark lineage recovery): after every coordinate
    update the models + step cursor persist atomically; a rerun with the
    same arguments fast-forwards past completed updates, recomputing scores
    from the checkpointed models, and reproduces the uninterrupted result
    (down-sampling keys derive from (seed, step), so resumed subsamples are
    identical).

    `stale_checkpoint` picks the policy for a checkpoint whose config
    fingerprint does not match this run's: "error" (default) refuses to
    resume — the single-run safety contract, an edited config should be
    loud — while "discard" clears it and starts fresh. The refresh loop
    uses "discard": each round's full refit is a NEW run configuration
    (the merged dataset grew), so a leftover checkpoint from a prior
    completed round can never be resumed, only a crash of THIS round's
    fit (same fingerprint) can.

    MID-FIT MESH ELASTICITY (ISSUE 13): a typed `faults.MeshLoss` raised
    during a coordinate update — the armed `mesh_loss` fault site, or a
    device-shaped failure (watchdog-escalated DeviceHang, exhausted
    collective retries past even the bucket-loop fallback) on an
    entity-sharded coordinate — is caught AT THE SWEEP BOUNDARY instead of
    killing the fit: the interrupted sweep rolls back to its boundary
    state, every model reassembles IN MEMORY through the surviving
    replicas (`checkpoint.reassemble_model_in_memory`, the elastic
    checkpoint's any-shape reassembly without the filesystem round trip;
    the durable checkpoint is the fallback when the device fetch itself
    fails), `mesh_rebuilder()` supplies coordinates re-formed over the
    surviving mesh (same ids; None keeps the current ones), residual
    state recomputes from the models, and the sweep replays — bitwise
    equal to the uninterrupted fit at the cost of exactly one repeated
    sweep, because sharded and replicated sweeps are bitwise-identical by
    construction (PR 7/10). At most `max_mesh_losses` recoveries; the
    next loss re-raises.
    """
    locked = locked_coordinates or set()
    ids = list(coordinates.keys())
    unlocked = [c for c in ids if c not in locked]
    if not unlocked:
        raise ValueError("At least one coordinate must be trainable")
    for c in locked:
        if initial_models is None or c not in initial_models:
            raise ValueError(f"Locked coordinate {c!r} needs an initial model")

    first = next(iter(coordinates.values()))
    base_offsets = first.dataset.offsets
    n = first.dataset.num_samples
    dtype = base_offsets.dtype

    models: Dict[str, object] = dict(initial_models.models) if initial_models else {}
    timing: Dict[str, float] = {}
    fn_evals: Dict[str, int] = {}
    line_search_rejected: Dict[str, int] = {}
    tron: Dict[str, dict] = {}
    diverged_steps = 0
    collective_bytes = 0
    validation_history: List[Tuple[int, str, EvaluationResults]] = []
    best_results: Optional[EvaluationResults] = None
    best_models: Dict[str, object] = dict(models)
    completed_steps = 0

    ckpt = None
    ckpt_config_key = None
    if checkpoint_dir is not None:
        import hashlib

        from photon_ml_tpu.game.checkpoint import CoordinateDescentCheckpoint
        from photon_ml_tpu.optimize.config import static_config_key

        # Fingerprint the run configuration: resume with changed
        # coordinates/optimizer settings/reg weights must refuse, not
        # silently fast-forward past training with stale models.
        def _shard_identity(feats) -> tuple:
            from photon_ml_tpu.data.containers import SparseFeatures

            if isinstance(feats, SparseFeatures):
                return ("sparse", tuple(feats.indices.shape), feats.dim)
            return ("dense", tuple(feats.shape))

        fp = (
            tuple(ids),
            tuple(sorted(locked)),
            tuple(static_config_key(coordinates[c].config) for c in ids),
            # Effective per-coordinate reg weight: the override when given,
            # else the coordinate's own configured weight (static_config_key
            # deliberately excludes it, so it must enter here).
            tuple(
                (c, float((reg_weights or {}).get(c, coordinates[c].config.reg_weight)))
                for c in ids
            ),
            # Cheap dataset identity: resuming after the input data changed
            # must refuse rather than fast-forward past steps trained on the
            # old data (full content hashes would cost a pass over the data;
            # shape + sample-count changes catch the realistic swaps).
            tuple(
                (
                    c,
                    coordinates[c].dataset.num_samples,
                    tuple(
                        sorted(
                            (name, _shard_identity(f))
                            for name, f in coordinates[c].dataset.shards.items()
                        )
                    ),
                )
                for c in ids
            ),
        )
        ckpt_config_key = hashlib.sha256(repr(fp).encode()).hexdigest()

        # `checkpoint_factory(checkpoint_dir)` substitutes a checkpoint
        # implementation with the same commit protocol — the multi-host
        # mode passes parallel/hostmesh.MultihostCheckpoint so each host
        # writes only its own shards behind a cross-host commit barrier.
        ckpt = (
            checkpoint_factory(checkpoint_dir)
            if checkpoint_factory is not None
            else CoordinateDescentCheckpoint(checkpoint_dir)
        )
        if (
            stale_checkpoint == "discard"
            and ckpt.exists()
            and ckpt.stored_config_key() != ckpt_config_key
        ):
            logger.info(
                "checkpoint at %s was written for a different run "
                "configuration — discarding and starting fresh",
                checkpoint_dir,
            )
            ckpt.clear()
        if ckpt.exists():
            task = next(iter(coordinates.values())).task
            state = ckpt.load(task, config_key=ckpt_config_key)
            if state.seed != seed:
                raise ValueError(
                    f"checkpoint at {checkpoint_dir} was written with seed "
                    f"{state.seed}, not {seed} — refusing to resume"
                )
            models = state.models
            best_models = state.best_models or dict(models)
            best_results = state.best_results
            validation_history = list(state.validation_history)
            completed_steps = state.completed_steps
            logger.info(
                "resuming coordinate descent from %s at step %d",
                checkpoint_dir,
                completed_steps,
            )

    scores: Dict[str, jnp.ndarray] = {}
    summed = _score_zeros(n, dtype, base_offsets)
    # Locked coordinates, warm-start and checkpointed models contribute
    # scores immediately (reference seeds summedScores from initial models,
    # :168-220; on resume the residual state is a pure function of models).
    for cid in ids:
        if cid in models:
            s = coordinates[cid].score(models[cid])
            scores[cid] = s
            summed = summed + s

    val_scores: Dict[str, jnp.ndarray] = {}
    if validation_scorer is not None:
        for cid in ids:
            if cid in models:
                val_scores[cid] = validation_scorer(cid, models[cid])

    import jax

    # Planned quantity (ISSUE 14): how many upcoming unlocked coordinates
    # the loop prefetches while the current one solves. Default 1 (the
    # pre-planner behavior); a plan deepens it when the profile shows the
    # upload stage un-hidden. Bitwise-neutral: prefetch is an async
    # upload of shards that upload anyway.
    from photon_ml_tpu import planner

    prefetch_depth = max(1, int(planner.planned_value("prefetch_depth")))

    def _prefetch_after(step: int) -> None:
        """Kick the next `prefetch_depth` DISTINCT upcoming unlocked
        coordinates' async shard uploads so they overlap the CURRENT
        coordinate's solve. The currently-solving coordinate (whose
        shards are already resident) and already-kicked coordinates do
        not consume depth slots — on a 2-coordinate job a planned depth
        of 2 honestly degrades to the 1 other coordinate that exists.
        Best-effort: a prefetch failure surfaces (if real) at the
        consumer's own access."""
        if not prefetch:
            return
        total = num_iterations * len(ids)
        current = ids[step % len(ids)]
        kicked: set = set()
        for s in range(step + 1, total):
            nxt = ids[s % len(ids)]
            if nxt in locked or nxt == current or nxt in kicked:
                continue
            hook = getattr(coordinates[nxt], "prefetch", None)
            if hook is not None:
                try:
                    hook()
                except Exception:  # noqa: BLE001 - resurfaces at the gather
                    logger.debug("prefetch of %s failed", nxt, exc_info=True)
            kicked.add(nxt)
            if len(kicked) >= prefetch_depth:
                return

    root_key = jax.random.PRNGKey(seed)
    # Most recent validation results (best-pass selection compares against
    # these at each pass-final coordinate). On resume, reconstruct from the
    # persisted history: a replayed step whose update is REJECTED skips
    # validation, so without this the resumed run would compare against
    # None where the uninterrupted run compared against the previous
    # step's results — a kill-resume best-model divergence.
    pass_results: Optional[EvaluationResults] = (
        validation_history[-1][2] if validation_history else None
    )
    last_unlocked = unlocked[-1]
    # What CoordinateDescentResult.evaluation will hold.
    model_evaluation: Optional[EvaluationResults] = None
    mesh_losses = 0
    repeated_sweeps = 0
    it = 0
    while it < num_iterations:
        # Sweep-boundary snapshot: what a mesh-loss recovery rolls back to.
        # Cheap — dict copies of model/score REFERENCES plus a few
        # scalars; the arrays themselves are immutable. The counters are
        # snapshotted too: a rejection/collective that happened INSIDE
        # the interrupted sweep replays deterministically, and counting
        # it twice would break the "bitwise the uninterrupted fit"
        # contract for the result record.
        sweep_snapshot = (
            dict(models),
            pass_results,
            len(validation_history),
            dict(best_models),
            best_results,
        )
        snap_diverged = diverged_steps
        snap_collective = collective_bytes
        try:
          for ci, cid in enumerate(ids):
            if cid in locked:
                continue
            step = it * len(ids) + ci
            if step < completed_steps:
                continue  # fast-forward past checkpointed updates
            coord = coordinates[cid]
            # Divergence guard: an update whose new model or scores carry a
            # non-finite value is REJECTED — committing it would poison every
            # later coordinate's residual this run AND, via the checkpoint,
            # every resumed run. A rejected solve gets a bounded number of
            # retries (PHOTON_SOLVE_RETRIES, default 1): a transient cause
            # (injected fault, flaky accelerator) re-solves to the exact
            # fault-free result; a deterministic divergence reproduces and
            # the coordinate keeps its last-good model.
            model = None
            new_scores = None
            new_summed = None
            update_counts: Dict[str, int] = {}  # summed over the attempts
            # One stage per coordinate update, its wall the update's
            # `timing` entry; the cd/* stages inside it are declared in
            # contracts.SOLVE_STAGES (which are dispatch walls, which wait).
            with stage_timer(
                "coordinate_update", coordinate=cid, iteration=it
            ) as update:
                with stage_timer("cd/residual"):
                    _prefetch_after(step)
                    residual, offsets = _residual_offsets(
                        summed,
                        scores.get(cid, _score_zeros(n, dtype, base_offsets)),
                        base_offsets,
                    )
                kwargs = {}
                if reg_weights and cid in reg_weights:
                    kwargs["reg_weight"] = reg_weights[cid]
                if getattr(coord.config, "down_sampling_rate", 1.0) < 1.0:
                    # Fresh subsample per optimize call, as in the reference's
                    # runWithSampling
                    # (DistributedOptimizationProblem.scala:144).
                    kwargs["key"] = jax.random.fold_in(root_key, step)

                # Mesh-loss fault site (ISSUE 13): one invocation per
                # coordinate update. An armed plan simulates part of the
                # mesh dying mid-update — converted to the typed MeshLoss
                # the sweep-boundary handler below recovers from.
                try:
                    faults.fault_point("mesh_loss")
                except faults.InjectedFault as exc:
                    raise faults.MeshLoss(
                        f"injected mesh loss at iteration {it} "
                        f"coordinate {cid!r}"
                    ) from exc

                for attempt in range(1 + faults.solve_retry_attempts()):
                    try:
                        faults.fault_point("solve")
                    except faults.InjectedFault:
                        # Only the solve site's OWN injection reads as a
                        # divergence; faults raised inside train/score (e.g.
                        # an upload whose retries exhausted) keep their
                        # surface semantics — swallowing them here would ship
                        # an untrained model as a "diverged" counter.
                        finite = False
                    else:
                        try:
                            with stage_timer("cd/train"):
                                cand_model, stats = coord.train(
                                    offsets, models.get(cid), **kwargs
                                )
                            with stage_timer("cd/score"):
                                cand_scores = coord.score(cand_model)
                            with stage_timer("cd/commit"):
                                # One fused program: the next summed-scores
                                # vector and the divergence guard's
                                # reduction; one fetch, the solve's
                                # evaluation count riding along.
                                cand_summed, ok = _commit_update(
                                    residual,
                                    cand_scores,
                                    _model_arrays(cand_model, cand_scores),
                                )
                                finite, counts = _fetch_verdict(ok, stats)
                            for name, count in counts.items():
                                update_counts[name] = update_counts.get(name, 0) + count
                        except faults.MeshLoss:
                            raise
                        except BaseException as exc:
                            # Escalation to MeshLoss: a device-shaped
                            # failure that escaped the coordinate's OWN
                            # failure domain (bounded re-dispatch AND the
                            # bucket-loop fallback both lost) on an
                            # entity-sharded coordinate means the shard
                            # group is dead — in-place retry would re-hit
                            # the same dead devices, so hand it to the
                            # sweep-boundary elastic resume instead.
                            if getattr(
                                coord, "entity_mesh", None
                            ) is not None and faults.is_device_error(exc):
                                raise faults.MeshLoss(
                                    f"device-shaped failure on the "
                                    f"entity-sharded coordinate {cid!r} "
                                    f"at iteration {it}: {exc!r}"
                                ) from exc
                            raise
                    if finite:
                        model, new_scores = cand_model, cand_scores
                        new_summed = cand_summed
                        break
                    diverged_steps += 1
                    record_stage("diverged", 1.0)
                    logger.warning(
                        "iteration %d coordinate %s: non-finite update "
                        "rejected (attempt %d)",
                        it,
                        cid,
                        attempt + 1,
                    )
                accepted = model is not None
                update_evals = update_counts.get("fn_evals")
                update.set(accepted=accepted, fn_evals=update_evals)
                if accepted:
                    summed = new_summed
                    scores[cid] = new_scores
                    models[cid] = model
                    collective_bytes += int(
                        getattr(coord, "last_train_collective_bytes", 0)
                    )
                else:
                    logger.error(
                        "iteration %d coordinate %s diverged on every "
                        "attempt — keeping its last-good model; the "
                        "rejected update is not checkpointed",
                        it,
                        cid,
                    )
            if update_evals is not None:
                fn_evals[cid] = fn_evals.get(cid, 0) + update_evals
            if "hv_evals" in update_counts:  # a fixed effect's TRON solve
                solved = tron.setdefault(
                    cid,
                    {
                        "accepted": 0,
                        "rejected": 0,
                        "hessian_vector_products": 0,
                        "kernel": coord.hessian_vector_kernel,
                    },
                )
                solved["accepted"] += update_counts["iterations"]
                solved["rejected"] += update_counts["rejected"]
                solved["hessian_vector_products"] += update_counts["hv_evals"]
                set_stage_note("tron", dict(solved))
            elif (
                update_evals is not None
                and coord.config.optimizer.optimizer_type != OptimizerType.TRON
            ):
                line_search_rejected[cid] = (
                    line_search_rejected.get(cid, 0) + update_counts["rejected"]
                )
            timing[f"{cid}/iter{it}"] = update.seconds
            telemetry.METRICS.observe("coordinate_update_s", update.seconds)
            if on_event is not None:
                on_event(
                    "coordinate",
                    iteration=it,
                    coordinate=cid,
                    seconds=update.seconds,
                    accepted=accepted,
                    fn_evals=update_evals,
                )
            logger.info(
                "iteration %d coordinate %s trained in %.3fs (%s objective "
                "evaluations)",
                it,
                cid,
                update.seconds,
                "uncounted" if update_evals is None else update_evals,
            )

            # Overlap the step's durable model write with the validation
            # evaluation below (EvaluationSuite's device round trip): the
            # npz write is host disk I/O, so the two hide behind each
            # other. save() joins the write before the state.json commit —
            # the crash-exact protocol is untouched. Pipeline-gated like
            # every other overlap (a write thread on a 1-core host only
            # steals the evaluator's core).
            staged_write = None
            if (
                accepted
                and ckpt is not None
                and prefetch
                and validation_scorer is not None
                and validation_suite is not None
            ):
                with stage_timer("cd/checkpoint"):
                    staged_write = ckpt.begin_model_write(
                        completed_steps=step + 1, cid=cid, model=model
                    )

            if accepted and validation_scorer is not None and validation_suite is not None:
                with stage_timer("cd/validation_score"):
                    val_scores[cid] = validation_scorer(cid, model)
                    # Seed with the validation offsets so selection uses the
                    # same score definition as the final reported evaluation.
                    total = validation_offsets
                    for s in val_scores.values():
                        total = s if total is None else total + s
                with stage_timer("cd/validation_evaluate"):
                    results = validation_suite.evaluate(total)
                validation_history.append((it, cid, results))
                logger.info("validation after %s: %s", cid, results.results)
                pass_results = results
                # A model handed in for no coordinate of this call is in
                # `models` and in no sum of scores.
                model_evaluation = (
                    results if val_scores.keys() == models.keys() else None
                )

            # Best-model selection happens on full passes only, when every
            # coordinate's model exists (CoordinateDescent.scala:499-652) —
            # applied at the pass's last trained coordinate so the update is
            # covered by this step's checkpoint.
            best_updated = False
            if cid == last_unlocked and pass_results is not None and pass_results.better_than(best_results):
                best_results = pass_results
                best_models = dict(models)
                best_updated = True

            if ckpt is not None:
                # trained_cid=None on a rejected update: the step cursor
                # still advances (resume replays from the same (seed, step)
                # keys), but the non-finite model is NEVER written — the
                # durable state keeps the last-good model.
                with stage_timer("cd/checkpoint"):
                    ckpt.save(
                        completed_steps=step + 1,
                        seed=seed,
                        config_key=ckpt_config_key,
                        models=models,
                        trained_cid=cid if accepted else None,
                        best_is_current=best_updated,
                        best_results=best_results,
                        validation_history=validation_history,
                        staged=staged_write,
                    )
                if on_event is not None:
                    on_event("checkpoint", step=step + 1, coordinate=cid)
            elif staged_write is not None:  # pragma: no cover - ckpt is set
                staged_write[4].join()
        except faults.MeshLoss as exc:
            mesh_losses += 1
            faults.COUNTERS.increment("mesh_losses")
            if mesh_losses > max(0, int(max_mesh_losses)):
                logger.error(
                    "mesh loss #%d exceeds max_mesh_losses=%d — giving up",
                    mesh_losses,
                    max_mesh_losses,
                )
                raise
            (
                models,
                best_models,
                best_results,
                pass_results,
                completed_steps,
                source,
            ) = _recover_from_mesh_loss(
                exc,
                snapshot=sweep_snapshot,
                validation_history=validation_history,
                ckpt=ckpt,
                ckpt_config_key=ckpt_config_key,
                task=next(iter(coordinates.values())).task,
                completed_steps=completed_steps,
            )
            model_evaluation = None  # the models rolled back, unevaluated
            if source == "memory":
                # The rolled-back sweep replays in full, so its counter
                # increments recur deterministically — restore to the
                # boundary values or they double-count. The CHECKPOINT
                # path must NOT restore: its cursor may sit mid-sweep and
                # the fast-forward skips re-executing those steps, so
                # their already-counted events would be lost.
                diverged_steps = snap_diverged
                collective_bytes = snap_collective
            # Re-form the mesh from the surviving devices: the caller's
            # rebuilder supplies coordinates over the new layout (same
            # ids); None keeps the current ones (replicated fits).
            if mesh_rebuilder is not None:
                rebuilt = mesh_rebuilder()
                if rebuilt is not None:
                    if list(rebuilt.keys()) != ids:
                        raise ValueError(
                            "mesh_rebuilder must return the same coordinate "
                            f"ids ({ids}), got {list(rebuilt.keys())}"
                        )
                    coordinates = rebuilt
            # Residual state is a pure function of the models — recompute
            # it through the NEW coordinates (the rebuilt dataset may pad
            # samples differently on the smaller mesh).
            first = next(iter(coordinates.values()))
            base_offsets = first.dataset.offsets
            n = first.dataset.num_samples
            dtype = base_offsets.dtype
            scores = {}
            summed = _score_zeros(n, dtype, base_offsets)
            for c2 in ids:
                if c2 in models:
                    s = coordinates[c2].score(models[c2])
                    scores[c2] = s
                    summed = summed + s
            val_scores = {}
            if validation_scorer is not None:
                for c2 in ids:
                    if c2 in models:
                        val_scores[c2] = validation_scorer(c2, models[c2])
            surviving = max(
                int(m.devices.size)
                if (m := getattr(c, "entity_mesh", None)) is not None
                else 1
                for c in coordinates.values()
            )
            repeated_sweeps += 1
            telemetry.emit_event(
                "mesh_loss",
                iteration=it,
                coordinate=cid,
                surviving_devices=surviving,
                source=source,
            )
            logger.warning(
                "mesh loss recovered at the iteration-%d sweep boundary "
                "(%s; state reassembled from %s, %d surviving device(s)) — "
                "repeating the sweep",
                it,
                exc,
                source,
                surviving,
            )
            continue  # repeat the interrupted sweep on the surviving mesh
        it += 1

    final = GameModel(dict(models))
    best = GameModel(dict(best_models)) if best_models else final
    if best_results is None:
        best = final
    return CoordinateDescentResult(
        model=final,
        best_model=best,
        validation_history=validation_history,
        timing=timing,
        diverged_steps=diverged_steps,
        fn_evals=fn_evals,
        line_search_rejected=line_search_rejected,
        tron=tron,
        collective_bytes=collective_bytes,
        mesh_losses=mesh_losses,
        repeated_sweeps=repeated_sweeps,
        evaluation=model_evaluation,
    )

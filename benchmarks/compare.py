"""The comparison that decides `correct`.

Every compared number is a gap between what the timed fits produced and what
the configuration's plain reference produced on the same rows (`numbers`), held
against a limit of its own from the configuration's `limits`. A number without a limit
there fails: a limit is never defaulted.
"""

import numpy as np


ENTITY_QUANTILE = 0.99


def frobenius_gap(program: np.ndarray, reference: np.ndarray) -> float:
    """|program - reference| / |reference| over one coordinate (Frobenius)."""
    gap = float(np.linalg.norm(program - reference) / max(np.linalg.norm(reference), 1e-30))
    return gap if np.isfinite(gap) else float("inf")


def entity_gaps(program: np.ndarray, reference: np.ndarray) -> np.ndarray:
    """One gap an entity (a row): |row difference| over the reference row's
    norm or the median row's, whichever is larger, since some entities' rows
    are all but zero."""
    norms = np.linalg.norm(reference, axis=1)
    floor = np.median(norms[norms > 0]) if (norms > 0).any() else 1e-30
    return np.linalg.norm(program - reference, axis=1) / np.maximum(norms, floor)


def coefficient_gap(program: np.ndarray, reference: np.ndarray) -> float:
    """A fixed effect (one vector): the Frobenius gap. A random effect (one
    row an entity): the 99th percentile of the entities' gaps. Every entity is
    a truncated line-search solve of its own, and in a few of tens of thousands
    an Armijo test falls within rounding and the two sides take different
    steps; the Frobenius gap then reads what those few entities read (PERF.md
    section 2), while a lower precision or a dropped row moves every entity."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    if program.shape != reference.shape:
        return float("inf")
    if reference.ndim == 1:
        return frobenius_gap(program, reference)
    gap = float(np.quantile(entity_gaps(program, reference), ENTITY_QUANTILE))
    return gap if np.isfinite(gap) else float("inf")


def numbers(fits: list, reference: dict) -> dict:
    """Worst over the compared fits: one gap a coordinate and the metric's gap."""
    out = {}
    for cid, ref in reference["coefficients"].items():
        out[f"coef_gap.{cid}"] = max(coefficient_gap(f["coefficients"][cid], ref) for f in fits)
    out["metric_gap"] = max(abs(f["metric"] - reference["metric"]) for f in fits)
    return out


def notes(fits: list, reference: dict) -> dict:
    """Readings printed beside the compared numbers and held to no limit: a
    random effect's Frobenius gap and its worst entity, in the last fit."""
    out = {}
    for cid, ref in reference["coefficients"].items():
        if np.ndim(ref) == 2:
            program = np.asarray(fits[-1]["coefficients"][cid], np.float64)
            if program.shape == np.shape(ref):
                ref = np.asarray(ref, np.float64)
                out[f"frobenius_gap.{cid}"] = frobenius_gap(program, ref)
                out[f"worst_entity_gap.{cid}"] = float(entity_gaps(program, ref).max())
    return out


def judge(values: dict, limits: dict) -> list:
    """[{name, value, limit, ok}] in a fixed order."""
    rows = []
    for name in sorted(values):
        limit = limits.get(name)
        value = values[name]
        ok = limit is not None and np.isfinite(value) and value <= limit
        rows.append({"name": name, "value": float(value), "limit": limit, "ok": bool(ok)})
    return rows

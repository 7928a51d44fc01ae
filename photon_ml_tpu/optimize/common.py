"""Optimizer state, results, and convergence criteria.

Counterpart of the reference's Optimizer template
(photon-lib optimization/Optimizer.scala:36-249, OptimizerState.scala:35,
util/ConvergenceReason.scala, OptimizationStatesTracker.scala). The JVM
template-method loop becomes: each optimizer is a pure function
`minimize(fun, w0, ...) -> OptResult` built on lax.while_loop, with
convergence encoded as an integer reason code inside the carry so the whole
thing jits and vmaps. State tracking (per-iteration loss/time history kept by
OptimizationStatesTracker) is returned as fixed-size arrays when requested.
"""

from __future__ import annotations

import enum
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array


class ConvergenceReason(enum.IntEnum):
    """Why optimization stopped (reference util/ConvergenceReason.scala).

    Values are stable — they are stored in OptResult arrays.
    """

    NOT_CONVERGED = 0
    MAX_ITERATIONS = 1
    FUNCTION_VALUES_CONVERGED = 2
    GRADIENT_CONVERGED = 3
    OBJECTIVE_NOT_IMPROVING = 4


class OptResult(NamedTuple):
    """Terminal optimizer state (reference OptimizerState + convergenceReason).

    All fields are arrays so a vmapped solve returns per-problem results.
    `loss_history` is all-NaN-padded beyond `iterations` when tracking is on,
    otherwise a zero-length array (reference isTrackingState,
    Optimizer.scala:46-99).
    """

    coefficients: Array
    loss: Array
    gradient_norm: Array
    iterations: Array
    reason: Array  # int32 ConvergenceReason code
    loss_history: Array
    # Full state tracking (reference OptimizationStatesTracker keeps
    # (coefficients, loss, gradient) per iteration; here the per-iteration
    # scalars ride along as fixed-size arrays, NaN beyond `iterations`).
    gradient_norm_history: Optional[Array] = None
    # Total objective-data passes: value+gradient evaluations (L-BFGS: the
    # initial one and one per line-search trial, 1 + iterations + rejected
    # trials; no point is evaluated twice) plus (TRON) Hessian-vector
    # products — each streams the design matrix once on the fused path, so
    # wall-clock / fn_evals is the per-pass cost.
    fn_evals: Optional[Array] = None
    # TRON only (None from a line-search solve): the Hessian-vector products
    # among `fn_evals`, one a CG iteration, rejected steps' included. A TRON
    # solve evaluates value+gradient once before its loop and once a trial
    # step, so `fn_evals == 1 + iterations + rejected steps + hv_evals` and
    # the rejected steps need no count of their own.
    hv_evals: Optional[Array] = None
    # (max_iterations + 1, D) per-iteration coefficient snapshots when
    # track_coefficients is requested (the reference OptimizationStatesTracker
    # keeps full OptimizerStates; here it is an opt-in fixed-size array).
    coefficients_history: Optional[Array] = None
    # TRON-only per-iteration diagnostics under tracking (TRON.scala:217-218
    # logs actual/predicted reduction, trust radius delta and CG count).
    trust_radius_history: Optional[Array] = None
    cg_iterations_history: Optional[Array] = None

    @property
    def converged(self) -> Array:
        return self.reason != ConvergenceReason.NOT_CONVERGED


def check_convergence(
    *,
    loss: Array,
    prev_loss: Array,
    init_loss: Array,
    grad_norm: Array,
    init_grad_norm: Array,
    iteration: Array,
    max_iterations: int,
    tolerance: float,
) -> Array:
    """Reference Optimizer.scala:135-149 convergence tests, as a reason code.

    - FUNCTION_VALUES_CONVERGED: |loss - prev_loss| <= tolerance * |init_loss|
    - GRADIENT_CONVERGED:        ||g||_2 <= tolerance * ||g0||_2
    - MAX_ITERATIONS:            iteration >= max_iterations
    Priority mirrors the reference's check order (function values first).
    """
    dtype = loss.dtype
    tol = jnp.asarray(tolerance, dtype)
    func_conv = jnp.abs(loss - prev_loss) <= tol * jnp.abs(init_loss)
    grad_conv = grad_norm <= tol * init_grad_norm
    reason = jnp.where(
        func_conv,
        ConvergenceReason.FUNCTION_VALUES_CONVERGED,
        jnp.where(
            grad_conv,
            ConvergenceReason.GRADIENT_CONVERGED,
            jnp.where(
                iteration >= max_iterations,
                ConvergenceReason.MAX_ITERATIONS,
                ConvergenceReason.NOT_CONVERGED,
            ),
        ),
    )
    return reason.astype(jnp.int32)


def record_loss(history: Array, iteration: Array, loss: Array) -> Array:
    """Append to the fixed-size loss history if tracking is enabled."""
    if history.shape[0] == 0:
        return history
    return history.at[iteration].set(loss)


def empty_history(max_iterations: int, tracking: bool, dtype) -> Array:
    n = max_iterations + 1 if tracking else 0
    return jnp.full((n,), jnp.nan, dtype=dtype)


def empty_coef_history(max_iterations: int, tracking: bool, w0: Array) -> Array:
    """(max_iterations + 1, D) NaN-filled snapshot buffer with w0 at row 0
    (zero rows when tracking is off)."""
    rows = max_iterations + 1 if tracking else 0
    hist = jnp.full((rows, w0.shape[0]), jnp.nan, w0.dtype)
    return hist.at[0].set(w0) if rows else hist


# Coefficient snapshots use the same guard/record semantics as the scalar
# histories; `record_loss` is rank-agnostic (`.at[iteration].set` works for
# the (rows, D) buffer too).
record_coefficients = record_loss


def safe_div(a: Array, b: Array, eps: float = 0.0) -> Array:
    """a / b with 0 where |b| is (near-)zero — guards CG/line-search ratios."""
    bad = jnp.abs(b) <= eps
    return jnp.where(bad, 0.0, a / jnp.where(bad, 1.0, b))

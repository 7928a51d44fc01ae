"""Trust-region Newton (TRON) as a vmappable JAX kernel.

TPU-native counterpart of photon-lib optimization/TRON.scala:80-339 — itself a
port of LIBLINEAR's TRON (Lin & More, "Newton's method for large-scale
logistic regression"). The algorithm semantics mirror the reference exactly:

  * trust radius initialised to ||g0||  (TRON.scala init)
  * constants (eta0, eta1, eta2) = (1e-4, 0.25, 0.75),
    (sigma1, sigma2, sigma3) = (0.25, 0.5, 4.0)      (TRON.scala:97-98)
  * inner truncated conjugate-gradient solve of the trust-region subproblem,
    max 20 iterations, tolerance 0.1*||g||, with the boundary-crossing
    quadratic solve (TRON.scala:278-338)
  * step acceptance when actual > eta0 * predicted reduction; radius update
    by the four-branch sigma rule; up to `max_failures`=5 consecutive
    rejected steps (TRON.scala:206-262)
  * defaults maxIter=15, tol=1e-5 (TRON.scala:256-262)

Structurally it is one lax.while_loop whose body contains the CG while_loop;
Hessian-vector products come from the caller (for GLMs,
ops.objective.hessian_vector — a pair of matvecs that XLA turns into MXU work
with an ICI all-reduce when the data is sharded). Requires a twice-
differentiable objective, like the reference (TwiceDiffFunction bound).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimize.common import (
    ConvergenceReason,
    OptResult,
    check_convergence,
    empty_coef_history,
    empty_history,
    record_coefficients,
    record_loss,
    safe_div,
)

Array = jax.Array
ValueAndGrad = Callable[[Array], Tuple[Array, Array]]
HessianVector = Callable[[Array, Array], Array]

DEFAULT_MAX_ITERATIONS = 15  # TRON.scala:256-262
DEFAULT_TOLERANCE = 1e-5
DEFAULT_MAX_FAILURES = 5
MAX_CG_ITERATIONS = 20

_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


class _CGCarry(NamedTuple):
    step: Array
    residual: Array
    direction: Array
    rtr: Array
    iteration: Array  # CG iterations = Hessian-vector products executed
    done: Array


def _truncated_cg(
    hvp: Callable[[Array], Array],
    gradient: Array,
    boundary: Array,
) -> Tuple[Array, Array, Array]:
    """Approximately solve min_s g.s + 0.5 s.H.s s.t. ||s|| <= boundary.

    Returns (cg_iterations, step, residual). Mirrors
    TRON.truncatedConjugateGradientMethod (TRON.scala:278-338) including the
    boundary quadratic: when ||s + alpha*d|| crosses the trust radius, solve
    ||s + alpha*d||^2 = boundary^2 for the positive root.

    One Hessian-vector product an iteration and none beside them: the
    residual test that ends the loop is made before the product, as upstream
    makes it, so `cg_iterations` is also the count of passes over the data.
    """
    tol = 0.1 * jnp.linalg.norm(gradient)
    init = _CGCarry(
        step=jnp.zeros_like(gradient),
        residual=-gradient,
        direction=-gradient,
        rtr=jnp.dot(gradient, gradient),
        iteration=jnp.zeros((), jnp.int32),
        done=jnp.zeros((), bool),
    )

    def cond(c: _CGCarry) -> Array:
        return (
            ~c.done
            & (c.iteration < MAX_CG_ITERATIONS)
            & (jnp.linalg.norm(c.residual) > tol)
        )

    def body(c: _CGCarry) -> _CGCarry:
        hd = hvp(c.direction)
        alpha = safe_div(c.rtr, jnp.dot(c.direction, hd))
        step_try = c.step + alpha * c.direction
        crossed = jnp.linalg.norm(step_try) > boundary

        # Boundary case: back off, then advance to the trust-region surface.
        std = jnp.dot(c.step, c.direction)
        sts = jnp.dot(c.step, c.step)
        dtd = jnp.dot(c.direction, c.direction)
        dsq = boundary * boundary
        rad = jnp.sqrt(jnp.maximum(std * std + dtd * (dsq - sts), 0.0))
        alpha_b = jnp.where(
            std >= 0.0, safe_div(dsq - sts, std + rad), safe_div(rad - std, dtd)
        )
        step_bound = c.step + alpha_b * c.direction
        resid_bound = c.residual - alpha_b * hd

        # Interior case: standard CG update.
        resid_in = c.residual - alpha * hd
        rtr_new = jnp.dot(resid_in, resid_in)
        beta = safe_div(rtr_new, c.rtr)
        dir_in = resid_in + beta * c.direction

        return _CGCarry(
            step=jnp.where(crossed, step_bound, step_try),
            residual=jnp.where(crossed, resid_bound, resid_in),
            direction=jnp.where(crossed, c.direction, dir_in),
            rtr=jnp.where(crossed, c.rtr, rtr_new),
            iteration=c.iteration + 1,
            done=crossed,
        )

    out = lax.while_loop(cond, body, init)
    return out.iteration, out.step, out.residual


class _Carry(NamedTuple):
    x: Array
    f: Array
    g: Array
    delta: Array
    iteration: Array
    failures: Array
    reason: Array
    init_f: Array
    init_gnorm: Array
    loss_history: Array
    gnorm_history: Array
    coef_history: Array
    delta_history: Array  # trust radius per iteration (tracking only)
    cg_history: Array  # CG iterations per accepted iteration (tracking)
    evals: Array  # passes over the data: value+gradient evaluations + hv_evals
    hv_evals: Array  # Hessian-vector products, one a CG iteration


@partial(
    jax.jit,
    static_argnames=(
        "value_and_grad_fn",
        "hessian_vector_fn",
        "max_iterations",
        "max_failures",
        "tracking",
        "track_coefficients",
    ),
)
def minimize_tron(
    value_and_grad_fn: ValueAndGrad,
    hessian_vector_fn: HessianVector,
    w0: Array,
    *,
    max_iterations: int = DEFAULT_MAX_ITERATIONS,
    tolerance: float = DEFAULT_TOLERANCE,
    max_failures: int = DEFAULT_MAX_FAILURES,
    tracking: bool = False,
    track_coefficients: bool = False,
) -> OptResult:
    """Minimize with trust-region Newton; `hessian_vector_fn(w, v) -> H(w) v`."""
    # Requesting snapshots implies state tracking (no silent None).
    tracking = tracking or track_coefficients
    dtype = w0.dtype
    f0, g0 = value_and_grad_fn(w0)
    init_gnorm = jnp.linalg.norm(g0)

    history = empty_history(max_iterations, tracking, dtype)
    history = record_loss(history, jnp.zeros((), jnp.int32), f0)
    gnorm_history = empty_history(max_iterations, tracking, dtype)
    gnorm_history = record_loss(gnorm_history, jnp.zeros((), jnp.int32), init_gnorm)
    coef_history = empty_coef_history(max_iterations, track_coefficients, w0)
    delta_history = empty_history(max_iterations, tracking, dtype)
    delta_history = record_loss(delta_history, jnp.zeros((), jnp.int32), init_gnorm)
    cg_history = empty_history(max_iterations, tracking, dtype)

    init = _Carry(
        x=w0,
        f=f0,
        g=g0,
        delta=init_gnorm,  # reference TRON.init: delta = ||g0||
        iteration=jnp.zeros((), jnp.int32),
        failures=jnp.zeros((), jnp.int32),
        reason=jnp.asarray(
            jnp.where(init_gnorm == 0.0, ConvergenceReason.GRADIENT_CONVERGED, 0),
            jnp.int32,
        ),
        init_f=f0,
        init_gnorm=init_gnorm,
        loss_history=history,
        gnorm_history=gnorm_history,
        coef_history=coef_history,
        delta_history=delta_history,
        cg_history=cg_history,
        evals=jnp.ones((), jnp.int32),
        hv_evals=jnp.zeros((), jnp.int32),
    )

    def cond(c: _Carry) -> Array:
        return c.reason == ConvergenceReason.NOT_CONVERGED

    def body(c: _Carry) -> _Carry:
        hvp_calls, step, residual = _truncated_cg(
            lambda v: hessian_vector_fn(c.x, v), c.g, c.delta
        )
        gs = jnp.dot(c.g, step)
        predicted = -0.5 * (gs - jnp.dot(step, residual))
        x_try = c.x + step
        f_try, g_try = value_and_grad_fn(x_try)
        actual = c.f - f_try
        step_norm = jnp.linalg.norm(step)

        # Radius update (TRON.scala:200-214): alpha from the quadratic
        # interpolation of f along the step, then the four-branch rule.
        denom = f_try - c.f - gs
        alpha = jnp.where(
            denom <= 0.0, _SIGMA3, jnp.maximum(_SIGMA1, -0.5 * safe_div(gs, denom))
        )
        delta = jnp.where(
            actual < _ETA0 * predicted,
            jnp.minimum(jnp.maximum(alpha, _SIGMA1) * step_norm, _SIGMA2 * c.delta),
            jnp.where(
                actual < _ETA1 * predicted,
                jnp.maximum(_SIGMA1 * c.delta, jnp.minimum(alpha * step_norm, _SIGMA2 * c.delta)),
                jnp.where(
                    actual < _ETA2 * predicted,
                    jnp.maximum(_SIGMA1 * c.delta, jnp.minimum(alpha * step_norm, _SIGMA3 * c.delta)),
                    jnp.maximum(c.delta, jnp.minimum(alpha * step_norm, _SIGMA3 * c.delta)),
                ),
            ),
        )

        improved = actual > _ETA0 * predicted
        x_new = jnp.where(improved, x_try, c.x)
        f_new = jnp.where(improved, f_try, c.f)
        g_new = jnp.where(improved, g_try, c.g)
        iteration = jnp.where(improved, c.iteration + 1, c.iteration)
        # Failure budget is per accepted step, as in the reference's do-while
        # inside runOneIteration (numImprovementFailure reset each call).
        failures = jnp.where(improved, 0, c.failures + 1)

        reason = check_convergence(
            loss=f_new,
            prev_loss=c.f,
            init_loss=c.init_f,
            grad_norm=jnp.linalg.norm(g_new),
            init_grad_norm=c.init_gnorm,
            iteration=iteration,
            max_iterations=max_iterations,
            tolerance=tolerance,
        )
        # A rejected step must not trigger FUNCTION_VALUES_CONVERGED (loss
        # delta is 0 by construction); keep running unless failures exhausted.
        reason = jnp.where(
            improved,
            reason,
            jnp.where(
                failures >= max_failures,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
            ),
        )

        return _Carry(
            x=x_new,
            f=f_new,
            g=g_new,
            delta=delta,
            iteration=iteration,
            failures=failures,
            reason=reason,
            init_f=c.init_f,
            init_gnorm=c.init_gnorm,
            loss_history=record_loss(c.loss_history, iteration, f_new),
            gnorm_history=record_loss(
                c.gnorm_history, iteration, jnp.linalg.norm(g_new)
            ),
            coef_history=record_coefficients(c.coef_history, iteration, x_new),
            # Diagnostics record only on ACCEPTED steps: a rejected attempt
            # must not clobber slot k's accepted radius/CG count (iteration
            # does not advance on rejection).
            delta_history=jnp.where(
                improved,
                record_loss(c.delta_history, iteration, delta),
                c.delta_history,
            ),
            cg_history=jnp.where(
                improved,
                record_loss(c.cg_history, iteration, hvp_calls.astype(dtype)),
                c.cg_history,
            ),
            evals=c.evals + hvp_calls + 1,
            hv_evals=c.hv_evals + hvp_calls,
        )

    final = lax.while_loop(cond, body, init)
    return OptResult(
        coefficients=final.x,
        loss=final.f,
        gradient_norm=jnp.linalg.norm(final.g),
        iterations=final.iteration,
        reason=final.reason,
        loss_history=final.loss_history,
        gradient_norm_history=final.gnorm_history,
        fn_evals=final.evals,
        hv_evals=final.hv_evals,
        coefficients_history=final.coef_history if final.coef_history.shape[0] else None,
        trust_radius_history=final.delta_history if final.delta_history.shape[0] else None,
        cg_iterations_history=final.cg_history if final.cg_history.shape[0] else None,
    )

"""L-BFGS / OWLQN / box-projected L-BFGS as a single vmappable JAX kernel.

TPU-native counterpart of the reference's Breeze-wrapping optimizers:
  - LBFGS.scala:39-157  (breeze.optimize.LBFGS, maxIter=100, m=10, tol=1e-7;
    post-step projection into box constraints at LBFGS.scala:70-75)
  - OWLQN.scala:40-86   (L1/elastic-net via orthant-wise learning)
  - LBFGSB.scala:40-95  (box constraints; realized here as projected L-BFGS,
    matching the projection the reference applies after every step)

Instead of an iterator of JVM states driving RDD jobs, the whole optimization
is one `lax.while_loop` over a fixed-size carry: circular (s, y) history for
the two-loop recursion, backtracking line search as an inner while_loop, and
integer convergence-reason codes. Because every shape is static, the same
kernel is

  * jitted once for the fixed effect (one big data-parallel problem), and
  * vmapped over entity blocks for random effects — thousands of co-resident
    L-BFGS instances that stop at different iterations via the reason mask
    (the JAX batching rule for while_loop keeps finished lanes frozen).

Evaluations: one value+gradient evaluation before the loop, then one per
line-search trial and none besides. A trial computes the gradient with the
value and the search carries the last trial's out, so the accepted point is
never evaluated again: `fn_evals == 1 + iterations + rejected trials`. A
first trial that is accepted, the common case, is the iteration's only
pass over the data. A rejected trial pays for a gradient it throws away:
nothing on the fused dense kernel, one scatter-add beside the gather on
the sparse paths. Under `vmap` a search runs to its slowest lane's trial
count and every lane pays each trial, so a bucket of thousands of entities
pays the thrown-away gradients of its worst lane (PERF.md section 6, PR 30).

OWLQN mode (l1_weight not None) uses the standard orthant-wise method: the
pseudo-gradient seeds the two-loop recursion, the direction is sign-projected
against it, steps are projected onto the orthant, and the line-search
objective includes the L1 term.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

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

DEFAULT_MAX_ITERATIONS = 100  # LBFGS.scala:152-157
DEFAULT_TOLERANCE = 1e-7
DEFAULT_HISTORY = 10
_CURVATURE_EPS = 1e-10
_MAX_LINE_SEARCH = 30
_ARMIJO_C1 = 1e-4


def _pseudo_gradient(x: Array, g: Array, l1: Array) -> Array:
    """OWLQN pseudo-gradient of f(x) + l1*||x||_1.

    For x_i != 0 the subgradient is g_i + l1*sign(x_i); at x_i == 0 pick the
    direction of steepest descent if one exists, else 0.
    """
    right = g + l1
    left = g - l1
    at_zero = jnp.where(right < 0.0, right, jnp.where(left > 0.0, left, 0.0))
    return jnp.where(x > 0.0, right, jnp.where(x < 0.0, left, at_zero))


class _Carry(NamedTuple):
    x: Array
    f: Array  # objective incl. L1 term in OWLQN mode
    g: Array  # smooth gradient
    pg: Array  # pseudo-gradient (== g in plain mode)
    S: Array  # (m, D) step history
    Y: Array  # (m, D) smooth-gradient-difference history
    rho: Array  # (m,)
    k: Array  # number of history updates so far
    iteration: Array
    reason: Array
    init_f: Array
    init_gnorm: Array
    loss_history: Array
    gnorm_history: Array
    coef_history: Array
    evals: Array  # cumulative value+gradient evaluations: 1 + every trial


def _two_loop(pg: Array, S: Array, Y: Array, rho: Array, k: Array) -> Array:
    """Classic two-loop recursion over a circular (s, y) buffer with masking."""
    m = S.shape[0]
    order = jnp.mod(k - 1 - jnp.arange(m), m)  # newest first
    valid = jnp.arange(m) < jnp.minimum(k, m)

    def loop1(i, carry):
        q, alphas = carry
        j = order[i]
        a = jnp.where(valid[i], rho[j] * jnp.dot(S[j], q), 0.0)
        return q - a * Y[j], alphas.at[i].set(a)

    q, alphas = lax.fori_loop(0, m, loop1, (pg, jnp.zeros((m,), dtype=pg.dtype)))

    newest = jnp.mod(k - 1, m)
    sy = jnp.dot(S[newest], Y[newest])
    yy = jnp.dot(Y[newest], Y[newest])
    gamma = jnp.where(k > 0, safe_div(sy, yy), 1.0)
    gamma = jnp.where(gamma > 0.0, gamma, 1.0)
    r = gamma * q

    def loop2(i, r):
        pos = m - 1 - i  # oldest first
        j = order[pos]
        b = jnp.where(valid[pos], rho[j] * jnp.dot(Y[j], r), 0.0)
        return r + S[j] * jnp.where(valid[pos], alphas[pos] - b, 0.0)

    return lax.fori_loop(0, m, loop2, r)


@partial(
    jax.jit,
    static_argnames=(
        "value_and_grad_fn",
        "max_iterations",
        "history_size",
        "use_l1",
        "use_box",
        "max_line_search",
        "tracking",
        "track_coefficients",
    ),
)
def _minimize(
    value_and_grad_fn: ValueAndGrad,
    w0: Array,
    l1_weight: Array,
    lower: Array,
    upper: Array,
    *,
    max_iterations: int,
    tolerance: float,
    history_size: int,
    use_l1: bool,
    use_box: bool,
    max_line_search: int,
    tracking: bool,
    track_coefficients: bool,
) -> OptResult:
    dtype = w0.dtype
    dim = w0.shape[0]
    m = history_size
    l1 = jnp.asarray(l1_weight, dtype)

    def clip_box(x: Array) -> Array:
        return jnp.clip(x, lower, upper) if use_box else x

    w0 = clip_box(w0)
    f0s, g0 = value_and_grad_fn(w0)
    f0 = f0s + l1 * jnp.sum(jnp.abs(w0)) if use_l1 else f0s
    pg0 = _pseudo_gradient(w0, g0, l1) if use_l1 else g0
    init_gnorm = jnp.linalg.norm(pg0)

    history = empty_history(max_iterations, tracking, dtype)
    history = record_loss(history, jnp.zeros((), jnp.int32), f0)
    gnorm_history = empty_history(max_iterations, tracking, dtype)
    gnorm_history = record_loss(gnorm_history, jnp.zeros((), jnp.int32), init_gnorm)
    coef_history = empty_coef_history(max_iterations, track_coefficients, w0)

    init = _Carry(
        x=w0,
        f=f0,
        g=g0,
        pg=pg0,
        S=jnp.zeros((m, dim), dtype),
        Y=jnp.zeros((m, dim), dtype),
        rho=jnp.zeros((m,), dtype),
        k=jnp.zeros((), jnp.int32),
        iteration=jnp.zeros((), jnp.int32),
        reason=jnp.asarray(
            jnp.where(init_gnorm == 0.0, ConvergenceReason.GRADIENT_CONVERGED, 0),
            jnp.int32,
        ),
        init_f=f0,
        init_gnorm=init_gnorm,
        loss_history=history,
        gnorm_history=gnorm_history,
        coef_history=coef_history,
        evals=jnp.ones((), jnp.int32),
    )

    def cond(c: _Carry) -> Array:
        return c.reason == ConvergenceReason.NOT_CONVERGED

    def body(c: _Carry) -> _Carry:
        d = -_two_loop(c.pg, c.S, c.Y, c.rho, c.k)
        if use_l1:
            # Constrain the direction to the descent orthant of the
            # pseudo-gradient (zero misaligned components).
            d = jnp.where(d * c.pg < 0.0, d, 0.0)
            # Orthant for this step: sign(x), or sign(-pg) where x == 0.
            orthant = jnp.where(c.x != 0.0, jnp.sign(c.x), jnp.sign(-c.pg))

        def take_step(t: Array) -> Array:
            x_new = c.x + t * d
            if use_l1:
                x_new = jnp.where(x_new * orthant >= 0.0, x_new, 0.0)
            return clip_box(x_new)

        t0 = jnp.where(c.k == 0, safe_div(1.0, jnp.linalg.norm(d)), 1.0)
        t0 = jnp.where(t0 > 0.0, t0, 1.0)

        def ls_cond(s):
            t, f_new, x_new, g_new, tries, ok = s
            return (~ok) & (tries < max_line_search)

        def ls_body(s):
            t, _, _, _, tries, _ = s
            x_new = take_step(t)
            # The body's one evaluation: the accepted trial's gradient is
            # the next iterate's, so it leaves the search in the carry.
            f_new, g_new = value_and_grad_fn(x_new)
            if use_l1:
                f_new = f_new + l1 * jnp.sum(jnp.abs(x_new))
            # Armijo on the projected step: f_new <= f + c1 * pg.(x_new - x).
            ok = f_new <= c.f + _ARMIJO_C1 * jnp.dot(c.pg, x_new - c.x)
            ok = ok & jnp.isfinite(f_new)
            return (jnp.where(ok, t, t * 0.5), f_new, x_new, g_new, tries + 1, ok)

        t, f_new, x_new, g_new, ls_tries, ls_ok = lax.while_loop(
            ls_cond,
            ls_body,
            (t0, c.f, c.x, c.g, jnp.zeros((), jnp.int32), jnp.zeros((), bool)),
        )

        pg_new = _pseudo_gradient(x_new, g_new, l1) if use_l1 else g_new

        s_vec = x_new - c.x
        y_vec = g_new - c.g
        sy = jnp.dot(s_vec, y_vec)
        do_update = ls_ok & (sy > _CURVATURE_EPS)
        slot = jnp.mod(c.k, m)
        S = jnp.where(do_update, c.S.at[slot].set(s_vec), c.S)
        Y = jnp.where(do_update, c.Y.at[slot].set(y_vec), c.Y)
        rho = jnp.where(do_update, c.rho.at[slot].set(safe_div(1.0, sy)), c.rho)
        k = jnp.where(do_update, c.k + 1, c.k)

        iteration = c.iteration + 1
        reason = check_convergence(
            loss=f_new,
            prev_loss=c.f,
            init_loss=c.init_f,
            grad_norm=jnp.linalg.norm(pg_new),
            init_grad_norm=c.init_gnorm,
            iteration=iteration,
            max_iterations=max_iterations,
            tolerance=tolerance,
        )
        # Failed line search: no progress possible along any remembered
        # curvature — stop with OBJECTIVE_NOT_IMPROVING (reference
        # ObjectiveNotImproving reason) and keep the previous point.
        reason = jnp.where(
            ls_ok, reason, jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32)
        )
        x_out = jnp.where(ls_ok, x_new, c.x)
        f_out = jnp.where(ls_ok, f_new, c.f)
        g_out = jnp.where(ls_ok, g_new, c.g)
        pg_out = jnp.where(ls_ok, pg_new, c.pg)

        return _Carry(
            x=x_out,
            f=f_out,
            g=g_out,
            pg=pg_out,
            S=S,
            Y=Y,
            rho=rho,
            k=k,
            iteration=iteration,
            reason=reason,
            init_f=c.init_f,
            init_gnorm=c.init_gnorm,
            loss_history=record_loss(c.loss_history, iteration, f_out),
            gnorm_history=record_loss(
                c.gnorm_history, iteration, jnp.linalg.norm(pg_out)
            ),
            coef_history=record_coefficients(c.coef_history, iteration, x_out),
            evals=c.evals + ls_tries,
        )

    final = lax.while_loop(cond, body, init)
    return OptResult(
        coefficients=final.x,
        loss=final.f,
        gradient_norm=jnp.linalg.norm(final.pg),
        iterations=final.iteration,
        reason=final.reason,
        loss_history=final.loss_history,
        gradient_norm_history=final.gnorm_history,
        fn_evals=final.evals,
        coefficients_history=final.coef_history if final.coef_history.shape[0] else None,
    )


def minimize_lbfgs(
    value_and_grad_fn: ValueAndGrad,
    w0: Array,
    *,
    max_iterations: int = DEFAULT_MAX_ITERATIONS,
    tolerance: float = DEFAULT_TOLERANCE,
    history_size: int = DEFAULT_HISTORY,
    l1_weight: Optional[float | Array] = None,
    lower_bounds: Optional[Array] = None,
    upper_bounds: Optional[Array] = None,
    max_line_search: int = _MAX_LINE_SEARCH,
    tracking: bool = False,
    track_coefficients: bool = False,
) -> OptResult:
    """Minimize `value_and_grad_fn` (smooth part) from `w0`.

    - `l1_weight` not None => OWLQN mode (reference OWLQN.scala); the weight
      itself may be a traced scalar (the reference mutates l1RegWeight across
      the regularization sweep the same way).
    - `lower_bounds`/`upper_bounds` => projected L-BFGS (reference
      LBFGS.scala:70-75 / LBFGSB).
    The function is jittable and vmappable; `value_and_grad_fn` must be pure.
    """
    use_box = lower_bounds is not None or upper_bounds is not None
    dtype = w0.dtype
    neg_inf = jnp.full_like(w0, -jnp.inf)
    pos_inf = jnp.full_like(w0, jnp.inf)
    lower = jnp.asarray(lower_bounds, dtype) if lower_bounds is not None else neg_inf
    upper = jnp.asarray(upper_bounds, dtype) if upper_bounds is not None else pos_inf
    use_l1 = l1_weight is not None
    l1 = jnp.asarray(0.0 if l1_weight is None else l1_weight, dtype)
    return _minimize(
        value_and_grad_fn,
        w0,
        l1,
        lower,
        upper,
        max_iterations=max_iterations,
        tolerance=tolerance,
        history_size=history_size,
        use_l1=use_l1,
        use_box=use_box,
        max_line_search=max_line_search,
        # Requesting snapshots implies state tracking (no silent None).
        tracking=tracking or track_coefficients,
        track_coefficients=track_coefficients,
    )

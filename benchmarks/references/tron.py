"""Plain trust-region Newton (TRON), written from the configuration's statement of it.

The statement (LIBLINEAR `-s 0`, Lin, Weng and Keerthi, JMLR 9, 2008, in the
form Photon ML's `TRON.scala` ports it; the configuration's `assumed` gives
each constant with its line):

* the trust radius starts at |g0|;
* a trial step s solves  min g.s + s.H.s / 2  within |s| <= radius by
  conjugate gradients from s = 0, at most 20 iterations, stopped before an
  iteration whose residual is already <= 0.1 |g|; an iterate that would leave
  the region is pulled back onto its surface along the direction, which ends
  the solve;
* predicted reduction = -(g.s - s.r) / 2 with r the CG's last residual,
  actual = f(w) - f(w + s); the step is taken when actual > 1e-4 predicted;
* the radius follows the four-branch rule with (eta1, eta2) = (0.25, 0.75),
  (sigma1, sigma2, sigma3) = (0.25, 0.5, 4) and alpha from the quadratic
  interpolation of f along s;
* stop after a taken step when |f - f_prev| <= tol |f0|, or |g| <= tol |g0|,
  or the limit of taken steps; give up after 5 refused steps in a row.

Departure from LIBLINEAR's `tron.cpp`, noted because the configuration
states it: the radius is NOT cut to the first step's norm on the first
iteration. `info["boundary_steps"]` counts the trial steps that ended on the
region's surface, so a run shows whether the region ever bound.

`objective(w)` returns (f, g, state) and `hessian_vector(state, v)` returns
H(w) v from the state the objective left at w (LIBLINEAR keeps the curvature
weights D of its last gradient so). One problem, Python loops, float32
scalars on the device compared on the host, no program code.
"""

import jax.numpy as jnp

MAX_CG_ITERATIONS = 20
CG_TOLERANCE = 0.1
MAX_REFUSED = 5
ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA2, SIGMA3 = 0.25, 0.5, 4.0


def _truncated_cg(hv, g, radius):
    """(iterations, step, residual, ended on the boundary)."""
    step = jnp.zeros_like(g)
    residual = -g
    direction = residual
    rtr = jnp.dot(residual, residual)
    tolerance = CG_TOLERANCE * jnp.linalg.norm(g)
    iterations = 0
    while iterations < MAX_CG_ITERATIONS and bool(jnp.linalg.norm(residual) > tolerance):
        iterations += 1
        hd = hv(direction)
        alpha = rtr / jnp.dot(direction, hd)
        tried = step + alpha * direction
        if bool(jnp.linalg.norm(tried) > radius):
            # The positive root of |step + a direction|^2 = radius^2, in the
            # form that does not cancel (tron.cpp's two cases by the sign of s.d).
            sd = jnp.dot(step, direction)
            ss = jnp.dot(step, step)
            dd = jnp.dot(direction, direction)
            gap = radius * radius - ss
            root = jnp.sqrt(jnp.maximum(sd * sd + dd * gap, 0.0))
            a = jnp.where(sd >= 0.0, gap / (sd + root), (root - sd) / dd)
            return iterations, step + a * direction, residual - a * hd, True
        step = tried
        residual = residual - alpha * hd
        rtr_new = jnp.dot(residual, residual)
        direction = residual + (rtr_new / rtr) * direction
        rtr = rtr_new
    return iterations, step, residual, False


def minimize(objective, hessian_vector, w0, *, max_iterations, tolerance):
    """(w, info). `info`: taken steps (`iterations`), value+gradient
    `evaluations`, `hessian_vector_products`, `refused` steps, the CG
    iterations of every trial step in order with whether it was taken
    (`cg_iterations`, `taken`), `boundary_steps`, and per taken step the
    actual reduction and the value it reached (`reductions`, `values`)."""
    w = w0
    f, g, state = objective(w)
    f0, g0_norm = f, jnp.linalg.norm(g)
    radius = g0_norm
    info = {
        "iterations": 0, "evaluations": 1, "hessian_vector_products": 0, "refused": 0,
        "cg_iterations": [], "taken": [], "boundary_steps": 0, "reductions": [], "values": [float(f)],
    }
    refused_in_a_row = 0
    while bool(g0_norm > 0.0):
        cg, step, residual, on_boundary = _truncated_cg(
            lambda v: hessian_vector(state, v), g, radius
        )
        info["hessian_vector_products"] += cg
        info["cg_iterations"].append(cg)
        info["boundary_steps"] += int(on_boundary)
        gs = jnp.dot(g, step)
        predicted = -0.5 * (gs - jnp.dot(step, residual))
        w_try = w + step
        f_try, g_try, state_try = objective(w_try)
        info["evaluations"] += 1
        actual = f - f_try
        step_norm = jnp.linalg.norm(step)
        curvature = f_try - f - gs
        alpha = (
            SIGMA3 if bool(curvature <= 0.0)
            else jnp.maximum(SIGMA1, -0.5 * (gs / curvature))
        )
        if bool(actual < ETA0 * predicted):
            radius = jnp.minimum(jnp.maximum(alpha, SIGMA1) * step_norm, SIGMA2 * radius)
        elif bool(actual < ETA1 * predicted):
            radius = jnp.maximum(SIGMA1 * radius, jnp.minimum(alpha * step_norm, SIGMA2 * radius))
        elif bool(actual < ETA2 * predicted):
            radius = jnp.maximum(SIGMA1 * radius, jnp.minimum(alpha * step_norm, SIGMA3 * radius))
        else:
            radius = jnp.maximum(radius, jnp.minimum(alpha * step_norm, SIGMA3 * radius))
        taken = bool(actual > ETA0 * predicted)
        info["taken"].append(taken)
        if not taken:
            info["refused"] += 1
            refused_in_a_row += 1
            if refused_in_a_row >= MAX_REFUSED:
                break
            continue
        refused_in_a_row = 0
        f_prev = f
        w, f, g, state = w_try, f_try, g_try, state_try
        info["iterations"] += 1
        info["reductions"].append(float(actual))
        info["values"].append(float(f))
        if (
            bool(jnp.abs(f - f_prev) <= tolerance * jnp.abs(f0))
            or bool(jnp.linalg.norm(g) <= tolerance * g0_norm)
            or info["iterations"] >= max_iterations
        ):
            break
    return w, info

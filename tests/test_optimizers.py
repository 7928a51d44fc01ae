"""Optimizer tests: convergence on analytic + GLM problems, OWLQN sparsity,
box projection, TRON vs LBFGS agreement, and vmapped batched solves.

Counterpart of the reference's OptimizerIntegTest / IntegTestObjective
(photon-lib src/integTest/.../optimization): analytic objectives with known
optima, plus sklearn as an external oracle for logistic regression.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.containers import dense_data
from photon_ml_tpu.ops import losses, objective
from photon_ml_tpu.optimize import lbfgs as lbfgs_module
from photon_ml_tpu.optimize.common import ConvergenceReason, check_convergence
from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs
from photon_ml_tpu.optimize.tron import minimize_tron


def _quadratic(center, scale=1.0):
    c = jnp.asarray(center)

    def vg(w):
        diff = w - c
        return 0.5 * scale * jnp.dot(diff, diff), scale * diff

    return vg


def _rosenbrock_vg(w):
    f = lambda x: jnp.sum(100.0 * (x[1:] - x[:-1] ** 2) ** 2 + (1.0 - x[:-1]) ** 2)
    return f(w), jax.grad(f)(w)


def _logistic_problem(rng, n=200, d=8, l2=1e-3):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    data = dense_data(X, y)
    vg = lambda w: objective.value_and_gradient(losses.LOGISTIC, w, data, None, l2)
    hvp = lambda w, v: objective.hessian_vector(losses.LOGISTIC, w, v, data, None, l2)
    return data, vg, hvp


def test_lbfgs_quadratic():
    center = jnp.arange(5.0, dtype=jnp.float32)
    res = minimize_lbfgs(_quadratic(center), jnp.zeros(5, jnp.float32))
    np.testing.assert_allclose(res.coefficients, center, atol=1e-4)
    assert int(res.reason) in (
        ConvergenceReason.FUNCTION_VALUES_CONVERGED,
        ConvergenceReason.GRADIENT_CONVERGED,
    )
    assert int(res.iterations) < 10


def test_lbfgs_rosenbrock():
    res = minimize_lbfgs(
        _rosenbrock_vg, jnp.zeros(4, jnp.float32), max_iterations=300, tolerance=1e-10
    )
    np.testing.assert_allclose(res.coefficients, jnp.ones(4), atol=2e-2)


def test_lbfgs_logistic_matches_sklearn(rng):
    from sklearn.linear_model import LogisticRegression

    n, d, l2 = 200, 8, 1e-2
    _, vg, _ = _logistic_problem(rng, n, d, l2)
    # Rebuild the same data for sklearn (regenerate with same seed path).
    rng2 = np.random.default_rng(20260729)
    X = rng2.normal(size=(n, d)).astype(np.float32)
    w_true = rng2.normal(size=d).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng2.uniform(size=n) < p).astype(np.float32)

    res = minimize_lbfgs(vg, jnp.zeros(d, jnp.float32), tolerance=1e-9)
    skl = LogisticRegression(
        C=1.0 / l2, fit_intercept=False, tol=1e-10, max_iter=2000
    ).fit(X, y)
    np.testing.assert_allclose(res.coefficients, skl.coef_[0], rtol=2e-2, atol=2e-3)


def test_owlqn_produces_sparse_solution(rng):
    _, vg, _ = _logistic_problem(rng, n=150, d=20, l2=0.0)
    dense_res = minimize_lbfgs(vg, jnp.zeros(20, jnp.float32))
    sparse_res = minimize_lbfgs(vg, jnp.zeros(20, jnp.float32), l1_weight=8.0)
    n_zero_dense = int(jnp.sum(jnp.abs(dense_res.coefficients) < 1e-8))
    n_zero_sparse = int(jnp.sum(jnp.abs(sparse_res.coefficients) < 1e-8))
    assert n_zero_sparse > n_zero_dense
    assert n_zero_sparse >= 5
    # The OWLQN objective value (smooth + L1) must beat the L1 value of the
    # dense solution.
    l1_of = lambda w: 8.0 * float(jnp.sum(jnp.abs(w)))
    f_sparse = float(vg(sparse_res.coefficients)[0]) + l1_of(sparse_res.coefficients)
    f_dense = float(vg(dense_res.coefficients)[0]) + l1_of(dense_res.coefficients)
    assert f_sparse <= f_dense + 1e-3


def test_owlqn_zero_l1_close_to_lbfgs(rng):
    _, vg, _ = _logistic_problem(rng, n=100, d=6, l2=1e-2)
    a = minimize_lbfgs(vg, jnp.zeros(6, jnp.float32), tolerance=1e-9)
    b = minimize_lbfgs(vg, jnp.zeros(6, jnp.float32), l1_weight=0.0, tolerance=1e-9)
    np.testing.assert_allclose(a.coefficients, b.coefficients, atol=5e-3)


def test_box_constraints():
    center = jnp.asarray([2.0, -3.0, 0.5], jnp.float32)
    res = minimize_lbfgs(
        _quadratic(center),
        jnp.zeros(3, jnp.float32),
        lower_bounds=jnp.asarray([-1.0, -1.0, -1.0], jnp.float32),
        upper_bounds=jnp.asarray([1.0, 1.0, 1.0], jnp.float32),
    )
    np.testing.assert_allclose(res.coefficients, [1.0, -1.0, 0.5], atol=1e-4)


def test_tron_quadratic():
    center = jnp.arange(4.0, dtype=jnp.float32)
    vg = _quadratic(center, scale=2.0)
    hvp = lambda w, v: 2.0 * v
    res = minimize_tron(vg, hvp, jnp.zeros(4, jnp.float32))
    np.testing.assert_allclose(res.coefficients, center, atol=1e-4)
    # Newton on a quadratic: one step.
    assert int(res.iterations) <= 3


def test_tron_matches_lbfgs_on_logistic(rng):
    _, vg, hvp = _logistic_problem(rng, l2=0.1)
    a = minimize_tron(vg, hvp, jnp.zeros(8, jnp.float32), tolerance=1e-9)
    b = minimize_lbfgs(vg, jnp.zeros(8, jnp.float32), tolerance=1e-9)
    np.testing.assert_allclose(a.coefficients, b.coefficients, rtol=5e-3, atol=5e-4)
    assert int(a.iterations) <= 15


def test_vmapped_lbfgs_batched_problems(rng):
    """Many independent problems in one kernel — the random-effect pattern."""
    B, d = 16, 4
    centers = jnp.asarray(rng.normal(size=(B, d)).astype(np.float32))

    def one(w0, center):
        vg = lambda w: (
            0.5 * jnp.dot(w - center, w - center),
            w - center,
        )
        return minimize_lbfgs(vg, w0)

    res = jax.vmap(one)(jnp.zeros((B, d), jnp.float32), centers)
    np.testing.assert_allclose(res.coefficients, centers, atol=1e-3)
    assert res.reason.shape == (B,)
    assert bool(jnp.all(res.reason != ConvergenceReason.NOT_CONVERGED))


def test_vmapped_tron_batched_glms(rng):
    """vmapped TRON over per-entity GLM blocks with padding rows."""
    B, n, d = 8, 30, 3
    X = rng.normal(size=(B, n, d)).astype(np.float32)
    w_true = rng.normal(size=(B, d)).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-np.einsum("bnd,bd->bn", X, w_true)))
    y = (rng.uniform(size=(B, n)) < p).astype(np.float32)
    weights = np.ones((B, n), np.float32)
    weights[:, 25:] = 0.0  # simulate ragged entities via padding

    def solve(Xb, yb, wb):
        data = dense_data(Xb, yb, weights=wb)
        vg = lambda w: objective.value_and_gradient(losses.LOGISTIC, w, data, None, 0.5)
        hvp = lambda w, v: objective.hessian_vector(losses.LOGISTIC, w, v, data, None, 0.5)
        return minimize_tron(vg, hvp, jnp.zeros(d, jnp.float32))

    res = jax.vmap(solve)(jnp.asarray(X), jnp.asarray(y), jnp.asarray(weights))
    assert res.coefficients.shape == (B, d)
    # Each batched solution must match its individually-solved counterpart.
    single = solve(jnp.asarray(X[0]), jnp.asarray(y[0]), jnp.asarray(weights[0]))
    np.testing.assert_allclose(res.coefficients[0], single.coefficients, atol=1e-4)


def test_tracking_records_monotone_losses(rng):
    _, vg, _ = _logistic_problem(rng)
    res = minimize_lbfgs(vg, jnp.zeros(8, jnp.float32), tracking=True)
    hist = np.asarray(res.loss_history)
    valid = hist[~np.isnan(hist)]
    assert len(valid) == int(res.iterations) + 1
    assert np.all(np.diff(valid) <= 1e-5)  # non-increasing losses


def test_coefficient_history_tracking():
    """Opt-in per-iteration coefficient snapshots (the reference
    OptimizationStatesTracker keeps full OptimizerStates)."""
    A = jnp.asarray(np.diag([1.0, 4.0, 9.0]), jnp.float32)
    b = jnp.asarray([1.0, -2.0, 3.0], jnp.float32)

    def vg(w):
        r = A @ w - b
        return 0.5 * jnp.dot(r, A @ w - b), A.T @ r

    res = minimize_lbfgs(vg, jnp.zeros(3, jnp.float32), tracking=True,
                         track_coefficients=True, max_iterations=20)
    hist = np.asarray(res.coefficients_history)
    its = int(res.iterations)
    assert hist.shape == (21, 3)
    np.testing.assert_array_equal(hist[0], 0.0)  # w0 snapshot
    np.testing.assert_allclose(hist[its], np.asarray(res.coefficients), rtol=1e-6)
    assert np.all(np.isnan(hist[its + 1:]))  # untouched rows stay NaN

    res_t = minimize_tron(vg, lambda w, v: A.T @ (A @ v),
                          jnp.zeros(3, jnp.float32), tracking=True,
                          track_coefficients=True, max_iterations=10)
    hist_t = np.asarray(res_t.coefficients_history)
    np.testing.assert_allclose(
        hist_t[int(res_t.iterations)], np.asarray(res_t.coefficients), rtol=1e-6
    )
    # Off by default: no history allocated.
    res_off = minimize_lbfgs(vg, jnp.zeros(3, jnp.float32), tracking=True)
    assert res_off.coefficients_history is None
    # track_coefficients alone implies tracking (no silent None).
    res_imp = minimize_lbfgs(vg, jnp.zeros(3, jnp.float32), track_coefficients=True)
    assert res_imp.coefficients_history is not None
    assert res_imp.loss_history.shape[0] > 0


def test_tron_diagnostic_histories():
    """TRON per-iteration trust radius + CG counts under tracking
    (TRON.scala:217-218's per-iteration log line, as returned arrays)."""
    A = jnp.asarray(np.diag([1.0, 4.0, 9.0]), jnp.float32)
    b = jnp.asarray([1.0, -2.0, 3.0], jnp.float32)

    def vg(w):
        r = A @ w - b
        return 0.5 * jnp.dot(r, r), A.T @ r

    res = minimize_tron(vg, lambda w, v: A.T @ (A @ v),
                        jnp.zeros(3, jnp.float32), tracking=True,
                        max_iterations=10)
    its = int(res.iterations)
    deltas = np.asarray(res.trust_radius_history)
    cgs = np.asarray(res.cg_iterations_history)
    assert deltas.shape == (11,) and cgs.shape == (11,)
    assert np.all(deltas[: its + 1] > 0)  # radius stays positive
    assert np.all(cgs[1 : its + 1] >= 1)  # every accepted step ran CG
    assert np.all(np.isnan(deltas[its + 1:]))
    # Off when not tracking.
    res2 = minimize_tron(vg, lambda w, v: A.T @ (A @ v), jnp.zeros(3, jnp.float32))
    assert res2.trust_radius_history is None


def test_tron_rejected_steps_preserve_diagnostics():
    """A rejected trust-region attempt must not overwrite the accepted
    history slots (iteration does not advance on rejection)."""
    # Highly non-quadratic scalar-ish objective that forces rejections: the
    # Newton model overshoots for exp-sum curvature far from the optimum.
    def vg(w):
        z = jnp.sum(jnp.exp(2.0 * w))
        return z, 2.0 * jnp.exp(2.0 * w)

    def hvp(w, v):
        return 4.0 * jnp.exp(2.0 * w) * v

    w0 = jnp.full((4,), 3.0, jnp.float32)
    res = minimize_tron(vg, hvp, w0, max_iterations=30, tolerance=1e-10,
                        tracking=True)
    its = int(res.iterations)
    deltas = np.asarray(res.trust_radius_history)
    cgs = np.asarray(res.cg_iterations_history)
    # Slot 0 keeps the INITIAL radius (||g0||) and the NaN cg sentinel even
    # if the very first attempt was rejected.
    g0 = float(np.linalg.norm(2.0 * np.exp(2.0 * np.full(4, 3.0))))
    assert deltas[0] == pytest.approx(g0, rel=1e-5)
    assert np.isnan(cgs[0])
    assert np.all(cgs[1 : its + 1] >= 1)


# -- one objective evaluation an iteration (ISSUE 30) -------------------------
# The line search evaluates value and gradient at each trial and hands the
# accepted trial's gradient on; no point is evaluated twice.


def _old_loop(vg, w0, *, max_iterations=100, tolerance=1e-7, l1=None, lower=None,
              upper=None, max_line_search=30):
    """The loop as it stood before ISSUE 30, in eager Python: a value at each
    trial, then value and gradient again at the point the search accepted. It
    is the statement of the iterates; the pieces ISSUE 30 left alone (two-loop
    recursion, pseudo-gradient, convergence test) are the module's own."""
    m = lbfgs_module.DEFAULT_HISTORY
    use_l1 = l1 is not None
    l1v = jnp.float32(0.0 if l1 is None else l1)
    use_box = lower is not None or upper is not None
    lo = -jnp.inf if lower is None else jnp.asarray(lower, jnp.float32)
    hi = jnp.inf if upper is None else jnp.asarray(upper, jnp.float32)
    clip = (lambda x: jnp.clip(x, lo, hi)) if use_box else (lambda x: x)
    total = lambda x, f: f + l1v * jnp.sum(jnp.abs(x)) if use_l1 else f
    pseudo = lambda x, g: lbfgs_module._pseudo_gradient(x, g, l1v) if use_l1 else g

    x = clip(jnp.asarray(w0, jnp.float32))
    f_smooth, g = vg(x)
    f, pg = total(x, f_smooth), pseudo(x, g)
    f0, gnorm0 = f, jnp.linalg.norm(pg)
    S = jnp.zeros((m, x.shape[0]), jnp.float32)
    Y, rho, k = S, jnp.zeros((m,), jnp.float32), 0
    xs, losses, trials, old_evals = [x], [f], [], 1
    reason = ConvergenceReason.GRADIENT_CONVERGED if float(gnorm0) == 0.0 else 0
    iteration = 0
    while reason == ConvergenceReason.NOT_CONVERGED:
        d = -lbfgs_module._two_loop(pg, S, Y, rho, jnp.int32(k))
        if use_l1:
            d = jnp.where(d * pg < 0.0, d, 0.0)
            orthant = jnp.where(x != 0.0, jnp.sign(x), jnp.sign(-pg))
        t = 1.0 / float(jnp.linalg.norm(d)) if k == 0 and float(jnp.linalg.norm(d)) > 0 else 1.0
        ok = False
        trials.append(0)
        for _ in range(max_line_search):
            x_new = x + jnp.float32(t) * d
            if use_l1:
                x_new = jnp.where(x_new * orthant >= 0.0, x_new, 0.0)
            x_new = clip(x_new)
            f_new = total(x_new, vg(x_new)[0])
            trials[-1] += 1
            old_evals += 1
            ok = bool(f_new <= f + 1e-4 * jnp.dot(pg, x_new - x)) and bool(jnp.isfinite(f_new))
            if ok:
                break
            t *= 0.5
        _, g_new = vg(x_new)
        old_evals += 1
        pg_new = pseudo(x_new, g_new)
        s_vec, y_vec = x_new - x, g_new - g
        sy = float(jnp.dot(s_vec, y_vec))
        if ok and sy > 1e-10:
            slot = k % m
            S, Y, rho = S.at[slot].set(s_vec), Y.at[slot].set(y_vec), rho.at[slot].set(1.0 / sy)
            k += 1
        iteration += 1
        reason = int(
            check_convergence(
                loss=f_new, prev_loss=f, init_loss=f0, grad_norm=jnp.linalg.norm(pg_new),
                init_grad_norm=gnorm0, iteration=iteration, max_iterations=max_iterations,
                tolerance=tolerance,
            )
        )
        if ok:
            x, f, g, pg = x_new, f_new, g_new, pg_new
        else:
            reason = int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING)
        xs.append(x)
        losses.append(f)
    return dict(
        xs=np.stack([np.asarray(v) for v in xs]),
        losses=np.asarray([float(v) for v in losses]),
        iterations=iteration,
        reason=int(reason),
        trials=sum(trials),
        old_evals=old_evals,
    )


def _assert_same_run(res, old, rtol=2e-5, atol=2e-6):
    """Iterates, loss history, reason and the count of one `OptResult` against
    the old loop's record of the same problem."""
    its = old["iterations"]
    assert int(res.iterations) == its
    assert int(res.reason) == old["reason"]
    np.testing.assert_allclose(
        np.asarray(res.coefficients_history)[: its + 1], old["xs"], rtol=rtol, atol=atol
    )
    np.testing.assert_allclose(
        np.asarray(res.loss_history)[: its + 1], old["losses"], rtol=rtol, atol=atol
    )
    np.testing.assert_allclose(res.coefficients, old["xs"][-1], rtol=rtol, atol=atol)
    # The same trials, and no evaluation besides them and the first: the old
    # loop made one more an iteration. (Every search here moves the point: a
    # projected step that moves nothing halves until float32 rounding lets it
    # pass, a count that differs between compiled and eager, so the box cases
    # stop on an iteration limit before their last, motionless search.)
    assert np.all(np.diff(old["losses"]) < 0.0)
    assert int(res.fn_evals) == 1 + old["trials"]
    assert old["old_evals"] == int(res.fn_evals) + its


def _ill_scaled_quadratic(d=6):
    """A quadratic whose unit first step overshoots: the search backtracks."""
    scales = jnp.asarray(np.geomspace(1.0, 400.0, d), jnp.float32)
    center = jnp.asarray(np.linspace(0.05, 0.3, d), jnp.float32)

    def vg(w):
        diff = w - center
        return 0.5 * jnp.sum(scales * diff * diff), scales * diff

    return vg


def test_a_well_conditioned_fit_evaluates_once_an_iteration(rng):
    _, vg, _ = _logistic_problem(rng, l2=1.0)
    res = minimize_lbfgs(vg, jnp.zeros(8, jnp.float32), max_iterations=5, tolerance=1e-12)
    assert int(res.iterations) == 5 and int(res.reason) == ConvergenceReason.MAX_ITERATIONS
    assert int(res.fn_evals) == 1 + 5


@pytest.mark.parametrize("problem", ["rosenbrock", "ill_scaled_quadratic"])
def test_rejected_trials_are_the_only_other_evaluations(problem):
    """Every execution of the objective is seen by a host callback: those at
    points that never became an iterate are the rejected trials."""
    inner = _rosenbrock_vg if problem == "rosenbrock" else _ill_scaled_quadratic(4)
    seen = []

    def instrumented(w):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), w)
        return inner(w)

    res = minimize_lbfgs(
        instrumented, jnp.zeros(4, jnp.float32), max_iterations=40, tolerance=1e-10,
        track_coefficients=True,
    )
    jax.block_until_ready(res)
    jax.effects_barrier()
    its = int(res.iterations)
    iterates = {np.asarray(x).tobytes() for x in np.asarray(res.coefficients_history)[: its + 1]}
    rejected = sum(1 for x in seen if x.tobytes() not in iterates)
    assert rejected > 0
    assert len(seen) == int(res.fn_evals) == 1 + its + rejected


def _holders(jaxpr, name, path=()):
    """Paths (enclosing primitives and the parameter that holds the sub-jaxpr)
    of every call of the jitted function `name` inside `jaxpr`."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.params.get("name") == name:
            found.append(path)
            continue
        for key, value in eqn.params.items():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    step = eqn.params.get("name", eqn.primitive.name)
                    found += _holders(sub, name, path + (f"{step}:{key}",))
    return found


@pytest.mark.parametrize("mode", ["plain", "owlqn", "box"])
def test_the_traced_solve_holds_the_objective_twice(mode):
    """Once before the loop, once in the line-search body, nowhere else."""

    @jax.jit
    def marked_objective(w):
        return _rosenbrock_vg(w)

    options = {
        "plain": {},
        "owlqn": {"l1_weight": 0.1},
        "box": {"lower_bounds": jnp.full(4, -0.5), "upper_bounds": jnp.full(4, 0.5)},
    }[mode]
    closed = jax.make_jaxpr(
        lambda w: minimize_lbfgs(marked_objective, w, max_iterations=7, **options)
    )(jnp.zeros(4, jnp.float32))
    holders = sorted(_holders(closed.jaxpr, "marked_objective"))
    assert len(holders) == 2
    before_loop, in_search = holders
    assert before_loop == ("_minimize:jaxpr",)
    assert in_search == ("_minimize:jaxpr", "while:body_jaxpr", "while:body_jaxpr")


_MODES = {
    "plain": {},
    "owlqn": {"l1": 0.05},
    "box": {"lower": -np.ones(8, np.float32), "upper": np.ones(8, np.float32), "max_iterations": 3},
    "owlqn_box": {
        "l1": 0.02,
        "lower": -np.ones(8, np.float32),
        "upper": np.full(8, np.inf, np.float32),
        "max_iterations": 3,
    },
}


def _solve(vg, w0, *, l1=None, lower=None, upper=None, **kw):
    return minimize_lbfgs(
        vg, w0, l1_weight=l1, lower_bounds=lower, upper_bounds=upper,
        tracking=True, track_coefficients=True, **kw,
    )


@pytest.mark.parametrize("mode", sorted(_MODES))
def test_iterates_equal_the_old_loops_on_a_logistic_problem(rng, mode):
    _, vg, _ = _logistic_problem(rng, l2=1e-2)
    kw = {"max_iterations": 25, "tolerance": 1e-5, **_MODES[mode]}
    res = _solve(vg, jnp.zeros(8, jnp.float32), **kw)
    old = _old_loop(vg, jnp.zeros(8, jnp.float32), **kw)
    assert old["iterations"] >= 3
    _assert_same_run(res, old)


@pytest.mark.parametrize("mode", ["plain", "owlqn", "box"])
def test_iterates_equal_the_old_loops_where_the_search_backtracks(mode):
    options = {
        "plain": {},
        "owlqn": {"l1": 0.5},
        "box": {"lower": np.full(6, -1.0, np.float32), "upper": np.full(6, 0.25, np.float32),
                "max_iterations": 6},
    }[mode]
    vg = _ill_scaled_quadratic()
    kw = {"max_iterations": 30, "tolerance": 1e-5, **options}
    res = _solve(vg, jnp.zeros(6, jnp.float32), **kw)
    old = _old_loop(vg, jnp.zeros(6, jnp.float32), **kw)
    assert old["trials"] > old["iterations"]  # some trial was rejected
    _assert_same_run(res, old)


def test_the_plain_solve_equals_the_benchmarks_reference(rng):
    from benchmarks.references import lbfgs as reference

    _, vg, _ = _logistic_problem(rng, l2=1.0)
    res = minimize_lbfgs(vg, jnp.zeros(8, jnp.float32), max_iterations=6, tolerance=1e-12)
    x_ref, info = reference.minimize(
        jax.vmap(vg), jnp.zeros((1, 8), jnp.float32), max_iterations=6, tolerance=1e-12
    )
    np.testing.assert_allclose(res.coefficients, x_ref[0], rtol=2e-5, atol=2e-6)
    assert info == {"iterations": int(res.iterations), "evaluations": int(res.fn_evals)}


def test_vmapped_lanes_keep_their_own_gradient_when_one_backtracks():
    """Lane 2 rejects trials while the others accept their first: each lane
    ends where it ends alone, with its own count."""
    d = 6
    scales = jnp.asarray(
        np.stack([np.ones(d), np.full(d, 2.0), np.geomspace(1.0, 400.0, d), np.full(d, 0.5)]),
        jnp.float32,
    )
    centers = jnp.asarray(
        np.stack([np.linspace(1, 2, d), np.linspace(-2, 1, d), np.linspace(0.05, 0.3, d),
                  np.linspace(3, -3, d)]),
        jnp.float32,
    )

    def one(scale, center):
        def vg(w):
            diff = w - center
            return 0.5 * jnp.sum(scale * diff * diff), scale * diff

        return vg

    kw = dict(max_iterations=30, tolerance=1e-9)
    batched = jax.vmap(lambda s, c: _solve(one(s, c), jnp.zeros(d, jnp.float32), **kw))(
        scales, centers
    )
    rejected = np.asarray(batched.fn_evals) - 1 - np.asarray(batched.iterations)
    assert rejected[2] > 0 and not rejected[[0, 1, 3]].any()
    for lane in range(4):
        vg = one(scales[lane], centers[lane])
        res = jax.tree_util.tree_map(lambda a: a[lane], batched)
        _assert_same_run(res, _old_loop(vg, jnp.zeros(d, jnp.float32), **kw))
        alone = _solve(vg, jnp.zeros(d, jnp.float32), **kw)
        assert int(alone.fn_evals) == int(res.fn_evals)
        assert int(alone.iterations) == int(res.iterations)
        np.testing.assert_allclose(res.coefficients, alone.coefficients, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("l1", [None, 0.05])
def test_a_failed_search_keeps_the_previous_point_and_its_gradients(l1):
    """With one trial a search, Rosenbrock's first rejected trial ends the
    solve: the result is the iterate before it, with that iterate's loss and
    (pseudo-)gradient, not the rejected trial's."""
    res = minimize_lbfgs(
        _rosenbrock_vg, jnp.full(4, -1.2, jnp.float32), max_iterations=50, tolerance=1e-12,
        l1_weight=l1, max_line_search=1, tracking=True, track_coefficients=True,
    )
    its = int(res.iterations)
    assert int(res.reason) == ConvergenceReason.OBJECTIVE_NOT_IMPROVING
    assert its >= 2  # it failed after real progress, not at the start
    assert int(res.fn_evals) == 1 + its  # one trial an iteration, the failed one too
    hist = np.asarray(res.coefficients_history)
    np.testing.assert_array_equal(hist[its], hist[its - 1])
    np.testing.assert_array_equal(np.asarray(res.coefficients), hist[its - 1])
    losses = np.asarray(res.loss_history)
    assert losses[its] == losses[its - 1] == float(res.loss)
    f, g = _rosenbrock_vg(res.coefficients)
    if l1 is not None:
        g = lbfgs_module._pseudo_gradient(res.coefficients, g, jnp.float32(l1))
        f = f + l1 * jnp.sum(jnp.abs(res.coefficients))
    np.testing.assert_allclose(float(res.loss), float(f), rtol=1e-6)
    np.testing.assert_allclose(float(res.gradient_norm), float(jnp.linalg.norm(g)), rtol=1e-5)
    gnorms = np.asarray(res.gradient_norm_history)
    assert gnorms[its] == gnorms[its - 1]

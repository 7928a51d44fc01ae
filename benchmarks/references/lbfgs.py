"""Plain batched L-BFGS, written from the configuration's statement of it.

The statement: history 10; direction by the two-loop recursion scaled by
s.y / y.y of the newest pair; backtracking by halves from step 1 (from
1 / |d| while no pair is held) until f(x + t d) <= f(x) + 1e-4 g.(t d), at
most 30 trials; a pair is kept when s.y > 1e-10; stop when |f - f_prev| <=
tol |f_0|, or |g| <= tol |g_0|, or the iteration limit, or a failed search
(which keeps the point before it).

`fun(W)` maps (E, D) coefficients of E independent problems to their (E,)
values and (E, D) gradients; every problem follows its own trajectory and
stops on its own. Python loops, float32, no program code.
"""

import jax.numpy as jnp

HISTORY = 10
ARMIJO = 1e-4
MAX_TRIALS = 30
CURVATURE_EPS = 1e-10


def _rowdot(a, b):
    return jnp.sum(a * b, axis=-1)


def _direction(g, S, Y, rho, k):
    """Two-loop recursion; problem e holds min(k[e], HISTORY) pairs, newest
    in slot (k[e] - 1) mod HISTORY."""
    E = g.shape[0]
    rows = jnp.arange(E)
    held = jnp.minimum(k, HISTORY)
    q = g
    alphas = []
    for i in range(HISTORY):
        slot = jnp.mod(k - 1 - i, HISTORY)
        s, y, r = S[rows, slot], Y[rows, slot], rho[rows, slot]
        a = jnp.where(i < held, r * _rowdot(s, q), 0.0)
        q = q - a[:, None] * y
        alphas.append(a)
    newest = jnp.mod(k - 1, HISTORY)
    sy = _rowdot(S[rows, newest], Y[rows, newest])
    yy = _rowdot(Y[rows, newest], Y[rows, newest])
    gamma = jnp.where((k > 0) & (yy != 0.0), sy / jnp.where(yy == 0.0, 1.0, yy), 1.0)
    gamma = jnp.where(gamma > 0.0, gamma, 1.0)
    r_vec = gamma[:, None] * q
    for i in reversed(range(HISTORY)):
        slot = jnp.mod(k - 1 - i, HISTORY)
        s, y, r = S[rows, slot], Y[rows, slot], rho[rows, slot]
        b = r * _rowdot(y, r_vec)
        r_vec = r_vec + s * jnp.where(i < held, alphas[i] - b, 0.0)[:, None]
    return -r_vec


def minimize(fun, w0, *, max_iterations, tolerance):
    E, D = w0.shape
    rows = jnp.arange(E)
    x = w0
    f, g = fun(x)
    f_init, g_init = f, jnp.linalg.norm(g, axis=-1)
    S = jnp.zeros((E, HISTORY, D), jnp.float32)
    Y = jnp.zeros((E, HISTORY, D), jnp.float32)
    rho = jnp.zeros((E, HISTORY), jnp.float32)
    k = jnp.zeros((E,), jnp.int32)
    running = g_init > 0.0
    evaluations = 1
    for iteration in range(1, max_iterations + 1):
        if not bool(jnp.any(running)):
            break
        d = _direction(g, S, Y, rho, k)
        d_norm = jnp.linalg.norm(d, axis=-1)
        t = jnp.where((k == 0) & (d_norm > 0.0), 1.0 / jnp.where(d_norm > 0.0, d_norm, 1.0), 1.0)
        searching = running
        found = jnp.zeros((E,), bool)
        x_try, f_try, g_try = x, f, g
        for _ in range(MAX_TRIALS):
            if not bool(jnp.any(searching)):
                break
            xt = x + t[:, None] * d
            ft, gt = fun(xt)
            evaluations += 1
            good = (ft <= f + ARMIJO * _rowdot(g, xt - x)) & jnp.isfinite(ft)
            x_try = jnp.where(searching[:, None], xt, x_try)
            f_try = jnp.where(searching, ft, f_try)
            g_try = jnp.where(searching[:, None], gt, g_try)
            found = found | (searching & good)
            t = jnp.where(searching & ~good, 0.5 * t, t)
            searching = searching & ~good
        s_vec, y_vec = x_try - x, g_try - g
        sy = _rowdot(s_vec, y_vec)
        keep = running & found & (sy > CURVATURE_EPS)
        slot = jnp.mod(k, HISTORY)
        S = S.at[rows, slot].set(jnp.where(keep[:, None], s_vec, S[rows, slot]))
        Y = Y.at[rows, slot].set(jnp.where(keep[:, None], y_vec, Y[rows, slot]))
        rho = rho.at[rows, slot].set(
            jnp.where(keep, 1.0 / jnp.where(keep, sy, 1.0), rho[rows, slot])
        )
        k = jnp.where(keep, k + 1, k)
        stop = (
            (jnp.abs(f_try - f) <= tolerance * jnp.abs(f_init))
            | (jnp.linalg.norm(g_try, axis=-1) <= tolerance * g_init)
            | (iteration >= max_iterations)
            | ~found
        )
        move = running & found
        x = jnp.where(move[:, None], x_try, x)
        f = jnp.where(move, f_try, f)
        g = jnp.where(move[:, None], g_try, g)
        running = running & ~stop
    return x, {"iterations": iteration, "evaluations": evaluations}

"""Optimization problems: config + objective + optimizer + variance.

Counterpart of photon-api optimization/ (GeneralizedLinearOptimizationProblem
.scala:38, DistributedOptimizationProblem.scala:46-213,
SingleNodeOptimizationProblem.scala:40-138). The reference splits distributed
vs single-node problems because their Data types differ (RDD vs Iterable);
here one pure `solve` serves both — the fixed effect calls it on the full
(sharded) batch, random effects vmap it over entity blocks. Variance
computation (:84-103): SIMPLE = 1/diag(H), FULL = diag(H^-1) via Cholesky.

`solve` is not jitted itself: it composes jitted kernels (minimize_lbfgs /
minimize_tron) and is safe to call inside jit/vmap contexts.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.containers import LabeledData
from photon_ml_tpu.data.sampling import down_sample
from photon_ml_tpu.ops import objective
from photon_ml_tpu.ops.pallas_glm import DispatchMode
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.optimize.common import OptResult
from photon_ml_tpu.optimize.config import CoordinateOptimizationConfig
from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs
from photon_ml_tpu.optimize.tron import minimize_tron
from photon_ml_tpu.types import OptimizerType, TaskType, VarianceComputationType

Array = jax.Array


def solve(
    loss: PointwiseLoss,
    data: LabeledData,
    config: CoordinateOptimizationConfig,
    w0: Array,
    norm: Optional[NormalizationContext] = None,
    use_pallas: Optional[DispatchMode] = None,
) -> OptResult:
    """Run the configured optimizer on one GLM problem.

    Mirrors GeneralizedLinearOptimizationProblem.run + OptimizerFactory
    dispatch: LBFGS (plain), OWLQN when L1/elastic (reference selects OWLQN
    inside LBFGS config when l1 > 0), LBFGSB via box constraints, TRON via
    Hessian-vector products.
    """
    l2 = config.l2_weight

    # Scopes name the objective's operations in a device trace (metadata
    # only): under `fe_solve` or `re_scan/<capacity>`, whichever called.
    def vg(w):
        with jax.named_scope("objective"):
            return objective.value_and_gradient(
                loss, w, data, norm, l2, use_pallas
            )

    opt = config.optimizer
    ot = opt.optimizer_type

    if ot == OptimizerType.TRON:
        if not loss.has_hessian:
            raise ValueError(
                f"{loss.name} has no Hessian; TRON requires TwiceDiffFunction "
                "(reference restricts smoothed hinge to LBFGS)"
            )
        def hvp(w, v):
            with jax.named_scope("hessian_vector"):
                return objective.hessian_vector(
                    loss, w, v, data, norm, l2, use_pallas
                )

        return minimize_tron(
            vg, hvp, w0, max_iterations=opt.max_iterations, tolerance=opt.tolerance
        )

    lower = upper = None
    if opt.box_constraints is not None:
        lower, upper = opt.box_constraints
    # The L1-vs-plain decision must be static (reg weights may be traced):
    # it follows the regularization *type*, as in OptimizerFactory.
    from photon_ml_tpu.types import RegularizationType

    use_l1 = ot == OptimizerType.OWLQN or config.regularization.reg_type in (
        RegularizationType.L1,
        RegularizationType.ELASTIC_NET,
    )
    l1 = config.l1_weight
    return minimize_lbfgs(
        vg,
        w0,
        max_iterations=opt.max_iterations,
        tolerance=opt.tolerance,
        l1_weight=l1 if use_l1 else None,
        lower_bounds=lower,
        upper_bounds=upper,
    )


def solve_with_sampling(
    loss: PointwiseLoss,
    data: LabeledData,
    config: CoordinateOptimizationConfig,
    w0: Array,
    norm: Optional[NormalizationContext] = None,
    *,
    task: TaskType,
    key: Optional[jax.Array] = None,
    use_pallas: Optional[DispatchMode] = None,
) -> OptResult:
    """DistributedOptimizationProblem.runWithSampling (:144-170): apply the
    coordinate's DownSampler before optimizing when rate < 1."""
    if config.down_sampling_rate < 1.0:
        if key is None:
            raise ValueError("down-sampling requires a PRNG key")
        data = down_sample(key, data, config.down_sampling_rate, task)
    return solve(loss, data, config, w0, norm, use_pallas)


def compute_variances(
    loss: PointwiseLoss,
    data: LabeledData,
    config: CoordinateOptimizationConfig,
    w: Array,
    norm: Optional[NormalizationContext] = None,
) -> Optional[Array]:
    """Coefficient variances at the optimum
    (DistributedOptimizationProblem.scala:84-103):
      SIMPLE: 1 / diag(H)  — elementwise inverse of the Hessian diagonal
      FULL:   diag(H^-1)   — via Cholesky factorization of the full Hessian
    Returns None for NONE.
    """
    vc = config.variance_computation
    if vc == VarianceComputationType.NONE:
        return None
    l2 = config.l2_weight
    if vc == VarianceComputationType.SIMPLE:
        diag = objective.hessian_diagonal(loss, w, data, norm, l2)
        return jnp.where(jnp.abs(diag) > 0.0, 1.0 / diag, jnp.inf)
    H = objective.hessian_matrix(loss, w, data, norm, l2)
    # diag(H^-1) via Cholesky solve against the identity.
    chol = jnp.linalg.cholesky(H)
    inv = jax.scipy.linalg.cho_solve((chol, True), jnp.eye(H.shape[0], dtype=H.dtype))
    return jnp.diagonal(inv)

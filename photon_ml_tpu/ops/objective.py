"""GLM objective: weighted loss value / gradient / Hessian products.

TPU-native collapse of the reference's aggregator family
(ValueAndGradientAggregator.scala:34-280, HessianVectorAggregator.scala:23-173,
HessianDiagonalAggregator.scala, HessianMatrixAggregator.scala) and of the
objective-function hierarchy that routes to them
(DistributedGLMLossFunction.scala:48-147, SingleNodeGLMLossFunction.scala:165).

Where the reference runs a hand-written per-datum hot loop inside
RDD.treeAggregate, here each quantity is a closed-form vectorized expression
over the whole (sharded) batch:

    z   = X (w*factor) - shifts.(w*factor) + offset            margins
    f   = sum_i weight_i * l(z_i, y_i)  (+ lambda/2 ||w||^2)
    g   = factor * (X^T u - (sum u) shifts) + lambda w,  u = weight * l'(z)
    Hv  = factor * (X^T r - (sum r) shifts) + lambda v,
          r = weight * l''(z) * ((X (v*factor)) - shifts.(v*factor))

Normalization is folded in as coefficient algebra exactly like the reference
(see ops/normalization.py) so the data is never rewritten. When data is
sharded over a device mesh, the sums above become XLA all-reduces over ICI —
the treeAggregate equivalent — inserted automatically under jit/shard_map.

All functions are pure and vmappable: the same code serves the fixed effect
(one big problem, data-parallel) and random effects (vmap over thousands of
small entity problems).

The loss is a weighted *sum*, not mean, matching the reference — so
regularization weights are directly comparable.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.bucketed import BucketedSparseFeatures
from photon_ml_tpu.data.containers import LabeledData, SparseFeatures
from photon_ml_tpu.ops import pallas_glm, pallas_sparse
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext

Array = jax.Array


def _eff(w: Array, norm: Optional[NormalizationContext]) -> Tuple[Array, Array]:
    """(effective coefficients, scalar margin shift)."""
    if norm is None or norm.is_identity:
        return w, jnp.zeros((), dtype=w.dtype)
    return norm.effective_coefficients(w), norm.margin_shift(w)


def margin_params(
    w: Array, norm: Optional[NormalizationContext]
) -> Tuple[Array, Array]:
    """Public view of `_eff` for scoring-side consumers (the transformer's
    row-stable dense margin path and the serving engine): the effective
    coefficient vector plus the scalar margin shift normalization folds in."""
    return _eff(w, norm)


def _matvec(features, w_eff: Array) -> Array:
    if isinstance(features, BucketedSparseFeatures):
        if pallas_sparse.should_use(features):
            return pallas_sparse.matvec(
                features, w_eff, interpret=pallas_glm.FORCE_INTERPRET
            )
        return pallas_sparse.matvec_xla(features, w_eff)
    if isinstance(features, SparseFeatures):
        return features.matvec(w_eff)
    return features @ w_eff


def _rmatvec(features, u: Array) -> Array:
    if isinstance(features, BucketedSparseFeatures):
        if pallas_sparse.should_use(features):
            return pallas_sparse.rmatvec(
                features, u, interpret=pallas_glm.FORCE_INTERPRET
            )
        return pallas_sparse.rmatvec_xla(features, u)
    if isinstance(features, SparseFeatures):
        return features.rmatvec(u)
    return u @ features


def _sq_rmatvec(features, u: Array) -> Array:
    """sum_i u_i * x_i^2 per feature (Hessian diagonals)."""
    if isinstance(features, BucketedSparseFeatures):
        if pallas_sparse.should_use(features):
            return pallas_sparse.rmatvec(
                features, u, interpret=pallas_glm.FORCE_INTERPRET, square=True
            )
        return pallas_sparse.rmatvec_xla(features, u, square=True)
    if isinstance(features, SparseFeatures):
        return features.sq_rmatvec(u)
    return u @ jnp.square(features)


def _needs_row_sum(norm: Optional[NormalizationContext]) -> bool:
    """Does the normalization's gradient algebra need sum_i u_i (shifts)?"""
    return norm is not None and not norm.is_identity and norm.shifts is not None


def _value_gradient_sums(loss, w_eff, shift, data: LabeledData, row_sum: bool):
    """(value, X^T u, sum u or None) of the rows in `data`, through XLA."""
    z = _matvec(data.features, w_eff) + shift + data.offsets
    val = jnp.sum(data.weights * loss.loss(z, data.labels))
    u = data.weights * loss.d1(z, data.labels)
    return val, _rmatvec(data.features, u), jnp.sum(u) if row_sum else None


def _hessian_vector_sums(loss, w_eff, shift, v_eff, v_shift, data: LabeledData, row_sum: bool):
    """(X^T r, sum r or None) of the rows in `data`, through XLA."""
    z = _matvec(data.features, w_eff) + shift + data.offsets
    d2 = loss.d2(z, data.labels)
    q = _matvec(data.features, v_eff) + v_shift
    r = data.weights * d2 * q
    return _rmatvec(data.features, r), jnp.sum(r) if row_sum else None


def _summed_over_samples(dispatch: pallas_glm.ShardedDispatch, sums, replicated, data: LabeledData):
    """`sums(*replicated, data)` on each device's own rows of a sample-sharded
    ELL shard, and the devices' results added ONCE: every device runs the
    one-device plane loops (table and accumulator in VMEM) into its own
    partial, and one `psum` reduces value and (d,) gradient together. Left to
    the partitioner, the loop's replicated accumulator is reduced after every
    plane's scatter-add: K reductions of d floats an evaluation."""
    from jax.sharding import PartitionSpec as P

    from photon_ml_tpu.parallel.mesh import shard_map_compat

    axis = dispatch.axis
    rows = P(axis)
    # The shard's (N, K) planes are cut by sample; what else it carries (the
    # planes' least ids, where it is annotated) is every device's.
    features = jax.tree.map(lambda a: P(axis, None) if a.ndim == 2 else P(), data.features)

    def per_device(replicated, features, labels, offsets, weights):
        partial = sums(*replicated, LabeledData(features, labels, offsets, weights))
        with jax.named_scope("allreduce"):
            return jax.lax.psum(partial, axis)

    return shard_map_compat(
        per_device,
        mesh=dispatch.mesh,
        in_specs=(P(), features, rows, rows, rows),
        out_specs=P(),
    )(replicated, data.features, data.labels, data.offsets, data.weights)


def compute_margins(
    w: Array, data: LabeledData, norm: Optional[NormalizationContext] = None
) -> Array:
    """z_i = x_i.(w*factor) + shift-term + offset_i (LabeledPoint.computeMargin)."""
    w_eff, shift = _eff(w, norm)
    return _matvec(data.features, w_eff) + shift + data.offsets


def value(
    loss: PointwiseLoss,
    w: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
) -> Array:
    z = compute_margins(w, data, norm)
    val = jnp.sum(data.weights * loss.loss(z, data.labels))
    return val + 0.5 * l2 * jnp.dot(w, w)


def value_and_gradient(
    loss: PointwiseLoss,
    w: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
    use_pallas: Optional[pallas_glm.DispatchMode] = None,
) -> Tuple[Array, Array]:
    """One fused pass: margins computed once, shared by value and gradient.

    Replaces ValueAndGradientAggregator.calculateValueAndGradient + its
    treeAggregate (lines 137-161, 240-255 of the reference file).

    On TPU, large dense problems take the fused Pallas path
    (ops/pallas_glm.py) that streams X from HBM once for both matmuls; the
    sparse path and small (vmapped per-entity) problems stay on XLA.

    `use_pallas` forces the decision: callers that know their data placement
    (the fixed-effect coordinate decides once at construction on the
    concrete array) pass True/False so the trace-time heuristic — which
    cannot see sharding or vmap context — is bypassed. None = auto.
    """
    w_eff, shift = _eff(w, norm)
    # An explicit use_pallas=False (the caller's escape hatch for contexts
    # the trace-time heuristics cannot see) disables the fused sparse path
    # too; wide problems whose tiles exceed the fused kernel's VMEM budget
    # fall through to the grouped matvec/rmatvec composition below.
    fused_sparse = (
        use_pallas is not False
        and isinstance(data.features, BucketedSparseFeatures)
        and pallas_sparse.should_use(data.features)
        and pallas_sparse.fused_feasible(data.features)
    )
    if use_pallas is None and not fused_sparse:
        use_pallas = pallas_glm.should_use(data.features, w_eff)
    if fused_sparse:
        # Sparse fused path: one stream over the bucketed entries computes
        # value, u and the gradient together (pallas_sparse._fused_kernel) —
        # same raw-sum contract as the dense fused kernel below. The
        # per-level layout rides in the features pytree (level1 may be
        # row-aligned per data/bucketed.choose_layout, level2 is always
        # grouped): the kernels branch per level, so no dispatch decision
        # is needed here beyond feasibility.
        val, g, sum_u = pallas_sparse.fused_value_gradient_sums(
            loss, w_eff, shift, data.features, data.labels, data.offsets,
            data.weights, interpret=pallas_glm.FORCE_INTERPRET,
        )
    elif isinstance(use_pallas, pallas_glm.ShardedDispatch) and isinstance(
        data.features, SparseFeatures
    ):
        val, g, sum_u = _summed_over_samples(
            use_pallas,
            lambda w_, s_, d_: _value_gradient_sums(loss, w_, s_, d_, _needs_row_sum(norm)),
            (w_eff, shift), data,
        )
    elif isinstance(use_pallas, pallas_glm.ShardedDispatch):
        val, g, sum_u = pallas_glm.sharded_value_gradient_sums(
            loss, w_eff, shift, data.features, data.labels, data.offsets,
            data.weights, mesh=use_pallas.mesh, axis=use_pallas.axis,
            interpret=pallas_glm.FORCE_INTERPRET, column_major=data.column_major,
        )
    elif use_pallas:
        val, g, sum_u = pallas_glm.value_gradient_sums(
            loss, w_eff, shift, data.features, data.labels, data.offsets,
            data.weights, interpret=pallas_glm.FORCE_INTERPRET,
            column_major=data.column_major,
        )
    else:
        val, g, sum_u = _value_gradient_sums(
            loss, w_eff, shift, data, _needs_row_sum(norm)
        )
    if norm is not None and not norm.is_identity:
        if norm.shifts is not None:
            g = g - sum_u * norm.shifts
        if norm.factors is not None:
            g = g * norm.factors
    return val + 0.5 * l2 * jnp.dot(w, w), g + l2 * w


def gradient(
    loss: PointwiseLoss,
    w: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
) -> Array:
    return value_and_gradient(loss, w, data, norm, l2)[1]


def hessian_vector(
    loss: PointwiseLoss,
    w: Array,
    v: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
    use_pallas: Optional[pallas_glm.DispatchMode] = None,
) -> Array:
    """Gauss-Newton/Hessian product H(w) v (HessianVectorAggregator.scala:23-142).

    Exact for the GLM losses here (their Hessian is X^T diag(weight*l'') X in
    the normalized space).

    On TPU, large dense problems take the fused Pallas path: [w|v] is packed
    into one [D, 2] right-hand side so both forward matvecs and the backward
    contraction run in a single pass over X (ops/pallas_glm.py).
    """
    w_eff, shift = _eff(w, norm)
    v_eff, v_shift = _eff(v, norm)
    if use_pallas is None:
        use_pallas = pallas_glm.should_use(data.features, w_eff)
    if isinstance(use_pallas, pallas_glm.ShardedDispatch) and isinstance(
        data.features, SparseFeatures
    ):
        hv, sum_r = _summed_over_samples(
            use_pallas,
            lambda w_, s_, v_, vs_, d_: _hessian_vector_sums(
                loss, w_, s_, v_, vs_, d_, _needs_row_sum(norm)
            ),
            (w_eff, shift, v_eff, v_shift), data,
        )
    elif isinstance(use_pallas, pallas_glm.ShardedDispatch):
        hv, sum_r = pallas_glm.sharded_hessian_vector_sums(
            loss, w_eff, shift, v_eff, v_shift, data.features, data.labels,
            data.offsets, data.weights, mesh=use_pallas.mesh,
            axis=use_pallas.axis, interpret=pallas_glm.FORCE_INTERPRET,
            column_major=data.column_major,
        )
    elif use_pallas:
        hv, sum_r = pallas_glm.hessian_vector_sums(
            loss, w_eff, shift, v_eff, v_shift, data.features, data.labels,
            data.offsets, data.weights, interpret=pallas_glm.FORCE_INTERPRET,
            column_major=data.column_major,
        )
    else:
        hv, sum_r = _hessian_vector_sums(
            loss, w_eff, shift, v_eff, v_shift, data, _needs_row_sum(norm)
        )
    if norm is not None and not norm.is_identity:
        if norm.shifts is not None:
            hv = hv - sum_r * norm.shifts
        if norm.factors is not None:
            hv = hv * norm.factors
    return hv + l2 * v


def hessian_diagonal(
    loss: PointwiseLoss,
    w: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
) -> Array:
    """diag H = factor^2 * sum_i c_i (x_ij - s_j)^2 + lambda, c = weight * l''.

    (HessianDiagonalAggregator.scala:96-102; used for SIMPLE variance.)
    Expanded as sum c x^2 - 2 s (sum c x) + s^2 (sum c) so the sparse path
    never densifies.
    """
    w_eff, shift = _eff(w, norm)
    z = _matvec(data.features, w_eff) + shift + data.offsets
    c = data.weights * loss.d2(z, data.labels)
    feats = data.features
    sq = _sq_rmatvec(feats, c)
    lin = _rmatvec(feats, c)
    diag = sq
    if norm is not None and norm.shifts is not None:
        s = norm.shifts
        diag = sq - 2.0 * s * lin + jnp.square(s) * jnp.sum(c)
    if norm is not None and norm.factors is not None:
        diag = diag * jnp.square(norm.factors)
    return diag + l2


def hessian_matrix(
    loss: PointwiseLoss,
    w: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
) -> Array:
    """Full D x D Hessian (HessianMatrixAggregator.scala:96-102; FULL variance).

    Densifies sparse features — intended for modest D (the reference holds the
    same D x D Breeze matrix on the driver).
    """
    w_eff, shift = _eff(w, norm)
    z = _matvec(data.features, w_eff) + shift + data.offsets
    c = data.weights * loss.d2(z, data.labels)
    feats = data.features
    if isinstance(feats, BucketedSparseFeatures):
        X = pallas_sparse.to_dense_xla(feats)
    elif isinstance(feats, SparseFeatures):
        X = feats.to_dense()
    else:
        X = feats
    if norm is not None and norm.shifts is not None:
        X = X - norm.shifts
    H = (X * c[:, None]).T @ X
    if norm is not None and norm.factors is not None:
        H = H * jnp.outer(norm.factors, norm.factors)
    return H + l2 * jnp.eye(w.shape[0], dtype=w.dtype)
